"""Test harness: force an 8-device virtual CPU mesh before any JAX use.

The reference tests multi-node behavior without a cluster by faking the
seams (SURVEY.md §4: envtest for the k8s API, gomock for the kernel). The
TPU analog: fake the chips — XLA's host platform exposes N virtual CPU
devices, so every sharding/collective path runs in CI with no TPU attached.
bench.py and chip_smoke.py do NOT import this and run on real hardware.

The suite runs on the CPU backend: the driver's command sets
``JAX_PLATFORMS=cpu``, and the setdefault below does the same for a bare
``pytest tests/`` — also for the child processes tests start — on a
machine that has a chip (there a chip belongs to one process at a time,
and the suite starts many).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


# Every test starts from fresh exporter/metrics singletons: modules used
# to carry identical per-file autouse fixtures for this (review finding);
# the reset is cheap and global state bleed between tests is never wanted.
import pytest  # noqa: E402

from retina_tpu.exporter import render_exposition as _real_render  # noqa: E402
from retina_tpu.exporter import reset_for_tests as _reset_exporter  # noqa: E402
from retina_tpu.metrics import reset_for_tests as _reset_metrics  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_metric_singletons():
    _reset_exporter()
    _reset_metrics()
    yield


@pytest.fixture
def long_parks(monkeypatch):
    """Moves the safety bound of the feed path's idle waits (1 s) out
    of reach, so that a test can tell a signalled wake from it: a lost
    wake-up then hangs the test instead of costing a second. The feed
    loop keeps its own bound (its stop is not signalled)."""
    import retina_tpu.engine as engine_mod
    import retina_tpu.parallel.feed as feed_mod

    monkeypatch.setattr(feed_mod, "PARK_MAX_S", 60.0)
    monkeypatch.setattr(engine_mod, "PARK_MAX_S", 60.0)


class CountingRender:
    """Stand-in for the exporter's two renderers
    (``exporter.render_exposition`` for the default registry,
    ``exporter.render_rows`` for the pod-level one): the real bytes,
    counted by registry."""

    def __init__(self):
        self.calls: list = []

    def counting(self, real):
        def render(registry) -> bytes:
            self.calls.append(registry)
            return real(registry)

        return render

    def count(self, registry) -> int:
        return sum(1 for r in self.calls if r is registry)


@pytest.fixture
def counting_render(monkeypatch):
    """Counts the exporter's renders by registry from here on."""
    import retina_tpu.exporter as exporter_mod

    render = CountingRender()
    for name in ("render_exposition", "render_rows"):
        monkeypatch.setattr(exporter_mod, name,
                            render.counting(getattr(exporter_mod, name)))
    return render


@pytest.fixture
def fresh_exposition():
    """What rendering both registries of an exporter afresh gives (by
    the real renderer, uncounted): every family sample by sample
    through ``collect()``, a row table's too."""

    def fresh(ex) -> bytes:
        return (_real_render(ex.default_registry)
                + _real_render(ex.advanced_registry))

    return fresh
