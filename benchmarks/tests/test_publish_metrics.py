"""``publish_cpu_ms_per_s`` and ``publish_changed_pct`` (PR 32) on a
hand-made load. Run by hand with the benchmark's other tests, not part
of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(BENCH, "layer_metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import poller  # noqa: E402
import publish_changed_pct as pct  # noqa: E402
import publish_cpu_ms_per_s as cpu  # noqa: E402


def _load(samples):
    """Window [10, 60); ``samples``: (sent, counters or None)."""
    return types.SimpleNamespace(t_open=10.0, t_close=60.0, scrapes=[
        {"sent": t, "done": t + 0.01, "ok": True, "c": c or {}}
        for t, c in samples])


def _c(cpu_s, rows, changed):
    return {cpu.CPU: cpu_s, pct.ROWS: rows, pct.CHANGED: changed}


def test_the_entries_are_appended_and_list_no_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    names = [m["name"] for m in doc["per_layer"]]
    assert names[-2:] == ["publish_cpu_ms_per_s", "publish_changed_pct"]
    for entry, reader in zip(doc["per_layer"][-2:], (cpu, pct)):
        assert entry["unit"] == reader.UNIT
        assert entry["layer"] == "snapshot + publish"
        assert entry["moves"] == "host_cpu_us_per_event"
        assert "workloads" not in entry
        for w in doc["workloads"]:
            assert entry in harness.metrics_of(doc, "per_layer", w["name"])


def test_between_the_windows_first_and_last_scrape():
    run = _load([(9.0, _c(0.5, 70e3, 70e3)), (10.0, _c(0.6, 105e3, 90e3)),
                 (35.0, _c(2.0, 900e3, 500e3)),
                 (59.0, _c(4.03, 1785e3, 1098e3)),
                 (61.0, _c(9.0, 9e6, 9e6))])
    assert cpu.read(run) == pytest.approx(1e3 * 3.43 / 49.0)
    assert pct.read(run) == pytest.approx(60.0)


@pytest.mark.parametrize("samples", [
    [(10.0, _c(0.0, 0.0, 0.0)), (59.0, _c(0.0, 0.0, 0.0))],  # no series
    [(10.0, None), (59.0, None)],  # a poller not asked for them
    [(35.0, _c(1.0, 10.0, 5.0))],  # one scrape in the window
    [],
])
def test_a_program_without_the_counters_reads_nothing(samples):
    assert cpu.read(_load(samples)) is None
    assert pct.read(_load(samples)) is None


def test_the_poller_keeps_the_two_row_counters_apart():
    body = (
        b"networkobservability_tpu_publish_rows_counter_total 40.0\n"
        b"networkobservability_tpu_publish_rows_changed_counter_total 10.0\n"
        b"networkobservability_tpu_publish_cpu_seconds_counter_total"
        b'{part="series"} 0.5\n'
        b"networkobservability_tpu_publish_cpu_seconds_counter_total"
        b'{part="render"} 0.25\n')
    names = tuple(poller.PREFIX + n.encode()
                  for n in (pct.ROWS, pct.CHANGED, cpu.CPU))
    assert poller.series_sum(body, names) == dict(
        zip(names, (40.0, 10.0, 0.75)))
