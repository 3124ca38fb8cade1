"""Metrics module tests: CRD validation, reconcile→registry reset, metric
objects publishing labeled series from synthetic snapshots — mirroring the
reference's pkg/module/metrics/*_test.go (synthetic flows → asserted
Prometheus label/value outcomes, SURVEY.md §4)."""

import threading

import numpy as np
import pytest

from retina_tpu.common import RetinaEndpoint
from retina_tpu.config import Config
from retina_tpu.controllers.cache import Cache
from retina_tpu.crd.types import (
    Capture,
    CaptureOutput,
    CaptureSpec,
    CaptureTarget,
    MetricsConfiguration,
    MetricsContextOptions,
    MetricsNamespaces,
    MetricsSpec,
    ValidationError,
)
from retina_tpu.events.schema import ip_to_u32
from retina_tpu.exporter import get_exporter
from retina_tpu.module.metrics_module import MetricsModule


# -------------------------------------------------------------- CRD types
def test_metrics_configuration_validation():
    MetricsConfiguration.default().validate()
    with pytest.raises(ValidationError):
        MetricsSpec(
            context_options=[MetricsContextOptions("bogus")]
        ).validate()
    with pytest.raises(ValidationError):
        MetricsSpec(
            context_options=[
                MetricsContextOptions("forward"),
                MetricsContextOptions("forward"),
            ]
        ).validate()
    with pytest.raises(ValidationError):
        MetricsNamespaces(include=["a"], exclude=["b"]).validate()


def test_metrics_configuration_from_yaml():
    conf = MetricsConfiguration.from_yaml(
        """
metadata: {name: custom}
spec:
  contextOptions:
    - metricName: forward
      sourceLabels: [podname, namespace]
    - metricName: drop
  namespaces:
    exclude: [kube-system]
"""
    )
    assert conf.name == "custom"
    assert [c.metric_name for c in conf.spec.context_options] == [
        "forward", "drop",
    ]
    assert conf.spec.namespaces.admits("default")
    assert not conf.spec.namespaces.admits("kube-system")


def test_capture_validation():
    cap = Capture(
        name="c1",
        spec=CaptureSpec(
            target=CaptureTarget(node_names=["node1"]),
            output=CaptureOutput(host_path="/tmp/captures"),
        ),
    )
    cap.validate()
    with pytest.raises(ValidationError):
        Capture(name="c2", spec=CaptureSpec()).validate()  # no target/output
    with pytest.raises(ValidationError):
        CaptureTarget(node_names=["n"], pod_selector={"a": "b"}).validate()
    with pytest.raises(ValidationError):
        CaptureSpec(
            target=CaptureTarget(node_names=["n"]),
            output=CaptureOutput(host_path="/x"),
            duration_s=0,
        ).validate()


# ----------------------------------------------------- module + objects
class FakeEngine:
    """Synthetic snapshot provider (the device-state test double)."""

    def __init__(self, n_pods=16, n_reasons=16):
        z = np.zeros
        self.snap = {
            "pod_forward": z((n_pods, 2, 2), np.uint32),
            "pod_drop": z((n_pods, n_reasons, 2), np.uint32),
            "pod_tcpflags": z((n_pods, 8), np.uint32),
            "pod_dns": z((n_pods, 16, 2), np.uint32),
            "pod_retrans": z((n_pods,), np.uint32),
            "node_counters": z((2, 2), np.uint32),
            "totals": z((8,), np.uint32),
            "lat_hist": z((16,), np.uint32),
            "hll_flows": np.array([42.0]),
            "hll_src_per_reason": z((16,), np.float32),
            "hll_src_per_pod": z((n_pods,), np.float32),
            "flow_hh": {"keys": z((1, 8, 4), np.uint32),
                        "counts": z((1, 8), np.uint32)},
            "svc_hh": {"keys": z((1, 8, 2), np.uint32),
                       "counts": z((1, 8), np.uint32)},
            "dns_hh": {"keys": z((1, 8, 1), np.uint32),
                       "counts": z((1, 8), np.uint32)},
            "active_conns": np.uint32(0),
        }

    def snapshot(self, max_age_s: float = 0.5):
        return self.snap


def build_module(engine, ns_exclude=()):
    cache = Cache()
    cache.update_endpoint(
        RetinaEndpoint(name="web-0", namespace="default",
                       ips=("10.0.0.1",),
                       owner_refs=(("StatefulSet", "web"),))
    )
    cache.update_endpoint(
        RetinaEndpoint(name="sys-0", namespace="kube-system",
                       ips=("10.0.0.2",))
    )
    cfg = Config()
    mm = MetricsModule(cfg, engine=engine, cache=cache)
    conf = MetricsConfiguration.default()
    conf.spec.namespaces = MetricsNamespaces(exclude=list(ns_exclude))
    mm.reconcile(conf)
    return mm, cache


def adv_text() -> str:
    from prometheus_client.exposition import generate_latest

    return generate_latest(get_exporter().advanced_registry).decode()


def test_forward_and_drop_publish_with_labels():
    eng = FakeEngine()
    mm, cache = build_module(eng)
    i_web = cache.get_index("default/web-0")
    eng.snap["pod_forward"][i_web, 0] = (100, 5000)  # ingress pkts, bytes
    eng.snap["pod_drop"][i_web, 1, 0] = 7  # iptable_rule_drop pkts
    mm.publish_once()
    text = adv_text()
    assert (
        'networkobservability_adv_forward_count{direction="ingress",'
        'namespace="default",podname="web-0",workload_kind="web"} 100.0'
        in text
    )
    assert 'reason="iptable_rule_drop"' in text and "} 7.0" in text


def test_namespace_exclusion_suppresses_series():
    eng = FakeEngine()
    mm, cache = build_module(eng, ns_exclude=["kube-system"])
    i_sys = cache.get_index("kube-system/sys-0")
    eng.snap["pod_forward"][i_sys, 1] = (50, 2500)
    mm.publish_once()
    assert "sys-0" not in adv_text()


def test_reconcile_resets_advanced_registry():
    eng = FakeEngine()
    mm, cache = build_module(eng)
    i_web = cache.get_index("default/web-0")
    eng.snap["pod_forward"][i_web, 0] = (1, 1)
    mm.publish_once()
    assert "adv_forward_count" in adv_text()
    # Reconcile down to drop-only: forward family must vanish.
    conf = MetricsConfiguration(
        spec=MetricsSpec(context_options=[MetricsContextOptions("drop")])
    )
    mm.reconcile(conf)
    assert "adv_forward_count" not in adv_text()
    assert mm.enabled_metrics() == ["drop"]


def test_flows_and_distinct_sources_publish():
    eng = FakeEngine()
    # one heavy flow candidate on device 0 slot 0
    eng.snap["flow_hh"]["keys"][0, 0, :] = (
        ip_to_u32("10.0.0.9"), ip_to_u32("10.0.0.1"),
        (1234 << 16) | 80, 6,
    )
    eng.snap["flow_hh"]["counts"][0, 0] = 999
    eng.snap["hll_src_per_pod"][1] = 12.3
    mm, cache = build_module(eng)
    mm.publish_once()
    text = adv_text()
    assert "networkobservability_sketch_distinct_flows 42.0" in text
    assert ('src_ip="10.0.0.9"' in text and 'dst_port="80"' in text
            and "} 999.0" in text)
    assert "distinct_sources_per_pod" in text


def test_dirty_pod_sync_to_filtermanager():
    from retina_tpu.managers.filtermanager import FilterManager
    from retina_tpu.pubsub import PubSub

    ps = PubSub()
    fm = FilterManager()
    cache = Cache(ps)
    MetricsModule(Config(), engine=FakeEngine(), cache=cache,
                  filtermanager=fm, pubsub=ps)
    done = threading.Event()
    orig = fm.add_ips

    def traced(*a, **k):
        orig(*a, **k)
        done.set()

    fm.add_ips = traced
    cache.update_endpoint(
        RetinaEndpoint(name="p", namespace="d", ips=("10.1.2.3",))
    )
    assert done.wait(2.0)
    assert fm.has_ip(ip_to_u32("10.1.2.3"))
    ps.shutdown()


def test_publish_cycle_is_a_span_tree_and_observes_its_lag():
    """One publish_once: pod_publish with series_publish under it, the
    watermark's lag observed once and events_included on the span."""
    from retina_tpu.metrics import get_metrics
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    eng = FakeEngine()
    eng.snap["events_in"] = 1234
    seen = []

    def publish_lag_s(snap):
        seen.append(snap["events_in"])
        return 2.5, snap["events_in"]

    eng.publish_lag_s = publish_lag_s
    mm, _ = build_module(eng)
    hist = get_metrics().publish_lag_seconds
    s0 = hist._sum.get()
    old = get_recorder()
    rec = initialize_recorder(capacity=64)
    try:
        mm.publish_once()
        spans = {s["stage"]: s for s in rec.spans()}
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)
    assert seen == [1234]
    assert hist._sum.get() - s0 == 2.5
    pub = spans[mn.STAGE_POD_PUBLISH]
    assert pub["args"] == {"events_included": 1234, "lag_ms": 2500.0}
    assert spans[mn.STAGE_SERIES_PUBLISH]["parent"] == pub["id"]
    assert pub["trace_id"] == spans[mn.STAGE_SERIES_PUBLISH]["trace_id"] > 0


def test_publish_without_a_watermark_still_publishes():
    """An engine with no sink (a test double) has no lag to observe:
    the cycle publishes and its span carries no watermark."""
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    eng = FakeEngine()
    mm, cache = build_module(eng)
    eng.snap["pod_forward"][cache.get_index("default/web-0"), 0] = (3, 30)
    old = get_recorder()
    rec = initialize_recorder(capacity=64)
    try:
        mm.publish_once()
        (pub,) = [s for s in rec.spans()
                  if s["stage"] == mn.STAGE_POD_PUBLISH]
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)
    assert pub["args"] == {}
    assert 'podname="web-0"' in adv_text()


# --------------------------- the publisher declares its cycle complete
def test_publish_once_declares_once_per_cycle_after_series_publish():
    """(h) One cycle, one declaration, made when ``series_publish`` has
    closed and ``pod_publish`` is still open; the values it covers are
    in the registry by then."""
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    eng = FakeEngine()
    mm, cache = build_module(eng)
    ex = get_exporter()
    eng.snap["pod_forward"][cache.get_index("default/web-0"), 0] = (3, 30)
    declared = []
    real = ex.advanced_published
    old = get_recorder()
    rec = initialize_recorder(capacity=64)

    def spy() -> None:
        declared.append((
            [s["stage"] for s in rec.spans()],
            'workload_kind="web"} 3.0' in adv_text(),
        ))
        real()

    ex.advanced_published = spy
    try:
        gen0 = ex._adv_gen
        mm.publish_once()
        assert ex._adv_gen == gen0 + 1 and ex._adv_published
        mm.publish_once()
        stages = [s["stage"] for s in rec.spans()]
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)
    assert len(declared) == 2
    closed, written = declared[0]
    assert written
    assert mn.STAGE_SERIES_PUBLISH in closed
    assert mn.STAGE_POD_PUBLISH not in closed  # still open
    assert stages.count(mn.STAGE_POD_PUBLISH) == 2


def test_gathers_between_two_publishes_render_pod_level_once(
        counting_render):
    """N gathers between two publish cycles: the advanced registry is
    rendered once, the default registry N times; the next publish brings
    one more render, and its values."""
    eng = FakeEngine()
    mm, cache = build_module(eng)
    i_web = cache.get_index("default/web-0")
    ex = get_exporter()
    n = 6
    for value in (100, 101):
        eng.snap["pod_forward"][i_web, 0] = (value, 50 * value)
        mm.publish_once()
        bodies = [ex.gather_text() for _ in range(n)]
        want = f'podname="web-0",workload_kind="web"}} {value}.0'.encode()
        assert all(want in b for b in bodies)
    assert counting_render.count(ex.advanced_registry) == 2
    assert counting_render.count(ex.default_registry) == 2 * n


@pytest.mark.parametrize("change", [
    "publish", "publish_new_pod", "reconcile", "reset_advanced",
])
def test_gather_equals_fresh_render_after_every_change(
        change, fresh_exposition):
    """After every publish cycle, a reconcile and a bare reset,
    gather_text() is byte for byte what rendering both registries
    afresh gives, on the gather that renders and on those that reuse;
    a reconcile invalidates at once."""
    eng = FakeEngine()
    mm, cache = build_module(eng)
    ex = get_exporter()
    i_web = cache.get_index("default/web-0")
    for cycle in range(3):
        eng.snap["pod_forward"][i_web, 0] = (cycle + 1, 10 * cycle)
        eng.snap["pod_drop"][i_web, 1, 0] = cycle
        mm.publish_once()
        for _ in range(2):
            assert ex.gather_text() == fresh_exposition(ex)
    assert ex.gather()[1] == "reused"
    if change == "publish":
        eng.snap["hll_flows"] = np.array([77.0])
        mm.publish_once()
        assert b"sketch_distinct_flows 77.0" in ex.gather_text()
    elif change == "publish_new_pod":
        cache.update_endpoint(RetinaEndpoint(
            name="db-0", namespace="default", ips=("10.0.0.3",)))
        eng.snap["pod_forward"][
            cache.get_index("default/db-0"), 1] = (9, 900)
        mm.publish_once()
        assert b'podname="db-0"' in ex.gather_text()
    elif change == "reconcile":
        mm.reconcile(MetricsConfiguration(spec=MetricsSpec(
            context_options=[MetricsContextOptions("drop")])))
        assert b"adv_forward_count" not in ex.gather_text()
        mm.publish_once()
    else:
        ex.reset_advanced()
        assert b"adv_forward_count" not in ex.gather_text()
    for _ in range(3):
        assert ex.gather_text() == fresh_exposition(ex)


def test_metric_object_that_raises_still_declares_the_cycle(
        fresh_exposition):
    """(h) A metric object that raises in ``publish`` must not hold its
    siblings' new values back: the declaration is in a ``finally``,
    which also covers what the per-object guard does not catch."""
    eng = FakeEngine()
    mm, cache = build_module(eng)
    ex = get_exporter()
    i_web = cache.get_index("default/web-0")
    eng.snap["pod_forward"][i_web, 0] = (5, 50)
    mm.publish_once()
    assert b'workload_kind="web"} 5.0' in ex.gather_text()
    assert ex.gather()[1] == "reused"

    def boom(snap, ctx):
        raise RuntimeError("drop object broke")

    mm._metrics["drop"].publish = boom
    eng.snap["pod_forward"][i_web, 0] = (6, 60)
    gen = ex._adv_gen
    mm.publish_once()
    assert ex._adv_gen == gen + 1
    assert b'workload_kind="web"} 6.0' in ex.gather_text()
    assert ex.gather_text() == fresh_exposition(ex)

    def worse(snap, ctx):
        raise KeyboardInterrupt

    mm._metrics["drop"].publish = worse
    order = list(mm._metrics)
    assert order.index("forward") < order.index("drop")
    eng.snap["pod_forward"][i_web, 0] = (7, 70)
    with pytest.raises(KeyboardInterrupt):
        mm.publish_once()
    assert ex._adv_gen == gen + 2
    assert b'workload_kind="web"} 7.0' in ex.gather_text()


def test_only_a_publish_cycle_writes_the_advanced_registry():
    """The invariant the exporter's kept bytes rest on, held on the
    tree: under retina_tpu/ the advanced registry's families are made
    by module/metric_objects.py alone, every ``publish`` of a metric
    object is called from MetricsModule._publish_series, and
    publish_once is its only caller."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "retina_tpu"
    makers, publishers, callers = set(), set(), set()
    for path in root.rglob("*.py"):
        rel = path.relative_to(root).as_posix()
        for fn in ast.walk(ast.parse(path.read_text())):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                attr = node.func.attr
                if attr in ("new_adv_gauge", "new_adv_counter"):
                    makers.add(rel)
                elif attr == "_publish_series":
                    callers.add((rel, fn.name))
                elif attr == "publish":
                    first = node.args[0] if node.args else None
                    if not (isinstance(first, ast.Name)
                            and "TOPIC" in first.id):  # not pubsub
                        publishers.add((rel, fn.name))
    assert makers == {"module/metric_objects.py"}
    assert publishers == {("module/metrics_module.py", "_publish_series")}
    assert callers == {("module/metrics_module.py", "publish_once")}
