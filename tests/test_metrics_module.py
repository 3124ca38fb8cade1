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


@pytest.mark.parametrize("beside", ["tables_alone", "plain_gauge"])
@pytest.mark.parametrize("change", [
    "publish", "publish_new_pod", "reconcile", "reset_advanced",
])
def test_gather_equals_fresh_render_after_every_change(
        change, beside, fresh_exposition):
    """After every publish cycle, a reconcile and a bare reset,
    gather_text() is byte for byte what rendering both registries
    afresh gives (sample by sample through ``collect()``), on the
    gather that renders and on those that reuse; a reconcile
    invalidates at once. The metric objects' row tables alone, and
    with a plain prometheus_client gauge registered beside them."""
    eng = FakeEngine()
    mm, cache = build_module(eng)
    ex = get_exporter()
    if beside == "plain_gauge":
        ex.new_adv_gauge("beside_adv_gauge", ["pod"]).labels(pod="x").set(4)
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
    (row tables included) by module/metric_objects.py alone, every
    ``publish`` of a metric object is called from
    MetricsModule._publish_series, and publish_once is its only caller;
    a ``SeriesTable`` is constructed by the exporter's
    ``new_adv_table`` and nowhere else."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "retina_tpu"
    makers, publishers, callers, tables = set(), set(), set(), set()
    for path in root.rglob("*.py"):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())
        tables.update(
            rel for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and getattr(node.func, "id", getattr(node.func, "attr", ""))
            == "SeriesTable")
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                attr = node.func.attr
                if attr in ("new_adv_gauge", "new_adv_counter",
                            "new_adv_table"):
                    makers.add(rel)
                elif attr == "_publish_series":
                    callers.add((rel, fn.name))
                elif attr == "publish":
                    first = node.args[0] if node.args else None
                    if not (isinstance(first, ast.Name)
                            and "TOPIC" in first.id):  # not pubsub
                        publishers.add((rel, fn.name))
    assert makers == {"module/metric_objects.py"}
    assert tables == {"exporter.py"}
    assert publishers == {("module/metrics_module.py", "_publish_series")}
    assert callers == {("module/metrics_module.py", "publish_once")}


# ------------------------------------------------ the row tables (PR 32)
class Labeler:
    """Stands in for the endpoint cache: the test says which endpoint
    each pod index has."""

    def __init__(self):
        self.map: dict[int, RetinaEndpoint] = {}

    def index_label_map(self):
        return dict(self.map)


def _pod(name, ns="default", owner=None, labels=()):
    return RetinaEndpoint(
        name=name, namespace=ns, labels=tuple(labels),
        owner_refs=(("Deployment", owner),) if owner else ())


def _resolver(qhash: int) -> str:
    # one name that needs every escape of the text format
    return 'we"ird\\na\nme.' if qhash == 3 else f"name-{qhash}.example."


class PlainPublisher:
    """The reference: the publisher as it was before the row tables
    (PR 31's ``module/metric_objects.py``, its loops copied here): one
    prometheus_client child per series, ``labels(**lv).set(v)`` on
    every active series every cycle, whether or not the value
    changed."""

    FLAGS = ["FIN", "SYN", "RST", "PSH", "ACK", "URG", "ECE", "CWR"]
    QTYPES = {1: "A", 5: "CNAME", 28: "AAAA", 12: "PTR"}

    def __init__(self, names):
        from prometheus_client import CollectorRegistry, Gauge

        from retina_tpu.utils import metric_names as mn

        self.registry = CollectorRegistry()
        self.names = list(names)
        pod = [mn.L_POD, mn.L_NAMESPACE, mn.L_WORKLOAD]
        families = {
            "forward": [(mn.ADV_FORWARD_COUNT, [mn.L_DIRECTION, *pod]),
                        (mn.ADV_FORWARD_BYTES, [mn.L_DIRECTION, *pod])],
            "drop": [(mn.ADV_DROP_COUNT, [mn.L_REASON, *pod]),
                     (mn.ADV_DROP_BYTES, [mn.L_REASON, *pod])],
            "tcpflags": [(mn.ADV_TCP_FLAG_COUNTERS, [mn.L_FLAG, *pod])],
            "tcpretrans": [(mn.ADV_TCP_RETRANS_COUNT, pod)],
            "dns": [(mn.ADV_DNS_REQUEST_COUNT, [mn.L_QTYPE, *pod]),
                    (mn.ADV_DNS_RESPONSE_COUNT, [mn.L_QTYPE, *pod]),
                    (mn.HEAVY_HITTER_DNS, ["query"])],
            "latency": [(mn.ADV_API_LATENCY, [mn.L_BUCKET]),
                        (mn.ADV_API_NO_RESPONSE, [])],
            "distinct_sources": [(mn.DISTINCT_SRC_PER_POD, pod)],
            "flows": [(mn.DISTINCT_FLOWS, []),
                      (mn.HEAVY_HITTER_FLOWS,
                       ["src_ip", "dst_ip", "src_port", "dst_port",
                        mn.L_PROTO])],
            "services": [(mn.HEAVY_HITTER_SERVICES,
                          ["src_" + mn.L_POD, "src_" + mn.L_NAMESPACE,
                           "dst_" + mn.L_POD, "dst_" + mn.L_NAMESPACE])],
        }
        self.g = {
            name: [Gauge(n, n, labels, registry=self.registry)
                   for n, labels in families[name]]
            for name in self.names
        }

    def publish(self, snap, labeler, namespaces) -> None:
        def admit(idx):
            ep = labeler.get(idx)
            if ep is None or not namespaces.admits(ep.namespace):
                return None
            return ep

        for name in self.names:
            getattr(self, "_" + name)(snap, admit, *self.g[name])

    @staticmethod
    def _lv(ep):
        return {"podname": ep.name, "namespace": ep.namespace,
                "workload_kind": ep.workload()}

    def _forward(self, snap, admit, count, nbytes):
        pf = snap["pod_forward"]
        for idx in np.nonzero(pf.sum(axis=(1, 2)))[0]:
            ep = admit(int(idx))
            if ep is None:
                continue
            lv = self._lv(ep)
            for d, dname in ((0, "ingress"), (1, "egress")):
                count.labels(direction=dname, **lv).set(int(pf[idx, d, 0]))
                nbytes.labels(direction=dname, **lv).set(int(pf[idx, d, 1]))

    def _drop(self, snap, admit, count, nbytes):
        from retina_tpu.plugins.dropreason import DROP_REASONS

        pd = snap["pod_drop"]
        for idx, r in zip(*np.nonzero(pd[:, :, 0])):
            ep = admit(int(idx))
            if ep is None:
                continue
            lv = self._lv(ep)
            rname = DROP_REASONS.get(int(r), str(int(r)))
            count.labels(reason=rname, **lv).set(int(pd[idx, r, 0]))
            nbytes.labels(reason=rname, **lv).set(int(pd[idx, r, 1]))

    def _tcpflags(self, snap, admit, count):
        tf = snap["pod_tcpflags"]
        for idx, bit in zip(*np.nonzero(tf)):
            ep = admit(int(idx))
            if ep is None:
                continue
            count.labels(flag=self.FLAGS[int(bit)],
                         **self._lv(ep)).set(int(tf[idx, bit]))

    def _tcpretrans(self, snap, admit, count):
        pr = snap["pod_retrans"]
        for idx in np.nonzero(pr)[0]:
            ep = admit(int(idx))
            if ep is None:
                continue
            count.labels(**self._lv(ep)).set(int(pr[idx]))

    def _dns(self, snap, admit, req, resp, heavy):
        from retina_tpu.parallel.telemetry import topk_from_snapshot

        pdns = snap["pod_dns"]
        for idx, qt in zip(*np.nonzero(pdns.sum(axis=2))):
            ep = admit(int(idx))
            if ep is None:
                continue
            lv = self._lv(ep)
            qname = self.QTYPES.get(int(qt), str(int(qt)))
            req.labels(query_type=qname, **lv).set(int(pdns[idx, qt, 0]))
            resp.labels(query_type=qname, **lv).set(int(pdns[idx, qt, 1]))
        keys, counts = topk_from_snapshot(snap, "dns_hh", 50)
        for key, cnt in zip(keys, counts):
            heavy.labels(query=_resolver(int(key[0]))).set(int(cnt))

    def _latency(self, snap, admit, hist, no_resp):
        for b in range(len(snap["lat_hist"])):
            hist.labels(le_ms=str((1 << b) - 1)).set(
                int(snap["lat_hist"][b]))

    def _distinct_sources(self, snap, admit, gauge):
        est = snap["hll_src_per_pod"]
        for idx in np.nonzero(est >= 1.0)[0]:
            ep = admit(int(idx))
            if ep is None:
                continue
            gauge.labels(**self._lv(ep)).set(float(est[idx]))

    def _flows(self, snap, admit, distinct, heavy):
        from retina_tpu.events.schema import u32_to_ip
        from retina_tpu.parallel.telemetry import topk_from_snapshot

        distinct.set(float(snap["hll_flows"][0]))
        keys, counts = topk_from_snapshot(snap, "flow_hh", 50)
        for key, cnt in zip(keys, counts):
            src, dst, ports, proto = (int(k) for k in key)
            heavy.labels(
                src_ip=u32_to_ip(src), dst_ip=u32_to_ip(dst),
                src_port=str(ports >> 16), dst_port=str(ports & 0xFFFF),
                protocol={6: "TCP", 17: "UDP"}.get(proto, str(proto)),
            ).set(int(cnt))

    def _services(self, snap, admit, edges):
        from retina_tpu.parallel.telemetry import topk_from_snapshot

        keys, counts = topk_from_snapshot(snap, "svc_hh", 50)
        for key, cnt in zip(keys, counts):
            src, dst = admit(int(key[0])), admit(int(key[1]))
            if src is None or dst is None:
                continue
            edges.labels(
                src_podname=src.name, src_namespace=src.namespace,
                dst_podname=dst.name, dst_namespace=dst.namespace,
            ).set(int(cnt))


RECONCILE = "reconcile"


def _oracle_steps():
    """Snapshots and labelers in sequence, every family touched in
    every step: values rise, some stay, a new pod appears, a pod index
    changes hands, a namespace is excluded throughout, the labeler goes
    empty for a cycle, a reconcile resets the registry."""

    def first(snap, pods):
        pods.update({1: _pod("web-0", owner="web"),
                     2: _pod("sys-0", ns="kube-system"),
                     3: _pod("api-0")})
        f = snap["pod_forward"]
        f[1, 0] = (100, 5000)  # egress stays 0: a row that reads 0.0
        f[2, 1] = (50, 2500)  # excluded namespace
        f[3, 0] = (12345678, 4_000_000_000)  # exponent format
        f[7, 0] = (1, 1)  # an index without an endpoint
        d = snap["pod_drop"]
        d[1, 1], d[3, 2], d[2, 1], d[3, 14] = (7, 700), (1, 64), (9, 9), (2, 2)
        t = snap["pod_tcpflags"]
        t[1, 1], t[1, 4], t[3, 0], t[2, 2] = 3, 1_000_000, 5, 8
        snap["pod_retrans"][[1, 2, 3]] = (2, 4, 9)
        q = snap["pod_dns"]
        q[1, 1], q[3, 12], q[1, 7], q[2, 1] = (4, 3), (0, 6), (1, 0), (1, 1)
        snap["lat_hist"][[0, 3, 15]] = (5, 17, 1)
        snap["hll_src_per_pod"][[1, 2, 3]] = (12.3, 44.0, 0.5)
        snap["hll_flows"] = np.array([42.0])
        fk, fc = snap["flow_hh"]["keys"], snap["flow_hh"]["counts"]
        a, b = ip_to_u32("10.0.0.9"), ip_to_u32("10.0.0.1")
        fk[0, 0], fc[0, 0] = (a, b, (1234 << 16) | 80, 6), 999
        fk[0, 1], fc[0, 1] = (b, a, (53 << 16) | 4000, 17), 5
        fk[0, 2], fc[0, 2] = (a, a, 0, 1), 5_000_000
        sk, sc = snap["svc_hh"]["keys"], snap["svc_hh"]["counts"]
        sk[0, 0], sc[0, 0] = (1, 3), 10
        sk[0, 1], sc[0, 1] = (1, 2), 4  # to an excluded pod
        sk[0, 2], sc[0, 2] = (3, 9), 2  # to an index without endpoint
        dk, dc = snap["dns_hh"]["keys"], snap["dns_hh"]["counts"]
        dk[0, 0], dc[0, 0] = (3,), 8
        dk[0, 1], dc[0, 1] = (11,), 2

    def rise(snap, pods):
        snap["pod_forward"][1, 0] += np.array((1, 60), np.uint32)
        snap["pod_forward"][3, 1] += np.uint32(2)
        snap["pod_drop"][1, 1] += np.array((1, 100), np.uint32)  # d[3, 2] stays
        snap["pod_tcpflags"][1, 1] += 1
        snap["pod_retrans"][3] += 1
        snap["pod_dns"][1, 1, 0] += 1
        snap["lat_hist"][3] += 1
        snap["hll_src_per_pod"][1] += np.float32(1.6)
        snap["hll_flows"] = snap["hll_flows"] + 1.5
        snap["flow_hh"]["counts"][0, 0] += 201
        snap["svc_hh"]["counts"][0, 0] += 1
        snap["dns_hh"]["counts"][0, 1] += 7  # overtakes the other name

    def stay(snap, pods):
        pass

    def new_pod(snap, pods):
        pods[5] = _pod("db-0", owner="db")
        snap["pod_forward"][5, 1] = (9, 900)
        snap["pod_drop"][5, 3] = (6, 60)
        snap["pod_tcpflags"][5, 7] = 1
        snap["pod_retrans"][5] = 1
        snap["pod_dns"][5, 5] = (2, 2)
        snap["hll_src_per_pod"][5] = 3.0
        snap["svc_hh"]["keys"][0, 3] = (5, 1)
        snap["svc_hh"]["counts"][0, 3] = 30
        snap["flow_hh"]["keys"][0, 3] = (7, 8, (9 << 16) | 10, 6)
        snap["flow_hh"]["counts"][0, 3] = 77
        snap["dns_hh"]["keys"][0, 2] = (12,)
        snap["dns_hh"]["counts"][0, 2] = 1
        rise(snap, pods)

    def hand_over(snap, pods):
        pods[1] = _pod("web-1", owner="web")  # index 1 changes hands
        # the same pod under a new object: no new rows
        pods[3] = _pod("api-0", labels=(("app", "api"),))
        rise(snap, pods)

    def into_excluded(snap, pods):
        pods[5] = _pod("sys-1", ns="kube-system")  # db-0's rows stand
        rise(snap, pods)

    def hand_back(snap, pods):
        pods[1] = _pod("web-0", owner="web")  # web-0's old rows again
        pods[5] = _pod("db-0", owner="db")
        rise(snap, pods)

    def no_labels(snap, pods):
        pods.clear()  # as under label shedding: nothing is set
        rise(snap, pods)

    def labels_back(snap, pods):
        pods.update({1: _pod("web-0", owner="web"), 3: _pod("api-0"),
                     2: _pod("sys-0", ns="kube-system")})
        rise(snap, pods)

    return [first, rise, stay, new_pod, hand_over, rise, into_excluded,
            hand_back, RECONCILE, rise, no_labels, labels_back]


def _exposed(text: bytes):
    from prometheus_client.parser import text_string_to_metric_families

    return [
        (fam.name, fam.type, [(s.name, s.labels, s.value)
                              for s in fam.samples])
        for fam in text_string_to_metric_families(text.decode())
    ]


NINE = [co.metric_name
        for co in MetricsConfiguration.default().spec.context_options]


@pytest.mark.parametrize(
    "names", [[n] for n in NINE] + [NINE],
    ids=lambda names: names[0] if len(names) == 1 else "all_nine")
def test_row_tables_publish_the_bytes_of_the_plain_publisher(names):
    """(a) The oracle. Over a sequence of snapshots and labelers the
    pod-level bytes Exporter.gather() serves equal (1) the slow render
    of the advanced registry, every sample through ``collect()``,
    (2) the bytes of the plain prometheus_client publisher kept above,
    and (3) the library's own ``generate_latest``, which also parses
    back to the same samples."""
    from prometheus_client.exposition import generate_latest

    from retina_tpu.exporter import render_exposition

    conf = MetricsConfiguration(spec=MetricsSpec(
        context_options=[MetricsContextOptions(n) for n in names],
        namespaces=MetricsNamespaces(exclude=["kube-system"])))
    eng, labeler, ex = FakeEngine(), Labeler(), get_exporter()
    mm = MetricsModule(Config(), engine=eng, cache=labeler,
                       dns_resolver=_resolver)
    mm.reconcile(conf)
    plain = PlainPublisher(names)
    seen = set()
    for step in _oracle_steps():
        if step == RECONCILE:
            mm.reconcile(conf)
            plain = PlainPublisher(names)
            assert ex.gather()[0].endswith(generate_latest(plain.registry))
            continue
        step(eng.snap, labeler.map)
        mm.publish_once()
        plain.publish(eng.snap, labeler.map, conf.spec.namespaces)
        want = generate_latest(plain.registry)
        body, how = ex.gather()
        assert how == "rendered" and body.endswith(want)
        assert ex._adv_rendered[1] == want  # the pod-level bytes, kept
        again, how = ex.gather()
        assert how == "reused" and again.endswith(want)
        assert render_exposition(ex.advanced_registry) == want
        assert generate_latest(ex.advanced_registry) == want
        assert _exposed(want) == _exposed(
            generate_latest(ex.advanced_registry))
        seen.add(want)
    assert len(seen) >= 8  # the steps did move the bytes
    assert b"sys-0" not in want and b"sys-1" not in want


def _counting(monkeypatch, name):
    """Counts the calls of a function of the exporter module."""
    import retina_tpu.exporter as exporter_mod

    real, calls = getattr(exporter_mod, name), []

    def counted(*args):
        calls.append(args)
        return real(*args)

    monkeypatch.setattr(exporter_mod, name, counted)
    return calls


def _publish_args(mm) -> dict:
    """One publish cycle: the arguments of its series_publish span."""
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    old = get_recorder()
    rec = initialize_recorder(capacity=64)
    try:
        mm.publish_once()
        (span,) = [s for s in rec.spans()
                   if s["stage"] == mn.STAGE_SERIES_PUBLISH]
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)
    args = dict(span["args"])
    assert args.pop("cpu_s") >= 0.0  # the span's own clock, not the cycle's
    return args


def _counter(name: str, labels=None) -> float:
    return get_exporter().default_registry.get_sample_value(
        name + "_total", labels or {})


def test_publish_touches_only_rows_that_changed(monkeypatch):
    """(b) The delta: a cycle looks at every active row and formats the
    value and rebuilds the line of those alone whose value changed; the
    span's ``rows`` / ``changed`` / ``created`` and the two counters
    say so, and a counting ``_float_str`` sees one call per changed
    row."""
    from retina_tpu.utils import metric_names as mn

    eng, labeler = FakeEngine(), Labeler()
    mm = MetricsModule(Config(), engine=eng, cache=labeler,
                       dns_resolver=_resolver)
    mm.reconcile(MetricsConfiguration.default())
    _oracle_steps()[0](eng.snap, labeler.map)
    formatted = _counting(monkeypatch, "_float_str")
    first = _publish_args(mm)
    n = first["rows"]
    assert n > 60
    # every row looked at is new, but sketch_distinct_flows: a family
    # without labels has its one row from birth
    assert first == {"rows": n, "changed": n, "created": n - 1}
    assert len(formatted) == n
    del formatted[:]
    assert _publish_args(mm) == {"rows": n, "changed": 0, "created": 0}
    assert formatted == []
    # k values change, one in each kind of family
    eng.snap["pod_forward"][1, 0, 0] += 1  # a pair of tables: packets
    eng.snap["pod_drop"][3, 2, 1] += 1  # ... and bytes
    eng.snap["pod_retrans"][3] += 1  # pod labels alone
    eng.snap["lat_hist"][15] += 1  # no pod labels
    eng.snap["hll_flows"] = np.array([43.0])  # no labels at all
    eng.snap["hll_src_per_pod"][1] = 13.0  # a float
    eng.snap["svc_hh"]["counts"][0, 0] += 1  # a heavy hitter
    assert _publish_args(mm) == {"rows": n, "changed": 7, "created": 0}
    assert sorted(v for (v,) in formatted) == [
        2.0, 10.0, 11.0, 13.0, 43.0, 65.0, 101.0]
    assert _counter(mn.TPU_PUBLISH_ROWS) == 3 * n
    assert _counter(mn.TPU_PUBLISH_ROWS_CHANGED) == n + 7
    # a new series is one row created and changed, the rest untouched
    eng.snap["pod_tcpflags"][3, 6] = 4
    assert _publish_args(mm) == {"rows": n + 1, "changed": 1, "created": 1}
    # what the cycle cost is counted per part; a reused gather adds none
    ex = get_exporter()
    parts = {p: _counter(mn.TPU_PUBLISH_CPU_SECONDS, {mn.L_PART: p})
             for p in mn.PUBLISH_PARTS}
    assert parts["series"] > 0 and parts["render"] == 0
    assert [ex.gather()[1] for _ in range(3)] == [
        "rendered", "reused", "reused"]
    assert _counter(mn.TPU_PUBLISH_CPU_SECONDS,
                    {mn.L_PART: "render"}) >= 0


def test_a_pod_index_that_changes_hands(monkeypatch):
    """(c) The old pod's rows stand with their last values, the new
    pod's are appended after them, and the label values and line
    prefixes of an index are built once per endpoint: not again while
    the labeler returns the same one, not for rows that exist."""
    from retina_tpu.utils import metric_names as mn

    conf = MetricsConfiguration(spec=MetricsSpec(
        context_options=[MetricsContextOptions("forward")]))
    eng, labeler, ex = FakeEngine(), Labeler(), get_exporter()
    mm = MetricsModule(Config(), engine=eng, cache=labeler)
    mm.reconcile(conf)
    label_strs = _counting(monkeypatch, "_label_str")
    workloads = []
    real = RetinaEndpoint.workload
    monkeypatch.setattr(
        RetinaEndpoint, "workload",
        lambda ep: workloads.append(ep.name) or real(ep))

    def cycle(pkts: int) -> list[str]:
        eng.snap["pod_forward"][1, 0] = (pkts, 10 * pkts)
        del label_strs[:], workloads[:]
        mm.publish_once()
        # a table hands its label names over as a tuple; the default
        # registry's sample lines (a dict) are not prefixes of rows
        prefixes[:] = [c for c in label_strs if isinstance(c[0], tuple)]
        return [ln.split("networkobservability_adv_forward_")[1]
                for ln in ex.gather_text().decode().splitlines()
                if ln.startswith(mn.ADV_FORWARD_COUNT + "{")]

    prefixes: list = []

    def rows(pod: str, ingress: int) -> list[str]:
        lv = f'namespace="default",podname="{pod}",workload_kind="{pod}"}}'
        return [f'count{{direction="ingress",{lv} {float(ingress)}',
                f'count{{direction="egress",{lv} 0.0']

    labeler.map[1] = _pod("web-0")
    assert cycle(5) == rows("web-0", 5)
    assert len(prefixes) == 4 and workloads == ["web-0"]  # 2 rows, 2 tables
    assert cycle(6) == rows("web-0", 6)
    assert prefixes == [] and workloads == []
    labeler.map[1] = _pod("web-1")  # the index changes hands
    assert cycle(7) == rows("web-0", 6) + rows("web-1", 7)
    assert len(prefixes) == 4 and workloads == ["web-1"]
    assert cycle(8) == rows("web-0", 6) + rows("web-1", 8)
    assert prefixes == [] and workloads == []
    labeler.map[1] = _pod("web-0")  # and back: web-0's rows exist
    assert cycle(9) == rows("web-0", 9) + rows("web-1", 8)
    assert prefixes == [] and workloads == ["web-0"]
