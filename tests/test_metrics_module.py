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
