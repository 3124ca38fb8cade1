"""Metrics module: reconciles MetricsSpec into metric objects + publishes.

Reference analog: pkg/module/metrics/metrics_module.go — a singleton that
(a) Reconciles a MetricsSpec from CRD/annotations into a registry of
metric objects via a name→constructor switch (updateMetricsContexts
:205-263), resetting the advanced Prometheus registry when the set changes
(exporter reset, prometheusexporter.go:35-40); (b) runs the flow-
processing loop (:266-330); (c) tracks dirty pods and syncs their IPs into
the filtermanager.

TPU shape: (b) lives on device (engine feed loop); this module's run loop
is the **publish** side — every interval, read the merged device snapshot
and let each metric object set its labeled gauges. (c) is kept: pod events
from pubsub add/remove pod IPs in the filtermanager under requestor
"metrics-module" the way metrics_module.go's dirty-pod goroutine does.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Optional

from retina_tpu.common import (
    POD_ANNOTATION,
    POD_ANNOTATION_VALUE,
    TOPIC_NAMESPACES,
    TOPIC_PODS,
)
from retina_tpu.config import Config
from retina_tpu.controllers.cache import Cache
from retina_tpu.crd.types import MetricsConfiguration, MetricsSpec
from retina_tpu.events.schema import ip_to_u32
from retina_tpu.exporter import Exporter, get_exporter
from retina_tpu.fleet.shipper import window_epoch
from retina_tpu.log import logger
from retina_tpu.managers.filtermanager import FilterManager
from retina_tpu.metrics import get_metrics
from retina_tpu.obs.recorder import get_recorder
from retina_tpu.runtime.supervisor import NEVER, Heartbeat, Supervisor
from retina_tpu.utils import metric_names as mn
from retina_tpu.module.metric_objects import (
    METRIC_CONSTRUCTORS,
    AdvMetricBase,
    PodLabels,
    PublishCtx,
)

PUBLISH_INTERVAL_S = 1.0  # metrics_module.go:37 module interval


class MetricsModule:
    def __init__(
        self,
        cfg: Config,
        engine: Any,
        cache: Cache,
        filtermanager: Optional[FilterManager] = None,
        exporter: Optional[Exporter] = None,
        pubsub: Any = None,
        dns_resolver: Any = None,
        supervisor: Optional[Supervisor] = None,
    ):
        self._log = logger("metricsmodule")
        # The publisher's liveness cell: observed by the watchdog's
        # scan for stalls of seconds, never escalated (a cycle that
        # waits for a cold compile on the proxy is no fault).
        self._hb = (supervisor.register("metricsmodule", NEVER, parked=True)
                    if supervisor is not None
                    else Heartbeat("metricsmodule", NEVER, parked=True))
        self.cfg = cfg
        self.engine = engine
        self.cache = cache
        self.fm = filtermanager
        self.exporter = exporter or get_exporter()
        self.dns_resolver = dns_resolver
        self._lock = threading.Lock()
        self._metrics: dict[str, AdvMetricBase] = {}
        self._pods = PodLabels()
        self._spec: MetricsSpec = MetricsSpec()
        if pubsub is not None:
            pubsub.subscribe(TOPIC_PODS, self._on_pod_event)
            pubsub.subscribe(TOPIC_NAMESPACES, self._on_namespace_event)

    # -- annotation opt-in (metrics_module.go:575-595 podAnnotated) ---
    def _pod_of_interest(self, ep) -> bool:
        """With enable_annotations, only pods carrying retina.sh=observe
        (or living in an annotated namespace) are tracked; otherwise
        every pod is."""
        if not self.cfg.enable_annotations:
            return True
        if dict(ep.annotations).get(POD_ANNOTATION) == POD_ANNOTATION_VALUE:
            return True
        return ep.namespace in self.cache.annotated_namespaces()

    # -- dirty-pod → filtermanager sync (metrics_module.go run loop) --
    def _on_pod_event(self, msg: tuple) -> None:
        """Pubsub callbacks run on a pool with NO ordering guarantee, so
        the decision is derived from the cache's CURRENT state, not the
        event payload — stale events then converge to the same verdict
        as fresh ones instead of inverting it."""
        if self.fm is None:
            return
        _ev, ep = msg
        try:
            event_ips = [ip_to_u32(ip) for ip in ep.ips]
        except (ValueError, AttributeError):
            return
        current = self.cache.get_endpoint(ep.key())
        if current is not None and self._pod_of_interest(current):
            cur_ips = [ip_to_u32(ip) for ip in current.ips]
            self.fm.add_ips(cur_ips, "metrics-module", ep.key())
            stale = [ip for ip in event_ips if ip not in set(cur_ips)]
            if stale:  # pod changed IPs across updates
                self.fm.delete_ips(stale, "metrics-module", ep.key())
        else:
            # Deleted, opted out, or annotation dropped on update.
            cur_ips = (
                [ip_to_u32(ip) for ip in current.ips]
                if current is not None else []
            )
            self.fm.delete_ips(sorted(set(event_ips) | set(cur_ips)),
                               "metrics-module", ep.key())

    def _on_namespace_event(self, msg: tuple) -> None:
        """A namespace gained/lost the observe annotation: resync every
        pod already in it in ONE filter-table push
        (namespace_controller.go Start loop)."""
        if self.fm is None or not self.cfg.enable_annotations:
            return
        _ev, ns = msg
        with self.fm.deferred_push():
            for ep in self.cache.endpoints_in_namespace(ns):
                self._on_pod_event(("updated", ep))

    # -- reconcile (metrics_module.go:142-175, :205-263) ---------------
    def reconcile(self, conf: MetricsConfiguration) -> None:
        conf.validate()
        with self._lock:
            self._spec = conf.spec
            # Changed metric set ⇒ reset the advanced registry, then
            # recreate objects against the fresh registry.
            self.exporter.reset_advanced()
            self._metrics = {}
            self._pods = PodLabels()
            for co in conf.spec.context_options:
                ctor = METRIC_CONSTRUCTORS.get(co.metric_name)
                if ctor is None:
                    self._log.warning("no constructor for %s", co.metric_name)
                    continue
                self._metrics[co.metric_name] = ctor(co, self.exporter)
        self._log.info(
            "metrics module reconciled: %s", sorted(self._metrics)
        )

    def enabled_metrics(self) -> list[str]:
        with self._lock:
            return sorted(self._metrics)

    # -- publish loop --------------------------------------------------
    def publish_once(self) -> None:
        with self._lock:
            metrics = dict(self._metrics)
            spec, pods = self._spec, self._pods
        if not metrics:
            return
        rec = get_recorder()
        cpu = self.exporter.publish_cpu
        with rec.span(
            mn.STAGE_POD_PUBLISH,
            window_epoch(self.cfg.window_seconds),
        ) as span:
            t0 = time.thread_time()
            snap = self.engine.snapshot()
            t1 = time.thread_time()
            cpu[mn.PART_SNAPSHOT].inc(t1 - t0)
            try:
                with rec.span(mn.STAGE_SERIES_PUBLISH,
                              span.trace_id) as series:
                    ctx = self._publish_series(metrics, spec, pods, snap)
                    series.set(rows=ctx.n_rows, changed=ctx.n_changed,
                               created=ctx.n_created)
            finally:
                cpu[mn.PART_SERIES].inc(time.thread_time() - t1)
                # The one place the advanced registry's values are
                # written ends here, whatever a metric object raised:
                # until now the exporter serves the previous cycle's
                # bytes, from now the first gather renders this one's.
                self.exporter.advanced_published()
            # The watermark (engine.publish_lag_s): how far behind the
            # sink's accepts this cycle's series are, now that they are
            # out. An engine without a sink (test doubles) has none.
            lag = getattr(self.engine, "publish_lag_s", None)
            if lag is not None:
                lag_s, included = lag(snap)
                get_metrics().publish_lag_seconds.observe(lag_s)
                span.set(events_included=included,
                         lag_ms=round(lag_s * 1e3, 1))

    def _publish_series(self, metrics, spec, pods, snap) -> PublishCtx:
        shed = getattr(self.engine, "shed_active", None)
        labeler: dict = {}
        if shed is not None and shed("labels"):
            # Overload SHEDDING (runtime/overload.py): per-pod label
            # resolution is the last enrichment stage dropped — pod
            # series publish with index placeholders this pass instead
            # of walking the endpoint cache under saturation. Counted
            # per skipped pass.
            self.engine.overload.note_shed("labels")
        else:
            labeler = self.cache.index_label_map()
        pods.refresh(labeler, spec.namespaces)
        ctx = PublishCtx(
            pods=pods,
            remote_context=self.cfg.remote_context,
            dns_resolver=self.dns_resolver,
        )
        try:
            for name, m in metrics.items():
                try:
                    m.publish(snap, ctx)
                except Exception:
                    self._log.exception("metric %s publish failed", name)
        finally:
            self.exporter.publish_rows.inc(ctx.n_rows)
            self.exporter.publish_rows_changed.inc(ctx.n_changed)
        return ctx

    def start(self, stop: threading.Event) -> None:
        # Adaptive cadence: the 1 s module interval
        # (metrics_module.go:37) assumes a publish cycle is cheap. At
        # the benchmark's 35k series it is, since the series are rows
        # and a cycle sets only those that changed: on the chip's host
        # `pod_publish` is 40-48 ms (`series_publish` 25-26 ms, the
        # rest the snapshot's fetch; PERF.md section 6, PR 32), so the
        # rule below sleeps its 1 s floor, about 47 cycles in 50 s. It
        # backs off to 4x cost for a cycle that is not cheap (more
        # series, a slower host; 0.3 s a cycle slept 1.1-1.4 s until
        # PR 32), so that the cycle cannot take the host from the
        # feed, but never beyond 5 s (unbounded backoff turned
        # pod-gauge staleness into 12-15 s).
        hb = self._hb
        while not stop.is_set():
            t0 = time.perf_counter()
            hb.beat(t0)
            try:
                self.publish_once()
            except Exception:
                self._log.exception("publish cycle failed")
            t1 = time.perf_counter()
            hb.park(t1)
            cost = t1 - t0
            stop.wait(max(PUBLISH_INTERVAL_S, min(4 * cost, 5.0)))
