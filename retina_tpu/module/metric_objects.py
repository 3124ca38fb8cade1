"""Advanced (pod-level) metric objects.

Reference analog: pkg/module/metrics/*.go — per-metric aggregators
implementing ``AdvMetricsInterface{Init, ProcessFlow, Clean}`` (types.go),
e.g. ForwardMetrics.ProcessFlow incrementing a GaugeVec per flow
(forward.go:97-171). The TPU redesign inverts the dataflow: aggregation
already happened on device (the pipeline step), so each object implements
``publish(snapshot, ctx)`` — read its slice of the merged device snapshot
and set the rows of its tables (``exporter.SeriesTable``: one row per
series, found by small integers, touched only when its value changed).
Per-flow CPU work is gone; publish cost is O(active label sets) in numpy
and O(changed series) in Python, not O(events).

Local vs remote context (metrics_module.go:216-222, modes doc): local
context publishes per-pod series from the dense rectangles; remote context
publishes src×dst pod-pair series from the service-graph heavy-hitter
sketch — bounded by the sketch's slot count where the reference's remote
mode is unbounded.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import numpy as np

from retina_tpu.common import RetinaEndpoint
from retina_tpu.crd.types import MetricsContextOptions, MetricsNamespaces
from retina_tpu.exporter import Exporter, SeriesTable
from retina_tpu.utils import metric_names as mn


class PodLabels:
    """The pod label values of every pod index, kept from one publish
    cycle to the next and refreshed once a cycle: an index's values are
    built when the labeler first returns an endpoint for it and again
    only when it returns another one; the namespace filter is asked
    once per index, not once per series."""

    def __init__(self) -> None:
        self._eps: dict[int, RetinaEndpoint] = {}
        # (podname, namespace, workload_kind), in _POD_LABELS' order
        self.values: dict[int, tuple[str, str, str]] = {}
        # Advances when an index's label values change (a hand-over).
        self.epoch = np.zeros(0, np.int64)
        # This cycle: the labeler knows the index and its namespace
        # is admitted.
        self.admitted = np.zeros(0, bool)

    def refresh(self, labeler: dict[int, RetinaEndpoint],
                namespaces: MetricsNamespaces) -> None:
        grow = max(labeler, default=-1) + 1 - len(self.epoch)
        if grow > 0:
            self.epoch = np.concatenate(
                [self.epoch, np.zeros(grow, np.int64)])
        admitted = np.zeros(len(self.epoch), bool)
        for idx, ep in labeler.items():
            if self._eps.get(idx) is not ep:
                self._eps[idx] = ep
                values = (ep.name, ep.namespace, ep.workload())
                if self.values.get(idx) != values:
                    self.values[idx] = values
                    self.epoch[idx] += 1
            admitted[idx] = namespaces.admits(ep.namespace)
        self.admitted = admitted


@dataclasses.dataclass
class PublishCtx:
    """Everything a metric object needs at publish time, and the
    cycle's tally: every write to a table goes through :meth:`set` or
    :meth:`update`."""

    pods: PodLabels
    remote_context: bool = False
    dns_resolver: Any = None  # qname hash -> str
    top_k: int = 50
    n_rows: int = 0  # looked at
    n_changed: int = 0  # appended or rewritten
    n_created: int = 0  # appended

    def admit(self, idx: int) -> Optional[tuple[str, str, str]]:
        """The pod label values of an index, if it has an identity and
        its namespace is admitted."""
        admitted = self.pods.admitted
        if idx < len(admitted) and admitted[idx]:
            return self.pods.values[idx]
        return None

    def admitted(self, pods: np.ndarray) -> np.ndarray:
        """:meth:`admit` for an array of indexes, as a mask."""
        admitted = self.pods.admitted
        mask = pods < len(admitted)
        mask[mask] = admitted[pods[mask]]
        return mask

    def set(self, table: SeriesTable, labels: tuple[str, ...],
            value: float) -> int:
        """``labels(...).set(value)``: the row's number."""
        before = len(table)
        row, touched = table.set(labels, float(value))
        self.n_rows += 1
        self.n_changed += touched
        self.n_created += len(table) - before
        return row

    def update(self, table: SeriesTable, rows: np.ndarray,
               values: np.ndarray) -> None:
        self.n_rows += len(rows)
        self.n_changed += table.update(rows, values)


_POD_LABELS = [mn.L_POD, mn.L_NAMESPACE, mn.L_WORKLOAD]


class _PodRows:
    """The rows of pod-level tables that carry the same label values (a
    count and its bytes), found by (pod index, sub index): sub is the
    direction, reason, flag or query type, named by ``sub_name``; None
    for a family with the pod labels alone."""

    def __init__(self, sub_name: Optional[Callable[[int], str]],
                 *tables: SeriesTable) -> None:
        self.sub_name = sub_name
        self.tables = tables
        # row of table t for (pod, sub); -1: none yet
        self._row = np.full((len(tables), 0, 0), -1, np.int64)
        # PodLabels.epoch the rows of a pod index were built at
        self._epoch = np.zeros(0, np.int64)

    def put(self, ctx: PublishCtx, grid: tuple[int, int],
            pods: np.ndarray, subs: np.ndarray,
            *values: np.ndarray) -> None:
        """Set the active entries ``(pods[i], subs[i])`` of a (P, S)
        grid, in series order, to ``values[t][i]`` in table t."""
        keep = ctx.admitted(pods)
        pods, subs = pods[keep], subs[keep]
        if self._row.shape[1:] != grid:
            self._row = np.full((len(self.tables), *grid), -1, np.int64)
            self._epoch = np.zeros(grid[0], np.int64)
        # An index that changed hands: the old pod's rows stand with
        # their last values, the new pod's are found or appended.
        epoch = ctx.pods.epoch[pods]
        moved = self._epoch[pods] != epoch
        if moved.any():
            self._row[:, pods[moved]] = -1
            self._epoch[pods[moved]] = epoch[moved]
        for t, table in enumerate(self.tables):
            vals = values[t][keep].astype(np.float64)
            rows = self._row[t, pods, subs]
            known = rows >= 0
            for i in np.nonzero(~known)[0].tolist():
                pod, sub = int(pods[i]), int(subs[i])
                labels: tuple[str, ...] = ctx.pods.values[pod]
                if self.sub_name is not None:
                    labels = (self.sub_name(sub), *labels)
                self._row[t, pod, sub] = ctx.set(table, labels, vals[i])
            ctx.update(table, rows[known], vals[known])


class AdvMetricBase:
    """Init/publish/clean contract (AdvMetricsInterface analog)."""

    name = ""

    def __init__(self, opts: MetricsContextOptions, exporter: Exporter):
        self.opts = opts
        self.exporter = exporter
        self.init()

    def init(self) -> None:  # pragma: no cover - overridden
        raise NotImplementedError

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        raise NotImplementedError

    def clean(self) -> None:
        """Tables live in the advanced registry; reset drops them."""


class ForwardMetrics(AdvMetricBase):
    name = "forward"

    _DIRECTIONS = ("ingress", "egress")

    def init(self) -> None:
        labels = [mn.L_DIRECTION, *_POD_LABELS]
        self.rows = _PodRows(
            self._DIRECTIONS.__getitem__,
            self.exporter.new_adv_table(mn.ADV_FORWARD_COUNT, labels),
            self.exporter.new_adv_table(mn.ADV_FORWARD_BYTES, labels),
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        pf = snap["pod_forward"]  # (P, 2 dir, 2 {pkts, bytes})
        active = np.nonzero(pf.sum(axis=(1, 2)))[0]
        pods, dirs = np.repeat(active, 2), np.tile((0, 1), len(active))
        self.rows.put(ctx, (len(pf), 2), pods, dirs,
                      pf[pods, dirs, 0], pf[pods, dirs, 1])


class DropMetrics(AdvMetricBase):
    name = "drop"

    def init(self) -> None:
        from retina_tpu.plugins.dropreason import DROP_REASONS

        labels = [mn.L_REASON, *_POD_LABELS]
        self.rows = _PodRows(
            lambda r: DROP_REASONS.get(r, str(r)),
            self.exporter.new_adv_table(mn.ADV_DROP_COUNT, labels),
            self.exporter.new_adv_table(mn.ADV_DROP_BYTES, labels),
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        pd = snap["pod_drop"]  # (P, R, 2)
        pods, reasons = np.nonzero(pd[:, :, 0])
        self.rows.put(ctx, pd.shape[:2], pods, reasons,
                      pd[pods, reasons, 0], pd[pods, reasons, 1])


class TcpFlagsMetrics(AdvMetricBase):
    name = "tcpflags"

    _FLAGS = ["FIN", "SYN", "RST", "PSH", "ACK", "URG", "ECE", "CWR"]

    def init(self) -> None:
        self.rows = _PodRows(
            self._FLAGS.__getitem__,
            self.exporter.new_adv_table(
                mn.ADV_TCP_FLAG_COUNTERS, [mn.L_FLAG, *_POD_LABELS]
            ),
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        tf = snap["pod_tcpflags"]  # (P, 8)
        pods, bits = np.nonzero(tf)
        self.rows.put(ctx, tf.shape, pods, bits, tf[pods, bits])


class TcpRetransMetrics(AdvMetricBase):
    name = "tcpretrans"

    def init(self) -> None:
        self.rows = _PodRows(
            None,
            self.exporter.new_adv_table(
                mn.ADV_TCP_RETRANS_COUNT, _POD_LABELS
            ),
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        pr = snap["pod_retrans"]  # (P,)
        pods = np.nonzero(pr)[0]
        self.rows.put(ctx, (len(pr), 1), pods, np.zeros_like(pods),
                      pr[pods])


class DnsMetrics(AdvMetricBase):
    name = "dns"

    _QTYPES = {1: "A", 5: "CNAME", 28: "AAAA", 12: "PTR"}

    def init(self) -> None:
        labels = [mn.L_QTYPE, *_POD_LABELS]
        self.rows = _PodRows(
            lambda qt: self._QTYPES.get(qt, str(qt)),
            self.exporter.new_adv_table(mn.ADV_DNS_REQUEST_COUNT, labels),
            self.exporter.new_adv_table(mn.ADV_DNS_RESPONSE_COUNT, labels),
        )
        self.heavy = self.exporter.new_adv_table(
            mn.HEAVY_HITTER_DNS, ["query"]
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        pdns = snap["pod_dns"]  # (P, Q, 2)
        pods, qtypes = np.nonzero(pdns.sum(axis=2))
        self.rows.put(ctx, pdns.shape[:2], pods, qtypes,
                      pdns[pods, qtypes, 0], pdns[pods, qtypes, 1])
        # qname heavy hitters, resolved through the host string table
        if ctx.dns_resolver is not None and "dns_hh" in snap:
            from retina_tpu.parallel.telemetry import topk_from_snapshot

            keys, counts = topk_from_snapshot(snap, "dns_hh", ctx.top_k)
            for key, cnt in zip(keys, counts):
                ctx.set(self.heavy, (str(ctx.dns_resolver(int(key[0]))),),
                        int(cnt))


class LatencyMetrics(AdvMetricBase):
    """Apiserver RTT histogram (reference latency.go:286-301)."""

    name = "latency"

    def init(self) -> None:
        self.hist = self.exporter.new_adv_table(
            mn.ADV_API_LATENCY, [mn.L_BUCKET]
        )
        self.no_resp = self.exporter.new_adv_table(
            mn.ADV_API_NO_RESPONSE, []
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        hist = snap["lat_hist"]  # (H,) exponential ms buckets
        for b in range(len(hist)):
            ctx.set(self.hist, (str((1 << b) - 1),), int(hist[b]))


class DistinctSourcesMetrics(AdvMetricBase):
    """Per-pod distinct source IPs from the HLL bank (new capability the
    reference cannot express with bounded memory)."""

    name = "distinct_sources"

    def init(self) -> None:
        self.rows = _PodRows(
            None,
            self.exporter.new_adv_table(
                mn.DISTINCT_SRC_PER_POD, _POD_LABELS
            ),
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        est = snap["hll_src_per_pod"]  # (P,) float estimates
        pods = np.nonzero(est >= 1.0)[0]
        self.rows.put(ctx, (len(est), 1), pods, np.zeros_like(pods),
                      est[pods])


class FlowsMetrics(AdvMetricBase):
    """Flow-level series: distinct 5-tuples + top flow heavy hitters."""

    name = "flows"

    def init(self) -> None:
        self.distinct = self.exporter.new_adv_table(mn.DISTINCT_FLOWS, [])
        self.heavy = self.exporter.new_adv_table(
            mn.HEAVY_HITTER_FLOWS,
            ["src_ip", "dst_ip", "src_port", "dst_port", mn.L_PROTO],
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        from retina_tpu.events.schema import u32_to_ip
        from retina_tpu.parallel.telemetry import topk_from_snapshot

        ctx.set(self.distinct, (), float(snap["hll_flows"][0]))
        keys, counts = topk_from_snapshot(snap, "flow_hh", ctx.top_k)
        for key, cnt in zip(keys, counts):
            src, dst, ports, proto = (int(k) for k in key)
            ctx.set(self.heavy, (
                u32_to_ip(src), u32_to_ip(dst),
                str(ports >> 16), str(ports & 0xFFFF),
                {6: "TCP", 17: "UDP"}.get(proto, str(proto)),
            ), int(cnt))


class ServicesMetrics(AdvMetricBase):
    """Pod×pod service-graph edges from the svc heavy-hitter sketch —
    the REMOTE-context mode (src×dst pairs) with bounded memory."""

    name = "services"

    def init(self) -> None:
        self.edges = self.exporter.new_adv_table(
            mn.HEAVY_HITTER_SERVICES,
            ["src_" + mn.L_POD, "src_" + mn.L_NAMESPACE,
             "dst_" + mn.L_POD, "dst_" + mn.L_NAMESPACE],
        )

    def publish(self, snap: dict[str, Any], ctx: PublishCtx) -> None:
        from retina_tpu.parallel.telemetry import topk_from_snapshot

        keys, counts = topk_from_snapshot(snap, "svc_hh", ctx.top_k)
        for key, cnt in zip(keys, counts):
            src = ctx.admit(int(key[0]))
            dst = ctx.admit(int(key[1]))
            if src is None or dst is None:
                continue
            ctx.set(self.edges, (src[0], src[1], dst[0], dst[1]), int(cnt))


METRIC_CONSTRUCTORS = {
    cls.name: cls
    for cls in (
        ForwardMetrics, DropMetrics, TcpFlagsMetrics, TcpRetransMetrics,
        DnsMetrics, LatencyMetrics, DistinctSourcesMetrics, FlowsMetrics,
        ServicesMetrics,
    )
}
