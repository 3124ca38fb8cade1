"""TelemetryPipeline: the flagship fused aggregation step.

Reference analog: the enricher output ring -> Module.run loop calling every
registered metric's ProcessFlow per flow (metrics_module.go:283-303,
forward.go:97-171, drops.go, tcpflags.go, dns.go) — single-threaded Go, the
system's scaling bottleneck per SURVEY.md §3.2. Here all enabled
aggregators consume the whole batch inside ONE jit-compiled step, so XLA
fuses hashing, masking, enrichment join, and sketch scatters into a single
device program; HBM traffic is one pass over the (B, 16) record block plus
the sketch tables.

Cardinality design (the reference's modes, docs/03-Metrics/modes/modes.md):
- bounded label spaces (pod x direction, pod x reason, pod x flag) use
  **dense exact counter rectangles** — TPU-friendly scatter-adds, zero
  approximation, bounded memory (the "local context" mode);
- unbounded label spaces (5-tuples, pod-pairs, DNS queries) use **sketches**
  (CMS + candidate tables, HLL, entropy) — the "remote context" mode that
  the reference ships with unbounded Prometheus maps becomes fixed-memory.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from retina_tpu.devprog import device_entry
from retina_tpu.events.schema import (
    F,
    EV_DNS_REQ,
    EV_DNS_RESP,
    EV_TCP_RETRANS,
    VERDICT_DROPPED,
    VERDICT_FORWARDED,
    DIR_INGRESS,
    PROTO_TCP,
)
from retina_tpu.models.identity import IdentityMap
from retina_tpu.ops.conntrack import ConntrackTable
from retina_tpu.ops.entropy import AnomalyEWMA, EntropyWindow
from retina_tpu.ops.hyperloglog import HyperLogLog
from retina_tpu.ops.invertible import InvertibleSketch
from retina_tpu.ops.topk import HeavyHitterSketch


def priority_class(
    src_ip: jnp.ndarray,
    dst_ip: jnp.ndarray,
    mask: int,
    match: int,
) -> jnp.ndarray:
    """(B,) bool: rows belonging to the configured high-priority
    (tenant, service) class — either endpoint inside the priority
    prefix. mask == 0 disables the class (nothing matches). MUST stay
    bit-identical to the numpy mirror in runtime/overload.py
    (`priority_class_np`): the host sampler exempts these rows and the
    device step must agree or the Horvitz-Thompson rescale goes
    biased."""
    if mask == 0:
        return jnp.zeros(src_ip.shape, bool)
    m, v = np.uint32(mask), np.uint32(match)
    return ((src_ip & m) == v) | ((dst_ip & m) == v)


def sample_exempt(
    packets: jnp.ndarray,
    tsval: jnp.ndarray,
    tsecr: jnp.ndarray,
    is_priority: jnp.ndarray,
    exempt_packets: int,
) -> jnp.ndarray:
    """(B,) bool: rows the host overload sampler keeps unsampled —
    heavy-hitter candidates (packet weight >= the exemption
    threshold), apiserver latency probes (TSVAL/TSECR lanes), and
    priority-class rows. MUST stay bit-identical to the host tiering
    in runtime/overload.py (``row_tiers`` > TIER_BACKGROUND): the
    device step re-derives this predicate to decide which rows the
    Horvitz-Thompson rescale may touch, and any disagreement biases
    every packet-weighted estimate (RT304 sweeps the parity)."""
    return (
        (packets >= np.uint32(exempt_packets))
        | ((tsval | tsecr) != 0)
        | is_priority
    )


def ht_rescale(
    packets: jnp.ndarray,
    bytes_: jnp.ndarray,
    exempt: jnp.ndarray,
    sample_k,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Horvitz-Thompson re-weighting of a 1-in-k sampled batch:
    multiply surviving NON-exempt rows by k so every packet-weighted
    estimate stays unbiased. u32 saturating multiply — a row that
    would wrap is clamped to the cap (it is already a massive heavy
    hitter); RT301's interval analysis proves the non-saturated arm
    cannot wrap under the documented per-row envelope."""
    k = jnp.asarray(sample_k, jnp.uint32)
    scale = jnp.where((k > 1) & ~exempt, k, np.uint32(1))
    lim = np.uint32(0xFFFFFFFF) // jnp.maximum(k, np.uint32(1))
    cap = np.uint32(0xFFFFFFFF)
    packets = jnp.where(
        (scale > 1) & (packets > lim), cap, packets * scale
    )
    bytes_ = jnp.where(
        (scale > 1) & (bytes_ > lim), cap, bytes_ * scale
    )
    return packets, bytes_


def _sum64(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact (lo, hi) u32 limbs of sum(x) for a (B,) uint32 batch.

    TPU has no u64 and a direct u32 sum wraps (per-connection report
    accumulators reach 2^32-1, so even two reports can overflow). Summing
    the four 8-bit byte planes keeps every partial sum < 2^25 * B exact in
    u32, then the planes are recombined with explicit carries.
    """
    p0 = jnp.sum(x & np.uint32(0xFF)).astype(jnp.uint32)
    p1 = jnp.sum((x >> 8) & np.uint32(0xFF)).astype(jnp.uint32)
    p2 = jnp.sum((x >> 16) & np.uint32(0xFF)).astype(jnp.uint32)
    p3 = jnp.sum(x >> 24).astype(jnp.uint32)
    hi = (p1 >> 24) + (p2 >> 16) + (p3 >> 8)
    lo = p0
    for t in (p1 << 8, p2 << 16, p3 << 24):
        lo = lo + t
        hi = hi + (lo < t).astype(jnp.uint32)
    return lo, hi


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static shapes of every aggregator (hashable; part of the jit key)."""

    n_pods: int = 1 << 12  # dense pod-index space (0 = unknown/world)
    n_drop_reasons: int = 16
    n_dns_qtypes: int = 16
    # depth 2 x width 2^16 over the previous 4 x 2^15: same memory, half
    # the scatter/gather passes (the measured TPU cost driver), and a
    # tighter per-row error bound e/w*N; failure prob per point query rises
    # e^-4 -> e^-2, which the candidate slot table's ranking absorbs for
    # top-k purposes (only relative order of true heavies matters there).
    cms_depth: int = 2
    cms_width: int = 1 << 16
    topk_slots: int = 1 << 11
    hll_precision: int = 12
    hll_pod_precision: int = 6  # 64 regs: ~13% rel err per-pod, 4x fewer
    # register lines touched by the scatter-max than p=8
    entropy_buckets: int = 1 << 12
    conntrack_slots: int = 1 << 18
    latency_slots: int = 1 << 12
    latency_buckets: int = 16  # exponential RTT histogram buckets
    enable_conntrack: bool = True
    enable_latency: bool = True
    # Kernel-side filtering analog (reference _cprog/retina_filter.c:24-34:
    # the LPM "IPs of interest" lookup gates event emission; config
    # BYPASS_LOOKUP_IP_OF_INTEREST disables it, packetparser.c:151-158).
    # Here: events where neither endpoint resolves to a pod identity nor to
    # an entry in the explicit filter map are masked out of every
    # aggregator. bypass_filter=True admits everything.
    bypass_filter: bool = True
    # Overload-sampling exemption threshold (runtime/overload.py): a
    # combined row whose packet weight is >= this is a heavy-hitter
    # candidate — never sampled on the host and never rescaled here.
    # MUST match the host sampler's predicate (both read F.PACKETS of
    # the post-combine row; partition/wire transport preserve it).
    # 0 exempts every row, i.e. sampling rescale disabled.
    sample_exempt_packets: int = 64
    # Whether resolving to a pod identity alone makes an event
    # interesting. True matches the default deployment (the metrics
    # module tracks every pod, so the filter map holds every pod IP
    # anyway). False = annotation opt-in mode: ONLY the filter map
    # decides (retina_filter.c semantics) — an un-annotated pod's
    # identity must not readmit its traffic.
    identity_implies_interest: bool = True
    # DataAggregationLevel (reference config.go:16-23, compiled into the
    # datapath via dynamic.h and consumed at packetparser.c:214-225): at
    # "low", the packet-stream sketches (flow_hh, svc_hh, hll_flows,
    # entropy) do NOT take per-packet updates; only conntrack REPORT rows
    # feed them (SYN/FIN/RST or the 30s per-connection interval),
    # weighted by the accumulated packet totals the report carries — the
    # sketch traffic collapses from per-packet to per-connection just as
    # the reference's packetparser event stream does. dns_hh and the
    # drop-reason HLL stay per-event in both modes: in the reference,
    # DATA_AGGREGATION_LEVEL gates only packetparser.c — the dns and
    # dropreason plugins are separate programs it never touches. Dense
    # exact rectangles and node counters stay per-packet in both modes
    # (bounded and cheap). Requires enable_conntrack; validated in
    # __post_init__.
    data_aggregation_level: str = "high"
    # Invertible sketch (ops/invertible.py): recover heavy-flow keys
    # from sketch state at window close (cfg.heavy_keys_source). Two
    # instances: the main region takes every flow; a small dedicated
    # high-priority region takes ONLY priority-class rows (below) —
    # those rows are never host-sampled (runtime/overload.py lattice),
    # so the region is full-accuracy whatever the overload state.
    enable_invertible: bool = False
    inv_depth: int = 2
    inv_width: int = 1 << 12
    inv_hi_width: int = 1 << 9
    # High-priority (tenant, service) class: an endpoint IP matching
    # (ip & priority_ip_mask) == priority_ip_match is priority traffic.
    # 0 mask disables. Mirrors cfg.overload_priority_ip_mask/_match —
    # host sampler and device step MUST share the predicate.
    priority_ip_mask: int = 0
    priority_ip_match: int = 0

    def __post_init__(self):
        if self.inv_width & (self.inv_width - 1):
            raise ValueError("inv_width must be a power of two")
        if self.inv_hi_width & (self.inv_hi_width - 1):
            raise ValueError("inv_hi_width must be a power of two")
        if self.data_aggregation_level not in ("low", "high"):
            raise ValueError(
                f"data_aggregation_level must be low|high, "
                f"got {self.data_aggregation_level!r}"
            )
        if self.data_aggregation_level == "low" and not self.enable_conntrack:
            raise ValueError(
                "data_aggregation_level=low requires enable_conntrack "
                "(reports drive the sketch sampling)"
            )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PipelineState:
    """All device-resident aggregation state, one pytree."""

    # Dense exact rectangles (local-context mode).
    pod_forward: jnp.ndarray  # (P, 2 dir, 2 {pkts, bytes}) uint32
    pod_drop: jnp.ndarray  # (P, R, 2 {pkts, bytes}) uint32
    pod_tcpflags: jnp.ndarray  # (P, 8 flags) uint32
    pod_dns: jnp.ndarray  # (P, Q qtypes, 2 {req, resp}) uint32
    pod_retrans: jnp.ndarray  # (P,) uint32
    node_counters: jnp.ndarray  # (2 dir, 2 {pkts, bytes}) uint32, node-level
    totals: jnp.ndarray  # (8,) uint32: [events, fwd, drop, dnsreq, dnsresp,
    #                                    retrans, ct_reports, lost]
    # Cumulative conntrack-reported packet/byte totals as two u32 limbs
    # each (TPU has no u64; manual carry): [pkts_lo, pkts_hi, bytes_lo,
    # bytes_hi]. Feeds the conntrack GC accounting pass (the reference GC
    # iterates the map and sums conntrackmetadata, conntrack_linux.go:95+).
    ct_totals: jnp.ndarray  # (4,) uint32
    # Sketches (remote-context mode).
    flow_hh: HeavyHitterSketch  # 5-tuple heavy hitters
    svc_hh: HeavyHitterSketch  # (src_pod, dst_pod) service graph
    dns_hh: HeavyHitterSketch  # DNS query-name-hash heavy hitters
    hll_flows: HyperLogLog  # distinct 5-tuples, G=1
    hll_src_per_reason: HyperLogLog  # distinct srcs per drop reason, G=R
    hll_src_per_pod: HyperLogLog  # distinct srcs per dst pod, G=P
    entropy: EntropyWindow  # G=3: src_ip, dst_ip, dst_port
    anomaly: AnomalyEWMA  # G=3 EWMA over window entropies
    # Invertible 5-tuple sketches: main region + full-accuracy
    # high-priority region (1-wide placeholders when disabled).
    inv_flow: InvertibleSketch
    inv_hi: InvertibleSketch
    conntrack: ConntrackTable
    # apiserver latency: match table tsval-hash -> send-time, + histogram.
    lat_key: jnp.ndarray  # (L,) uint32 match fingerprints
    lat_ts: jnp.ndarray  # (L,) uint32 send time (ns >> 20, ~ms units)
    lat_hist: jnp.ndarray  # (H,) uint32 RTT histogram (exponential buckets)

    def tree_flatten(self):
        fields = [f.name for f in dataclasses.fields(self)]
        return tuple(getattr(self, n) for n in fields), tuple(fields)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(zip(aux, children)))


# Operator scopes of the fused step (``jax.named_scope``): metadata on
# the lowered operations, so a device profile can say which operator
# owns a fusion. The optimised program is the same with or without
# them (tests/test_obs.py compares it opcode for opcode). ``decode``
# and ``totals`` are the column extraction and the scalar sums round
# the operators proper.
STEP_SCOPES = (
    "decode", "rescale", "identity_join", "filter", "conntrack",
    "pod_counters", "cms_flow_hh", "cms_svc_hh", "cms_dns_hh",
    "invertible", "hll", "entropy", "latency_match", "totals",
)
SCOPE_END_WINDOW = "end_window"


class TelemetryPipeline:
    """Builds zero state and the jitted step for a PipelineConfig."""

    def __init__(self, config: PipelineConfig = PipelineConfig()):
        self.config = config

    def init_state(self) -> PipelineState:
        c = self.config
        u = lambda *shape: jnp.zeros(shape, jnp.uint32)
        return PipelineState(
            pod_forward=u(c.n_pods, 2, 2),
            pod_drop=u(c.n_pods, c.n_drop_reasons, 2),
            pod_tcpflags=u(c.n_pods, 8),
            pod_dns=u(c.n_pods, c.n_dns_qtypes, 2),
            pod_retrans=u(c.n_pods),
            node_counters=u(2, 2),
            totals=u(8),
            ct_totals=u(4),
            flow_hh=HeavyHitterSketch.zeros(
                4, c.cms_depth, c.cms_width, c.topk_slots, seed=1
            ),
            svc_hh=HeavyHitterSketch.zeros(
                2, c.cms_depth, c.cms_width, c.topk_slots, seed=2
            ),
            dns_hh=HeavyHitterSketch.zeros(
                1, c.cms_depth, c.cms_width, c.topk_slots, seed=3
            ),
            hll_flows=HyperLogLog.zeros(1, c.hll_precision, seed=4),
            hll_src_per_reason=HyperLogLog.zeros(
                c.n_drop_reasons, c.hll_precision, seed=5
            ),
            hll_src_per_pod=HyperLogLog.zeros(c.n_pods, c.hll_pod_precision, seed=6),
            entropy=EntropyWindow.zeros(3, c.entropy_buckets, seed=7),
            anomaly=AnomalyEWMA.zeros(3),
            inv_flow=InvertibleSketch.zeros(
                c.inv_depth if c.enable_invertible else 1,
                c.inv_width if c.enable_invertible else 1,
                n_key_cols=4, seed=9,
            ),
            inv_hi=InvertibleSketch.zeros(
                c.inv_depth if c.enable_invertible else 1,
                c.inv_hi_width if c.enable_invertible else 1,
                n_key_cols=4, seed=10,
            ),
            conntrack=ConntrackTable.zeros(c.conntrack_slots, seed=8),
            lat_key=u(c.latency_slots),
            lat_ts=u(c.latency_slots),
            lat_hist=u(c.latency_buckets),
        )

    # ------------------------------------------------------------------
    def step(
        self,
        state: PipelineState,
        records: jnp.ndarray,  # (B, NUM_FIELDS) uint32
        n_valid: jnp.ndarray,  # scalar uint32
        now_s: jnp.ndarray,  # scalar uint32 wall seconds
        ident: IdentityMap,
        apiserver_ip: jnp.ndarray,  # scalar uint32 (0 = disabled)
        filter_map: IdentityMap | None = None,  # explicit IPs of interest
        sample_k=np.uint32(1),  # overload 1-in-k factor (scalar uint32)
    ) -> tuple[PipelineState, dict[str, jnp.ndarray]]:
        """Process one batch. Pure; jit via TelemetryPipeline.jitted_step."""
        c = self.config
        b = records.shape[0]
        with jax.named_scope("decode"):
            col = lambda i: records[:, i]
            mask = jnp.arange(b, dtype=jnp.uint32) < n_valid

            src_ip, dst_ip = col(F.SRC_IP), col(F.DST_IP)
            ports, meta = col(F.PORTS), col(F.META)
            proto = meta >> 24
            tcp_flags = (meta >> 16) & np.uint32(0xFF)
            direction = (meta >> 4) & np.uint32(0xF)
            bytes_, packets = col(F.BYTES), col(F.PACKETS)

        # ---- overload-sampling rescale (Horvitz-Thompson) ----
        # When the host fed a 1-in-k sampled batch (ShardedBatch.
        # sample_k > 1, runtime/overload.py), re-weight the surviving
        # NON-exempt rows by k so every packet-weighted estimate below
        # (sketches, rectangles, totals, conntrack accumulators) stays
        # unbiased. The exemption predicate is recomputed here over the
        # same post-combine rows the host sampler saw: heavy-hitter
        # candidates (packet weight >= sample_exempt_packets) and
        # apiserver latency probes (TSVAL/TSECR lanes) were kept
        # unsampled and must not be rescaled. u32 saturating multiply —
        # a clamped row is already a massive heavy hitter.
        # Priority-class rows (the overload lattice's (tenant, service)
        # tier) are exempt on the host and therefore never rescaled
        # here; they also route to the dedicated invertible region.
        with jax.named_scope("rescale"):
            is_priority = priority_class(
                src_ip, dst_ip, c.priority_ip_mask, c.priority_ip_match
            )
            if c.sample_exempt_packets > 0:
                exempt = sample_exempt(
                    packets, col(F.TSVAL), col(F.TSECR), is_priority,
                    c.sample_exempt_packets,
                )
                packets, bytes_ = ht_rescale(
                    packets, bytes_, exempt, sample_k
                )
        with jax.named_scope("decode"):
            verdict = col(F.VERDICT)
            reason = jnp.minimum(col(F.DROP_REASON), np.uint32(c.n_drop_reasons - 1))
            ev_type = col(F.EVENT_TYPE)

            is_fwd = mask & (verdict == VERDICT_FORWARDED)
            is_drop = mask & (verdict == VERDICT_DROPPED)
            is_dns_req = mask & (ev_type == EV_DNS_REQ)
            is_dns_resp = mask & (ev_type == EV_DNS_RESP)
            is_retrans = mask & (ev_type == EV_TCP_RETRANS)
            is_ingress = direction == DIR_INGRESS

        # ---- enrichment join: IP -> pod index (one gather each) ----
        with jax.named_scope("identity_join"):
            src_pod = jnp.where(mask, ident.lookup(src_ip), 0)
            dst_pod = jnp.where(mask, ident.lookup(dst_ip), 0)

        # ---- IPs-of-interest filter (retina_filter.c lookup() analog) ----
        with jax.named_scope("filter"):
            if not c.bypass_filter:
                if c.identity_implies_interest:
                    interest = (src_pod > 0) | (dst_pod > 0)
                else:
                    interest = jnp.zeros((b,), bool)
                if filter_map is not None:
                    interest |= (filter_map.lookup(src_ip) > 0) | (
                        filter_map.lookup(dst_ip) > 0
                    )
                mask &= interest
                is_fwd &= interest
                is_drop &= interest
                is_dns_req &= interest
                is_dns_resp &= interest
                is_retrans &= interest
        # The "local pod" of an event: dst for ingress, src for egress
        # (reference forward.go:107-160 local-context basis).
        with jax.named_scope("pod_counters"):
            local_pod = jnp.where(is_ingress, dst_pod, src_pod)
            dir_idx = jnp.where(is_ingress, 0, 1).astype(jnp.uint32)

            w_pkts = jnp.where(is_fwd, packets, 0)
            w_bytes = jnp.where(is_fwd, bytes_, 0)

        # ---- conntrack sampling (before the sketches: low aggregation
        # gates sketch updates on the report decisions) ----
        with jax.named_scope("conntrack"):
            ct = state.conntrack
            n_reports = np.uint32(0)
            report = jnp.zeros((b,), bool)
            rep_pkts = jnp.zeros((b,), jnp.uint32)
            rep_bytes = jnp.zeros((b,), jnp.uint32)
            if c.enable_conntrack:
                ct, report, _, rep_pkts, rep_bytes = ct.process(
                    src_ip, dst_ip, ports, proto, tcp_flags, now_s, bytes_, mask,
                    packets_=packets,
                )
                n_reports = jnp.sum(report).astype(jnp.uint32)

        # ---- dense rectangles ----
        # Every rectangle updates through ONE row-scatter with the counter
        # pair/bank as the contiguous minor dimension: a (B, C) row update
        # touches one cache line per event instead of C scattered lines,
        # and the pass count (the measured TPU cost driver) drops from 17
        # scatters to 4.
        P = c.n_pods
        with jax.named_scope("pod_counters"):
            local_pod_c = jnp.minimum(local_pod, np.uint32(P - 1))
            pf = (
                state.pod_forward.reshape(P * 2, 2)
                .at[local_pod_c * 2 + dir_idx]
                .add(jnp.stack([w_pkts, w_bytes], axis=1), mode="drop")
                .reshape(P, 2, 2)
            )

            R = c.n_drop_reasons
            drop_idx = jnp.where(is_drop, local_pod_c * R + reason, np.uint32(P * R))
            pd = (
                state.pod_drop.reshape(P * R, 2)
                .at[drop_idx]
                .add(
                    jnp.stack(
                        [
                            jnp.where(is_drop, packets, 0),
                            jnp.where(is_drop, bytes_, 0),
                        ],
                        axis=1,
                    ),
                    mode="drop",
                )
                .reshape(P, R, 2)
            )

            # tcp flags: one (B, 8) row-scatter; non-TCP rows route OOB.
            is_tcp = mask & (proto == PROTO_TCP)
            flag_rows = jnp.stack(
                [
                    jnp.where(((tcp_flags >> bit) & 1).astype(bool), packets, 0)
                    for bit in range(8)
                ],
                axis=1,
            )
            ptf = state.pod_tcpflags.at[
                jnp.where(is_tcp, local_pod_c, np.uint32(P))
            ].add(flag_rows, mode="drop")

            Q = c.n_dns_qtypes
            qtype = jnp.minimum(col(F.DNS) >> 16, np.uint32(Q - 1))
            is_dns = is_dns_req | is_dns_resp
            dns_idx = jnp.where(is_dns, local_pod_c * Q + qtype, np.uint32(P * Q))
            # Every count below weights by F.PACKETS (1 for per-packet events,
            # N for combined/pre-aggregated rows) so host-side RLE combining
            # (parallel/combine.py) is exactly lossless.
            w_dns_req = jnp.where(is_dns_req, packets, 0)
            w_dns_resp = jnp.where(is_dns_resp, packets, 0)
            w_retrans = jnp.where(is_retrans, packets, 0)
            pdns = (
                state.pod_dns.reshape(P * Q, 2)
                .at[dns_idx]
                .add(
                    jnp.stack([w_dns_req, w_dns_resp], axis=1),
                    mode="drop",
                )
                .reshape(P, Q, 2)
            )

            pret = state.pod_retrans.at[
                jnp.where(is_retrans, local_pod_c, np.uint32(P))
            ].add(w_retrans, mode="drop")

            # Node counters are plain masked reductions (no scatter needed):
            # each masked forward event contributes to exactly one (dir) cell.
            ing = is_ingress.astype(jnp.uint32)
            nc = state.node_counters + jnp.stack(
                [
                    jnp.stack(
                        [jnp.sum(w_pkts * ing), jnp.sum(w_bytes * ing)]
                    ),
                    jnp.stack(
                        [jnp.sum(w_pkts * (1 - ing)), jnp.sum(w_bytes * (1 - ing))]
                    ),
                ]
            ).astype(jnp.uint32)

        # ---- sketches ----
        # At low aggregation, sketch updates ride the conntrack reports:
        # one weighted update per reporting connection (carrying the
        # accumulated packet count since its last report, all verdicts)
        # instead of one per packet — the documented low-mode semantics.
        low = c.data_aggregation_level == "low"
        five = [src_ip, dst_ip, ports, proto]
        with jax.named_scope("cms_flow_hh"):
            flow_w = rep_pkts if low else jnp.where(is_fwd, packets, 0)
            flow_hh = state.flow_hh.update(five, flow_w)
        # Invertible key-recovery sketches ride the SAME keys and
        # weights as flow_hh, so decode verification against its CMS is
        # apples-to-apples. Priority rows go ONLY to the hi region:
        # they are never host-sampled, so that region's counters are
        # exact whatever the overload state (background noise can't
        # even dilute its buckets).
        inv_flow, inv_hi = state.inv_flow, state.inv_hi
        with jax.named_scope("invertible"):
            if c.enable_invertible:
                inv_flow = inv_flow.update(
                    five, jnp.where(is_priority, 0, flow_w)
                )
                inv_hi = inv_hi.update(
                    five, jnp.where(is_priority, flow_w, 0)
                )
        with jax.named_scope("cms_svc_hh"):
            pods_known = (src_pod > 0) & (dst_pod > 0)
            svc_w = jnp.where(
                pods_known, rep_pkts if low else jnp.where(is_fwd, packets, 0), 0
            )
            svc_hh = state.svc_hh.update([src_pod, dst_pod], svc_w)
        with jax.named_scope("cms_dns_hh"):
            dns_hh = state.dns_hh.update([col(F.DNS_QHASH)], w_dns_req)

        with jax.named_scope("hll"):
            sk_mask = report if low else mask
            hll_flows = state.hll_flows.update(
                five, jnp.zeros_like(src_ip), sk_mask
            )
            hll_reason = state.hll_src_per_reason.update([src_ip], reason, is_drop)
            hll_pod = state.hll_src_per_pod.update(
                [src_ip],
                jnp.minimum(dst_pod, np.uint32(c.n_pods - 1)),
                is_ingress & sk_mask,
            )

        with jax.named_scope("entropy"):
            ones = (
                rep_pkts.astype(jnp.float32)
                if low
                else jnp.where(mask, packets, 0).astype(jnp.float32)
            )
            ent = state.entropy
            ent = ent.update([src_ip], jnp.zeros_like(src_ip), ones)
            ent = ent.update([dst_ip], jnp.ones_like(src_ip), ones)
            ent = ent.update(
                [ports & np.uint32(0xFFFF)], jnp.full_like(src_ip, 2), ones
            )

        # ---- apiserver latency (reference latency.go:286-301: match
        # TSval of outgoing apiserver packets to TSecr of replies) ----
        lat_key, lat_ts, lat_hist = state.lat_key, state.lat_ts, state.lat_hist
        with jax.named_scope("latency_match"):
            if c.enable_latency:
                L = c.latency_slots
                from retina_tpu.ops.hashing import hash_cols, reduce_range

                ts_ms = (col(F.TS_HI) << 12) | (col(F.TS_LO) >> 20)  # ns >> 20 ~ ms
                out_to_api = mask & (dst_ip == apiserver_ip) & (col(F.TSVAL) > 0)
                in_from_api = mask & (src_ip == apiserver_ip) & (col(F.TSECR) > 0)
                k_out = hash_cols([dst_ip, col(F.TSVAL)], 0x1A7)
                k_in = hash_cols([src_ip, col(F.TSECR)], 0x1A7)
                slot_out = jnp.where(out_to_api, reduce_range(k_out, L), L)
                lat_key = lat_key.at[slot_out].set(k_out, mode="drop")
                lat_ts = lat_ts.at[slot_out].set(ts_ms, mode="drop")
                slot_in = reduce_range(k_in, L).astype(jnp.int32)
                hit = in_from_api & (lat_key[slot_in] == k_in)
                rtt = jnp.where(hit, ts_ms - lat_ts[slot_in], 0)
                # Invalidate matched slots: later segments echoing the same
                # TSecr (normal TCP) must not re-record the sample, and a
                # recycled TSval hours later must not match a stale entry.
                lat_key = lat_key.at[jnp.where(hit, slot_in, L)].set(
                    np.uint32(0), mode="drop"
                )
                # exponential buckets: bucket = floor(log2(rtt_ms + 1)).
                bug = jnp.floor(
                    jnp.log2(rtt.astype(jnp.float32) + 1.0)
                ).astype(jnp.uint32)
                bug = jnp.minimum(bug, np.uint32(c.latency_buckets - 1))
                lat_hist = lat_hist.at[jnp.where(hit, bug, c.latency_buckets)].add(
                    jnp.where(hit, 1, 0).astype(jnp.uint32), mode="drop"
                )

        with jax.named_scope("totals"):
            # 64-bit (two-limb) accumulation of reported packets/bytes; exact
            # byte-plane sums — per-connection report accumulators are full
            # u32, so a plain batch sum could wrap before the carry applies.
            rp_lo, rp_hi = _sum64(rep_pkts)
            rb_lo, rb_hi = _sum64(rep_bytes)
            ctt = state.ct_totals
            lo_p = ctt[0] + rp_lo
            lo_b = ctt[2] + rb_lo
            ct_totals = jnp.stack(
                [
                    lo_p,
                    ctt[1] + rp_hi + (lo_p < rp_lo).astype(jnp.uint32),
                    lo_b,
                    ctt[3] + rb_hi + (lo_b < rb_lo).astype(jnp.uint32),
                ]
            )

            # totals[0] counts EVENTS REPRESENTED (sum of packet weights), not
            # rows: a combined row stands for F.PACKETS underlying events.
            n_events = jnp.sum(jnp.where(mask, packets, 0)).astype(jnp.uint32)
            totals = state.totals + jnp.stack(
                [
                    n_events,
                    jnp.sum(w_pkts).astype(jnp.uint32),
                    jnp.sum(jnp.where(is_drop, packets, 0)).astype(jnp.uint32),
                    jnp.sum(w_dns_req).astype(jnp.uint32),
                    jnp.sum(w_dns_resp).astype(jnp.uint32),
                    jnp.sum(w_retrans).astype(jnp.uint32),
                    n_reports,
                    np.uint32(0),
                ]
            )

        new_state = PipelineState(
            pod_forward=pf,
            pod_drop=pd,
            pod_tcpflags=ptf,
            pod_dns=pdns,
            pod_retrans=pret,
            node_counters=nc,
            totals=totals,
            ct_totals=ct_totals,
            flow_hh=flow_hh,
            svc_hh=svc_hh,
            dns_hh=dns_hh,
            hll_flows=hll_flows,
            hll_src_per_reason=hll_reason,
            hll_src_per_pod=hll_pod,
            entropy=ent,
            anomaly=state.anomaly,
            inv_flow=inv_flow,
            inv_hi=inv_hi,
            conntrack=ct,
            lat_key=lat_key,
            lat_ts=lat_ts,
            lat_hist=lat_hist,
        )
        summary = {
            "events": n_events,
            "ct_reports": n_reports,
            "report_mask": report,
            "report_packets": rep_pkts,
            "report_bytes": rep_bytes,
        }
        return new_state, summary

    def end_window(
        self, state: PipelineState, z_thresh: float = 4.0
    ) -> tuple[PipelineState, dict[str, jnp.ndarray]]:
        """Close an entropy window: compute entropies, update the anomaly
        EWMA, reset the window histograms. Called once per window (1s).
        Idle windows (no traffic) do not touch the baseline — see
        AnomalyEWMA.observe."""
        with jax.named_scope(SCOPE_END_WINDOW):
            h = state.entropy.entropy_bits()
            active = state.entropy.counts.sum(axis=-1) > 0
            anomaly, flags, z = state.anomaly.observe(
                h, z_thresh=z_thresh, active=active
            )
            new = dataclasses.replace(
                state, entropy=state.entropy.reset(), anomaly=anomaly
            )
        return new, {"entropy_bits": h, "anomaly": flags, "zscore": z}

    # ------------------------------------------------------------------
    @device_entry("pipeline.step", kind="jit")
    def jitted_step(self):
        return jax.jit(self.step, donate_argnums=(0,))

    @device_entry("pipeline.end_window", kind="jit")
    def jitted_end_window(self):
        return jax.jit(self.end_window, donate_argnums=(0,))
