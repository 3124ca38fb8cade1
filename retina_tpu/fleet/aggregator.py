"""Operator-side fleet aggregator: epoch alignment + on-device merge.

Ingests wire frames (fleet/codec.py) from N node agents, buckets them
by window epoch, and closes an epoch when either every expected node
has reported (``fleet_expected_nodes``) or the straggler timeout
expires after the FIRST arrival (``fleet_straggler_timeout_s``) — the
rollup never blocks on a dead node. Duplicates (same node+epoch) and
late frames (epoch at or below the watermark) are counted and dropped;
the watermark only moves forward.

The merge itself runs on device as ONE jitted batched reduction over
the stacked per-node arrays — sum for CM tables / entropy histograms /
totals (psum-style), max for HLL register banks, and a join-semilattice
fold for the heavy-hitter candidate tables (ops/topk.py). Cluster
heavy-hitter counts are then the merged CMS queried at the UNION of
every node's candidates: a key whose traffic splits across nodes is
undercounted in any single candidate table but exact (up to CMS error)
in the summed tables.

Published families (docs/metrics.md): cluster-wide top flows,
per-tenant top flows, per-service cardinality, DDoS entropy, distinct
flows — all ``fleet_*``. Label-space growth is bounded by construction:
keyed gauges are cleared and re-published each epoch, capped at
``fleet_topk_k`` cluster series plus ``fleet_tenant_series_max`` series
per tenant across at most ``fleet_max_tenants`` tenants; when over
budget the LOWEST-priority tenants are shed first (PSketch-style
priority awareness, PAPERS.md) and the shed is counted.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from retina_tpu.devprog import device_entry
from retina_tpu.fleet.codec import (
    ROLLUP_TOPIC, FleetDecodeError, FleetSnapshot, decode_snapshot,
)
from retina_tpu.log import logger, rate_limited
from retina_tpu.metrics import get_metrics
from retina_tpu.obs.recorder import get_recorder
from retina_tpu.ops.countmin import CountMinSketch
from retina_tpu.ops.entropy import EntropyWindow
from retina_tpu.ops.hyperloglog import HyperLogLog
from retina_tpu.ops.invertible import InvertibleSketch, decode_verified
from retina_tpu.ops.topk import TopKTable
from retina_tpu.pubsub import get_pubsub
from retina_tpu.utils import metric_names as mn

ENTROPY_DIMS = ("src_ip", "dst_ip", "dst_port")
_HH_FAMILIES = ("flow", "svc", "dns")

# Seed-generation reference history kept per aggregator: a live seed
# rotation is a handful of generations at most, and old generations'
# references are useless once every node has rotated past them.
_GEN_HISTORY = 8


def format_key(row: np.ndarray) -> str:
    """Stable label rendering of one candidate key row (C u32 columns)."""
    return "-".join(f"{int(c):08x}" for c in row)


class _EpochBucket:
    """Snapshots collected for one not-yet-closed epoch."""

    __slots__ = ("snaps", "first_t")

    def __init__(self, now: float) -> None:
        self.snaps: dict[str, FleetSnapshot] = {}
        self.first_t = now


class FleetAggregator:
    """Thread-safe; ``ingest`` runs on transport threads (pubsub pool /
    gRPC handlers), ``poll`` on the internal timer thread."""

    def __init__(self, cfg, supervisor=None, reship_transport=None) -> None:
        self.cfg = cfg
        self.log = logger("fleet.agg")
        self._supervisor = supervisor
        self._lock = threading.Lock()
        self._buckets: dict[int, _EpochBucket] = {}
        self._watermark = -1  # highest CLOSED epoch
        # Seed/shape references keyed by seed generation: a frame is
        # validated against ITS OWN generation's reference, so a rotated
        # node is never permanently quarantined — only a node whose
        # seeds disagree with its generation's reference is dropped
        # (``seed_mismatch``), which still catches real misconfig.
        self._gen_refs: dict[
            int, tuple[dict[str, int], dict[str, tuple]]
        ] = {}
        # Tier-2 re-ship: when configured, every merged epoch is
        # re-encoded as a (valid, tier=1) node snapshot and shipped to
        # the next rollup tier — the merge algebra is a semilattice, so
        # the root aggregator folds zone rollups exactly like node
        # frames. ``reship_transport`` injects a transport callable for
        # tests/harnesses; otherwise cfg.fleet_reship_addr dials gRPC.
        self._reshipper = None
        if reship_transport is not None or str(cfg.fleet_reship_addr):
            from retina_tpu.fleet.shipper import SnapshotShipper

            ship_cfg = dataclasses.replace(
                cfg, fleet_relay_addr=str(cfg.fleet_reship_addr)
            )
            self._reshipper = SnapshotShipper(
                ship_cfg, supervisor=supervisor,
                transport=reship_transport,
            )
            self._reshipper.tier = 1
        # jitted batched-merge executables keyed by (n_nodes, array
        # signature): re-lowering per epoch would dominate the merge.
        self._merge_cache: dict[Any, Any] = {}
        # Quorum-closed buckets awaiting merge when fleet_merge_async is
        # set: ingest only appends here (under the lock); the poll
        # thread drains it ahead of straggler checks.
        self._ready_q: deque[tuple[int, _EpochBucket]] = deque()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._sub_id: str | None = None
        # Merged-epoch history ring (timetravel/ring.py RingProtocol):
        # the aggregator OWNS its epoch ring — each merged epoch's
        # arrays are retained as a slot, so range queries (node tier and
        # the fleet query plane) cover cluster history, not just this
        # node's. Created here, not by the daemon: the ring is part of
        # the aggregator's state, and it exposes the exact
        # select/span/stats surface of the engine's SnapshotRing.
        self.epoch_ring: Any = None
        if getattr(cfg, "timetravel_enabled", False):
            from retina_tpu.timetravel.ring import SnapshotRing

            self.epoch_ring = SnapshotRing(
                cfg.timetravel_ring_windows, name="fleet",
                supervisor=supervisor,
            )
        # Rolling window of recent rollups for tests/dryrun/debug vars.
        # The retention is a plain attribute so harnesses that score a
        # fixed epoch window (fleet/churn.py) can widen it.
        self.rollups: list[dict] = []
        self.rollups_keep = 64
        self.epochs_merged = 0
        # High-water mark of concurrently-open epoch buckets; staying
        # at or under cfg.fleet_epoch_history proves the overflow
        # eviction never had to force-close an epoch (dryrun asserts
        # this at 100-agent scale).
        self.open_buckets_max = 0

    # Back-compat alias: older wiring (daemon, tests) reached the ring
    # as ``timetravel_ring``; both names see the same object.
    @property
    def timetravel_ring(self) -> Any:
        return self.epoch_ring

    @timetravel_ring.setter
    def timetravel_ring(self, ring: Any) -> None:
        self.epoch_ring = ring

    # -- lifecycle -----------------------------------------------------
    def start(self, subscribe: bool = True) -> None:
        """Start the straggler-poll thread; optionally subscribe to the
        in-process FLEET_TOPIC (the co-located transport)."""
        if subscribe and self._sub_id is None:
            from retina_tpu.fleet.codec import FLEET_TOPIC

            self._sub_id = get_pubsub().subscribe(FLEET_TOPIC, self.ingest)
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._poll_loop, name="fleet-agg", daemon=True
            )
            self._thread.start()
        if self._reshipper is not None:
            self._reshipper.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        if self._reshipper is not None:
            self._reshipper.stop(timeout_s=timeout_s)
        if self._sub_id is not None:
            from retina_tpu.fleet.codec import FLEET_TOPIC

            try:
                get_pubsub().unsubscribe(FLEET_TOPIC, self._sub_id)
            except KeyError:  # noqa: RT101 — already unsubscribed; stop is idempotent
                pass
            self._sub_id = None
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
            if self._supervisor is not None:
                self._supervisor.deregister("fleet-agg")
        self._thread = None

    def _poll_loop(self) -> None:  # runs-on: fleet-agg
        hb = None
        if self._supervisor is not None:
            hb = self._supervisor.register(
                "fleet-agg", self.cfg.watchdog_deadline_s
            )
        cadence = max(0.05, self.cfg.fleet_straggler_timeout_s / 4.0)
        while not self._stop.is_set():
            if hb is not None:
                hb.beat()
            try:
                self.poll()
            except Exception:
                get_metrics().fleet_merge_errors.inc()
                if rate_limited("fleet.poll"):
                    self.log.exception("fleet poll failed")
            if hb is not None:
                hb.park()
            self._stop.wait(cadence)

    # -- ingest --------------------------------------------------------
    def ingest(self, frame: bytes) -> bool:  # runs-on: pubsub*, grpc*  # hot-path: transport
        """Decode + bucket one wire frame. Returns True when accepted."""
        m = get_metrics()
        try:
            snap = decode_snapshot(frame)
        except FleetDecodeError as e:
            m.fleet_snapshots_dropped.labels(reason="decode").inc()
            if rate_limited("fleet.decode"):
                self.log.warning("fleet frame rejected: %s", e)
            return False
        ready = None
        with self._lock:
            if snap.epoch <= self._watermark:
                m.fleet_snapshots_dropped.labels(reason="late").inc()
                return False
            gen = int(snap.seed_gen)
            ref = self._gen_refs.get(gen)
            if ref is None:
                # First frame of this generation defines its reference;
                # bound the history so a node spraying bogus generations
                # cannot grow this dict unboundedly.
                while len(self._gen_refs) >= _GEN_HISTORY:
                    del self._gen_refs[min(self._gen_refs)]
                ref = (
                    dict(snap.seeds),
                    {k: v.shape for k, v in snap.arrays.items()},
                )
                self._gen_refs[gen] = ref
            ref_seeds, ref_shapes = ref
            if snap.seeds != ref_seeds:
                m.fleet_snapshots_dropped.labels(
                    reason="seed_mismatch"
                ).inc()
                return False
            shapes = {k: v.shape for k, v in snap.arrays.items()}
            if shapes != ref_shapes:
                m.fleet_snapshots_dropped.labels(
                    reason="shape_mismatch"
                ).inc()
                return False
            bucket = self._buckets.get(snap.epoch)
            if bucket is None:
                bucket = self._buckets[snap.epoch] = _EpochBucket(
                    time.monotonic()
                )
                if len(self._buckets) > self.open_buckets_max:
                    self.open_buckets_max = len(self._buckets)
            if snap.node in bucket.snaps:
                m.fleet_snapshots_dropped.labels(reason="duplicate").inc()
                return False
            bucket.snaps[snap.node] = snap
            m.fleet_snapshots_received.labels(node=snap.node).inc()
            expected = int(self.cfg.fleet_expected_nodes)
            if expected > 0 and len(bucket.snaps) >= expected:
                ready = [(snap.epoch, self._buckets.pop(snap.epoch))]
            else:
                ready = self._overflow_locked()
            if ready and self.cfg.fleet_merge_async:
                # Hand closed buckets to the poll thread: the transport
                # handler must not pay for the merge (or its compile).
                self._ready_q.extend(ready)
                ready = None
        for epoch, b in ready or ():
            try:
                self._merge_epoch(epoch, b, straggled=False)
            except Exception:
                m.fleet_merge_errors.inc()
                if rate_limited("fleet.merge"):
                    self.log.exception("fleet merge failed (epoch %d)", epoch)
        return True

    def _overflow_locked(self) -> list[tuple[int, _EpochBucket]]:
        """Bound open-epoch memory: keep at most fleet_epoch_history
        buckets, force-closing the oldest (counts as straggled)."""
        out = []
        limit = max(1, int(self.cfg.fleet_epoch_history))
        while len(self._buckets) > limit:
            oldest = min(self._buckets)
            out.append((oldest, self._buckets.pop(oldest)))
        return out

    def poll(self, now: float | None = None) -> int:
        """Close epochs whose straggler timeout has expired. Returns the
        number of epochs merged."""
        now = time.monotonic() if now is None else now
        timeout = self.cfg.fleet_straggler_timeout_s
        ready: list[tuple[int, _EpochBucket, bool]] = []
        with self._lock:
            # Quorum-closed buckets deferred by ingest (fleet_merge_async)
            # merge first — they are complete and older than any
            # still-open straggler.
            while self._ready_q:
                epoch, bucket = self._ready_q.popleft()
                ready.append((epoch, bucket, False))
            for epoch in sorted(self._buckets):
                if now - self._buckets[epoch].first_t >= timeout:
                    ready.append((epoch, self._buckets.pop(epoch), True))
        for epoch, bucket, straggled in ready:
            try:
                self._merge_epoch(epoch, bucket, straggled=straggled)
            except Exception:
                get_metrics().fleet_merge_errors.inc()
                if rate_limited("fleet.merge"):
                    self.log.exception("fleet merge failed (epoch %d)", epoch)
        return len(ready)

    # -- merge ---------------------------------------------------------
    @device_entry("fleet.merge", kind="jit")
    def _merge_fn(self, n: int, seeds: dict[str, int], names: tuple):
        key = (n, names, tuple(sorted(seeds.items())))
        fn = self._merge_cache.get(key)
        if fn is not None:
            return fn

        def merge(stacked: dict[str, jnp.ndarray]) -> dict[str, Any]:
            out: dict[str, Any] = {}
            for name in names:
                arr = stacked[name]
                if name.startswith("hll_"):
                    out[name] = jnp.max(arr, axis=0)
                elif name.endswith("_keys") or name.endswith("_counts"):
                    continue  # folded below as (keys, counts) pairs
                else:
                    out[name] = jnp.sum(arr, axis=0)
            for fam in _HH_FAMILIES:
                kname, cname = f"{fam}_keys", f"{fam}_counts"
                if kname not in stacked:  # noqa: RT212 — dict-key test, static per jit cache key
                    continue
                seed = int(seeds.get(fam, 0))
                t = TopKTable(
                    stacked[kname][0], stacked[cname][0], seed=seed
                )
                for i in range(1, n):
                    t = t.merge(TopKTable(
                        stacked[kname][i], stacked[cname][i], seed=seed,
                    ))
                out[kname], out[cname] = t.key_rows, t.counts
            return out

        # donate_argnums=(0,): `stacked` is built fresh per epoch in
        # _merge_epoch (jnp.asarray of a host stack) and never read
        # after this call — donating lets XLA fold the (n, ...) stacks
        # into the reduction outputs instead of holding both the stack
        # and the merged arrays live (RT302; found by the
        # device-program donation audit).
        fn = jax.jit(merge, donate_argnums=(0,))
        self._merge_cache[key] = fn
        return fn

    def _merge_epoch(  # may-block: device merge on the caller's thread — transport-lane reach is the sync cfg.fleet_merge_async=False mode (tests/bench); production fleets set it True and merge on the poll thread (windowed _ready_q handoff)
        self, epoch: int, bucket: _EpochBucket, straggled: bool
    ) -> None:
        t0 = time.monotonic()
        m = get_metrics()
        snaps = sorted(bucket.snaps.values(), key=lambda s: s.node)
        if not snaps:
            return
        # The shipped trace id is the window epoch by construction
        # (below); the span opens under the epoch and takes the shipped
        # id, should it ever differ, when it closes.
        span = get_recorder().span(mn.STAGE_AGG_MERGE, int(epoch))
        # Mid-rotation an epoch can hold frames from more than one seed
        # generation. Cross-generation sketches don't merge, so take the
        # dominant generation (ties break toward the NEWER one — the
        # rotation target) and count the minority as per-epoch skew
        # drops; those nodes re-admit next epoch, nothing is quarantined
        # permanently.
        by_gen: dict[int, list[FleetSnapshot]] = {}
        for s in snaps:
            by_gen.setdefault(int(s.seed_gen), []).append(s)
        gen = max(by_gen, key=lambda g: (len(by_gen[g]), g))
        if len(by_gen) > 1:
            skewed = len(snaps) - len(by_gen[gen])
            m.fleet_snapshots_dropped.labels(reason="gen_skew").inc(skewed)
            if rate_limited("fleet.gen_skew"):
                self.log.warning(
                    "fleet epoch %d: %d frame(s) outside dominant seed "
                    "generation %d dropped (rotation in flight)",
                    epoch, skewed, gen,
                )
            snaps = by_gen[gen]
        # Cross-process lineage: the shipped trace context carries the
        # window-epoch trace ID from the node's close path; frames from
        # trace-less (older) nodes fall back to the epoch itself, which
        # is the same value by construction.
        trace_id = next(
            (int(s.trace["tid"]) for s in snaps
             if s.trace is not None and "tid" in s.trace),
            int(epoch),
        )
        with self._lock:
            self._watermark = max(self._watermark, epoch)
        names = sorted(
            set.intersection(*(set(s.arrays) for s in snaps))
        )
        stacked = {
            name: jnp.asarray(
                np.stack([s.arrays[name] for s in snaps])
            )
            for name in names
        }
        seeds = snaps[0].seeds
        merged = self._merge_fn(len(snaps), seeds, tuple(names))(stacked)
        if self.timetravel_ring is not None:
            # Merged-epoch snapshot into the fleet ring: already a
            # valid fold operand (same algebra, same catalog), so
            # cluster-wide range queries are one more fold away. Host
            # readback here is fine — the poll thread does host work
            # for the rollup anyway.
            try:
                self.timetravel_ring.append_host(
                    epoch,
                    {k: np.asarray(v) for k, v in merged.items()},
                    float(snaps[0].window_s),
                    dict(seeds),
                )
            except Exception:
                if rate_limited("fleet.ttring"):
                    self.log.exception("timetravel ring append failed")
        if self._reshipper is not None:
            # Re-ship the merged epoch one tier up: the merged arrays
            # are themselves a valid node snapshot (same catalog, same
            # dtypes — the algebra is closed under merge), so the next
            # tier ingests this aggregator as if it were one big node.
            self._reshipper.offer(
                epoch,
                {k: np.asarray(v) for k, v in merged.items()},
                float(snaps[0].window_s),
                dict(seeds),
                seed_gen=gen,
            )
            m.fleet_rollups_reshipped.inc()
        rollup = self._rollup(epoch, snaps, merged, seeds)
        rollup["straggled"] = straggled
        rollup["seed_gen"] = gen
        rollup["merge_seconds"] = time.monotonic() - t0
        self._publish(rollup)
        span.trace_id = trace_id
        span.end()
        m.fleet_windows_merged.inc()
        if straggled:
            m.fleet_windows_stragglers.inc()
        m.fleet_merge_seconds.set(rollup["merge_seconds"])
        with self._lock:
            self.epochs_merged += 1
            self.rollups.append(rollup)
            del self.rollups[:-self.rollups_keep]

    # -- rollup computation -------------------------------------------
    def _cluster_topk(
        self,
        fam: str,
        snaps: list[FleetSnapshot],
        merged: dict[str, Any],
        seeds: dict[str, int],
        k: int,
        candidates: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k of the candidate set, counted by the summed CMS (exact
        cross-node totals up to CMS overestimate). ``candidates``
        defaults to the union of every node's shipped candidate tables;
        with invertible snapshots the caller passes the keys DECODED
        from merged sketch state instead — no node shipped them."""
        if candidates is not None:
            cand = [candidates.astype(np.uint32).reshape(-1, 4)]
        else:
            cand = []
            for s in snaps:
                keys = s.arrays.get(f"{fam}_keys")
                counts = s.arrays.get(f"{fam}_counts")
                if keys is None or counts is None:
                    continue
                cand.append(keys[counts > 0])
        if not cand:
            return np.zeros((0, 0), np.uint32), np.zeros((0,), np.uint64)
        union = np.unique(np.concatenate(cand, axis=0), axis=0)
        if not len(union):
            return union, np.zeros((0,), np.uint64)
        cms = CountMinSketch(
            table=merged[f"{fam}_cms"],
            seed=int(seeds.get(fam, 0)),
        )
        key_cols = [jnp.asarray(union[:, c]) for c in range(union.shape[1])]
        est = np.asarray(cms.query(key_cols)).astype(np.uint64)
        order = np.argsort(est)[::-1][:k]
        sel = est[order] > 0
        return union[order][sel], est[order][sel]

    def _invertible_decode(
        self, merged: dict[str, Any], seeds: dict[str, int]
    ) -> dict[str, Any] | None:
        """Recover CLUSTER-WIDE heavy keys from the merged invertible
        arrays (ops/invertible.py), verified against the merged flow
        CMS. The arrays are pure sums, so the fleet-summed sketch
        decodes exactly like a single node's — keys that were too light
        to decode on any one node surface once their cluster-wide
        weight dominates a bucket, and no node shipped a raw key.
        Returns sorted-descending ``keys (N, 4)``, ``est (N,)``,
        ``tier (N,)`` (1 = priority region) plus per-source packet
        attribution ``sources = (src_ips, packets)`` for DDoS
        attribution; None when the epoch carried no invertible state."""
        if "inv_flow_planes" not in merged or "flow_cms" not in merged:
            return None
        cms = CountMinSketch(
            table=merged["flow_cms"], seed=int(seeds.get("flow", 0))
        )
        all_keys, all_est, all_tier = [], [], []
        for region, tier in (("inv_flow", 0), ("inv_hi", 1)):
            if f"{region}_planes" not in merged:
                continue
            inv = InvertibleSketch(
                planes=jnp.asarray(merged[f"{region}_planes"]),
                weights=jnp.asarray(merged[f"{region}_weights"]),
                seed=int(seeds.get(region, 0)),
            )
            cols, est, ok = decode_verified(inv, cms)
            okh = np.asarray(ok, bool)
            keys = np.stack([np.asarray(c) for c in cols], axis=1)[okh]
            all_keys.append(keys.astype(np.uint32))
            all_est.append(np.asarray(est)[okh].astype(np.uint64))
            all_tier.append(np.full(len(keys), tier, np.uint32))
        if not all_keys:
            return None
        keys = np.concatenate(all_keys)
        est = np.concatenate(all_est)
        tier = np.concatenate(all_tier)
        if len(keys):
            # A key decodes from up to depth buckets per region.
            uniq, idx = np.unique(keys, axis=0, return_index=True)
            keys, est, tier = uniq, est[idx], tier[idx]
            order = np.argsort(est)[::-1]
            keys, est, tier = keys[order], est[order], tier[order]
            srcs, sinv = np.unique(keys[:, 0], return_inverse=True)
            spk = np.zeros(len(srcs), np.uint64)
            np.add.at(spk, sinv, est)
            sorder = np.argsort(spk)[::-1]
            sources = (srcs[sorder], spk[sorder])
        else:
            sources = (
                np.zeros((0,), np.uint32), np.zeros((0,), np.uint64)
            )
        return {"keys": keys, "est": est, "tier": tier,
                "sources": sources}

    def _rollup(
        self,
        epoch: int,
        snaps: list[FleetSnapshot],
        merged: dict[str, Any],
        seeds: dict[str, int],
    ) -> dict:
        cfg = self.cfg
        k = int(cfg.fleet_topk_k)
        rollup: dict[str, Any] = {
            "epoch": epoch,
            "nodes": [s.node for s in snaps],
            "window_s": snaps[0].window_s,
        }
        inv = None
        if "inv_flow_planes" in merged:
            try:
                inv = self._invertible_decode(merged, seeds)
            except Exception:
                get_metrics().fleet_invertible_decode_failed.inc()
                if rate_limited("fleet.invdec"):
                    self.log.exception("fleet invertible decode failed")
        if inv is not None:
            rollup["invertible"] = inv
        # Cluster-wide heavy hitters per family. With invertible state
        # in the epoch, the flow candidate set is the keys decoded from
        # MERGED sketch arrays (nodes shipped no raw keys); otherwise
        # it is the union of per-node candidate tables.
        for fam in _HH_FAMILIES:
            if f"{fam}_cms" not in merged:
                continue
            cand = (
                inv["keys"]
                if fam == "flow" and inv is not None and len(inv["keys"])
                else None
            )
            keys, counts = self._cluster_topk(
                fam, snaps, merged, seeds, k, candidates=cand
            )
            rollup[f"top_{fam}"] = (keys, counts)
        # Per-service (per-pod) distinct-source cardinality.
        if "hll_src_per_pod" in merged:
            hll = HyperLogLog(
                registers=merged["hll_src_per_pod"],
                seed=int(seeds.get("hll_src_per_pod", 0)),
            )
            est = np.asarray(hll.estimate())
            top = np.argsort(est)[::-1][: int(cfg.fleet_service_top)]
            rollup["service_cardinality"] = [
                (int(i), float(est[i])) for i in top if est[i] >= 1.0
            ]
        if "hll_flows" in merged:
            hll = HyperLogLog(
                registers=merged["hll_flows"],
                seed=int(seeds.get("hll_flows", 0)),
            )
            rollup["distinct_flows"] = float(np.asarray(hll.estimate())[0])
        # Cluster DDoS entropy of the merged histograms: exactly the
        # single-node estimate of the union stream (ops/entropy.py).
        if "entropy" in merged:
            ent = EntropyWindow(
                counts=merged["entropy"],
                seed=int(seeds.get("entropy", 0)),
            )
            bits = np.asarray(ent.entropy_bits())
            rollup["entropy_bits"] = {
                dim: float(bits[i])
                for i, dim in enumerate(ENTROPY_DIMS)
                if i < len(bits)
            }
        if "totals" in merged:
            rollup["totals"] = np.asarray(merged["totals"])
        # Per-tenant heavy hitters under the cardinality guardrails.
        rollup["tenants"] = self._tenant_rollups(
            snaps, seeds,
            inv_keys=(
                inv["keys"]
                if inv is not None and len(inv["keys"]) else None
            ),
        )
        return rollup

    def _tenant_rollups(
        self,
        snaps: list[FleetSnapshot],
        seeds: dict[str, int],
        inv_keys: np.ndarray | None = None,
    ) -> dict[str, dict]:
        """Per-tenant flow top-k with the label-space guardrails: at
        most ``fleet_max_tenants`` tenants (lowest priority shed first),
        at most ``fleet_tenant_series_max`` series each."""
        cfg = self.cfg
        m = get_metrics()
        by_tenant: dict[str, list[FleetSnapshot]] = {}
        prio: dict[str, int] = {}
        for s in snaps:
            by_tenant.setdefault(s.tenant, []).append(s)
            prio[s.tenant] = max(prio.get(s.tenant, s.priority), s.priority)
        ranked = sorted(by_tenant, key=lambda t: (-prio[t], t))
        kept = ranked[: max(0, int(cfg.fleet_max_tenants))]
        for t in ranked[len(kept):]:
            m.fleet_tenants_shed.inc()
            if rate_limited("fleet.tenant_shed"):
                self.log.warning(
                    "fleet: tenant %s shed (priority %d, budget %d)",
                    t, prio[t], cfg.fleet_max_tenants,
                )
        cap = max(1, int(cfg.fleet_tenant_series_max))
        out: dict[str, dict] = {}
        for tenant in kept:
            group = by_tenant[tenant]
            tables = [
                s.arrays["flow_cms"] for s in group
                if "flow_cms" in s.arrays
            ]
            if not tables:
                continue
            merged_cms = {
                "flow_cms": jnp.sum(
                    jnp.asarray(np.stack(tables)), axis=0
                )
            }
            keys, counts = self._cluster_topk(
                "flow", group, merged_cms, seeds,
                min(int(cfg.fleet_topk_k), cap),
                candidates=inv_keys,
            )
            if len(keys) > cap:  # defense in depth; min() above caps
                m.fleet_series_capped.inc(len(keys) - cap)
                keys, counts = keys[:cap], counts[:cap]
            out[tenant] = {
                "priority": prio[tenant],
                "top_flows": (keys, counts),
                "nodes": [s.node for s in group],
            }
        return out

    # -- publication ---------------------------------------------------
    def _publish(self, rollup: dict) -> None:
        m = get_metrics()
        m.fleet_nodes_reporting.set(len(rollup["nodes"]))
        # Keyed gauges: clear-and-republish each epoch so the exported
        # label space never exceeds this epoch's (capped) series set —
        # the guardrail is structural, not advisory.
        m.fleet_top_flows.clear()
        m.fleet_tenant_top_flows.clear()
        m.fleet_service_cardinality.clear()
        m.fleet_tenant_series.clear()
        m.fleet_invertible_sources.clear()
        inv = rollup.get("invertible")
        if inv is not None:
            m.fleet_invertible_keys.set(float(len(inv["keys"])))
            srcs, spk = inv["sources"]
            cap = max(0, int(self.cfg.fleet_topk_k))
            for ip, pk in zip(srcs[:cap], spk[:cap]):
                m.fleet_invertible_sources.labels(
                    key=f"{int(ip):08x}"
                ).set(float(pk))
        for fam, gauge in (("flow", m.fleet_top_flows),):
            pair = rollup.get(f"top_{fam}")
            if pair is None:
                continue
            keys, counts = pair
            for row, count in zip(keys, counts):
                gauge.labels(key=format_key(row)).set(float(count))
        for idx, est in rollup.get("service_cardinality", ()):
            m.fleet_service_cardinality.labels(service=f"pod{idx}").set(est)
        for dim, bits in rollup.get("entropy_bits", {}).items():
            m.fleet_entropy_bits.labels(dimension=dim).set(bits)
        if "distinct_flows" in rollup:
            m.fleet_distinct_flows.set(rollup["distinct_flows"])
        for tenant, tr in rollup["tenants"].items():
            keys, counts = tr["top_flows"]
            for row, count in zip(keys, counts):
                m.fleet_tenant_top_flows.labels(
                    tenant=tenant, key=format_key(row)
                ).set(float(count))
            m.fleet_tenant_series.labels(tenant=tenant).set(len(keys))
        get_pubsub().publish(ROLLUP_TOPIC, rollup)

    # -- observability -------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            out = {
                "watermark": self._watermark,
                "open_epochs": sorted(self._buckets),
                "ready_q": len(self._ready_q),
                "epochs_merged": self.epochs_merged,
                "generations": sorted(self._gen_refs),
                "nodes_last": (
                    self.rollups[-1]["nodes"] if self.rollups else []
                ),
            }
        if self._reshipper is not None:
            out["reship"] = self._reshipper.stats()
        return out
