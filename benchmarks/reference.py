"""The plain reference and the comparison that decides ``correct``.

Exact answers for the events a run offered, in numpy and dicts only. It
imports nothing of ``retina_tpu`` and takes nothing the agent made: the
record layout comes from ``traffic.py``, the drop-reason names (labels
of the scraped series) are restated below. The agent's semantics it
restates: the deployment filters to IPs of interest (an event counts
when either endpoint is a registered pod); a forwarded or dropped event
counts at its local pod, which is the destination on ingress and the
source on egress; counters are cumulative since boot.
"""

from __future__ import annotations

import re

import numpy as np

from traffic import (
    BYTES, DIR_INGRESS, DROP_REASON, DST_IP, META, PACKETS, POD_NET, PORTS,
    SRC_IP, VERDICT, VERDICT_DROPPED, VERDICT_FORWARDED,
)

PREFIX = "networkobservability_"
N_REASONS = 16
DROP_REASONS = {
    0: "unknown", 1: "iptable_rule_drop", 2: "iptable_nat_drop",
    3: "tcp_connect_basic", 4: "tcp_accept_basic", 5: "conntrack_add_drop",
    6: "softnet_drop", 7: "listen_overflow", 8: "policy_denied",
    9: "invalid_packet", 10: "invalid_source_ip", 11: "conntrack_invalid",
    12: "unsupported_proto", 13: "cilium_other",
}


def ip_str(u: int) -> str:
    return ".".join(str((u >> s) & 0xFF) for s in (24, 16, 8, 0))


class Counts:
    """Exact per-pod counters of a set of records."""

    def __init__(self, n_endpoints: int):
        self.n = n_endpoints
        self.fwd = np.zeros((n_endpoints, 2, 2), np.int64)  # pod,dir,{pk,by}
        self.drop = np.zeros((n_endpoints, N_REASONS, 2), np.int64)
        self.events = 0

    def add(self, rec: np.ndarray, times: int = 1) -> "Counts":
        if not len(rec) or not times:
            return self
        n = self.n
        ingress = ((rec[:, META] >> np.uint32(4)) & np.uint32(0xF)) \
            == DIR_INGRESS
        src = rec[:, SRC_IP].astype(np.int64) - POD_NET
        dst = rec[:, DST_IP].astype(np.int64) - POD_NET
        known_src = (src >= 1) & (src < n)
        known_dst = (dst >= 1) & (dst < n)
        interest = known_src | known_dst
        local = np.where(ingress, dst, src)
        ok = interest & np.where(ingress, known_dst, known_src)
        pk = rec[:, PACKETS].astype(np.float64)
        by = rec[:, BYTES].astype(np.float64)
        self.events += times * int(pk[interest].sum())
        d = np.where(ingress, 0, 1)
        fwd = ok & (rec[:, VERDICT] == VERDICT_FORWARDED)
        idx = local[fwd] * 2 + d[fwd]
        for lane, w in ((0, pk), (1, by)):
            self.fwd[:, :, lane] += times * np.bincount(
                idx, weights=w[fwd], minlength=n * 2
            ).astype(np.int64).reshape(n, 2)
        drp = ok & (rec[:, VERDICT] == VERDICT_DROPPED)
        reason = np.minimum(rec[:, DROP_REASON], N_REASONS - 1).astype(
            np.int64)
        idx = local[drp] * N_REASONS + reason[drp]
        for lane, w in ((0, pk), (1, by)):
            self.drop[:, :, lane] += times * np.bincount(
                idx, weights=w[drp], minlength=n * N_REASONS
            ).astype(np.int64).reshape(n, N_REASONS)
        return self

    def pod_series(self) -> tuple[dict, dict]:
        """Nonzero cells keyed like the scrape: forward by (pod,
        direction, lane), drop by (pod, reason name, lane)."""
        fwd, drop = {}, {}
        lanes = ("count", "bytes")
        for p, d, lane in zip(*np.nonzero(self.fwd)):
            fwd[(f"pod-{p}", ("ingress", "egress")[d], lanes[lane])] = int(
                self.fwd[p, d, lane])
        for p, r, lane in zip(*np.nonzero(self.drop)):
            drop[(f"pod-{p}", DROP_REASONS.get(int(r), str(int(r))),
                  lanes[lane])] = int(self.drop[p, r, lane])
        return fwd, drop


def offered(pool: np.ndarray, total_rows: int, n_endpoints: int) -> Counts:
    """Counters after ``total_rows`` rows walked from the pool's start,
    lap after lap: whole laps add up, so the pool is read at most
    twice whatever the run's length."""
    laps, rem = divmod(total_rows, len(pool))
    c = Counts(n_endpoints)
    # In pieces: the temporaries of one pass stay well under the pool.
    for a in range(0, len(pool), 1 << 21):
        b = min(len(pool), a + (1 << 21))
        c.add(pool[a:b], laps)
        if rem > a:
            c.add(pool[a:min(b, rem)], 1)
    return c


def flows(pool: np.ndarray, total_rows: int, n_endpoints: int,
          k: int) -> tuple[int, list[tuple]]:
    """(distinct 5-tuples of interest, the k heaviest as the label
    tuples of the scraped heavy-hitter series) after ``total_rows``."""
    laps, rem = divmod(total_rows, len(pool))
    src = pool[:, SRC_IP].astype(np.int64) - POD_NET
    dst = pool[:, DST_IP].astype(np.int64) - POD_NET
    interest = ((src >= 1) & (src < n_endpoints)) \
        | ((dst >= 1) & (dst < n_endpoints))
    seen = interest if laps else interest & (np.arange(len(pool)) < rem)
    proto = pool[:, META] >> np.uint32(24)
    cols = (pool[:, SRC_IP], pool[:, DST_IP], pool[:, PORTS], proto)
    key = np.zeros(len(pool), np.uint64)
    for c, mul in zip(cols, (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                             0x165667B19E3779F9, 0x27D4EB2F165667C5)):
        key = (key ^ c.astype(np.uint64)) * np.uint64(mul)
        key ^= key >> np.uint64(29)
    rows = np.flatnonzero(seen)
    _, first, inverse = np.unique(key[rows], return_index=True,
                                  return_inverse=True)
    weight = np.full(len(rows), laps, np.int64) + (rows < rem)
    counts = np.bincount(inverse, weights=weight).astype(np.int64)
    top = np.argsort(-counts, kind="stable")[:k]
    out = []
    for i in rows[first[top]]:
        s, d, ports, p = (int(c[i]) for c in cols)
        out.append((ip_str(s), ip_str(d), str(ports >> 16),
                    str(ports & 0xFFFF),
                    {6: "TCP", 17: "UDP"}.get(p, str(p))))
    return len(counts), out


# -- the scrape ------------------------------------------------------------
_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


class Scrape:
    """One parsed ``/metrics`` body (text exposition format)."""

    def __init__(self, text: str):
        self.samples: dict[str, list[tuple[dict, float]]] = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            m = _SAMPLE.match(line)
            if not m:
                continue
            name, labels, value = m.groups()
            try:
                v = float(value)
            except ValueError:
                continue
            self.samples.setdefault(name, []).append(
                (dict(_LABEL.findall(labels)) if labels else {}, v))

    def get(self, name: str) -> list[tuple[dict, float]]:
        """Samples of a series, with or without the counter suffix."""
        full = PREFIX + name
        return self.samples.get(full, []) + self.samples.get(
            full + "_total", [])

    def total(self, name: str, **labels) -> float:
        return sum(v for lab, v in self.get(name)
                   if all(lab.get(k) == x for k, x in labels.items()))

    def pod_series(self) -> tuple[dict, dict]:
        fwd, drop = {}, {}
        for lane in ("count", "bytes"):
            for lab, v in self.get(f"adv_forward_{lane}"):
                if v:
                    fwd[(lab["podname"], lab["direction"], lane)] = int(v)
            for lab, v in self.get(f"adv_drop_{lane}"):
                if v:
                    drop[(lab["podname"], lab["reason"], lane)] = int(v)
        return fwd, drop

    def heavy_flows(self) -> set:
        return {
            tuple(lab[k] for k in ("src_ip", "dst_ip", "src_port",
                                   "dst_port", "protocol"))
            for lab, _ in self.get("sketch_heavy_hitter_flow_packets")
        }


# Series that must read zero: the agent keeps running through every one
# of them, which is right in production (drop and count, rebuild,
# restart) and is a broken guarantee in a run that claims exact counters.
ZERO_SERIES = (
    "tpu_degraded_mode", "tpu_engine_restarts", "engine_errors_counter",
    "watchdog_stalls_counter", "thread_restarts_counter",
    "plugin_restarts_counter", "lost_events_counter",
    "tpu_overload_state", "tpu_events_sampled_counter",
)


def health_nonzero(scrape: Scrape) -> dict:
    """Nonzero members of ZERO_SERIES, as {series{labels}: value}."""
    bad = {}
    for name in ZERO_SERIES:
        for lab, v in scrape.get(name):
            if v:
                lab_s = ",".join(f"{k}={x}" for k, x in sorted(lab.items()))
                bad[f"{name}{{{lab_s}}}"] = v
    return bad


def mismatched(got: dict, want: dict) -> list[tuple]:
    """Keys on which two series dicts differ, with both values."""
    return [(k, got.get(k, 0), want.get(k, 0))
            for k in sorted(set(got) | set(want))
            if got.get(k, 0) != want.get(k, 0)]


class Verdict:
    """The numbers compared, each beside its limit. ``correct`` is
    every number within its limit."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, float, str, float]] = []
        self.notes: dict[str, object] = {}

    def hold(self, name: str, value: float, limit: float,
             op: str = "<=") -> None:
        self.rows.append((name, value, op, limit))

    @property
    def correct(self) -> bool:
        return all(v <= lim if op == "<=" else v >= lim
                   for _, v, op, lim in self.rows)

    def failed_names(self) -> list[str]:
        return [n for n, v, op, lim in self.rows
                if not (v <= lim if op == "<=" else v >= lim)]

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim, "must_be": op}
                for n, v, op, lim in self.rows}


def compare(scrape: Scrape, pool: np.ndarray, total_rows: int,
            n_endpoints: int, held: dict, verdict: Verdict) -> None:
    """Hold what the settled scrape says against the reference: the
    per-pod counters exactly, the sketches and conntrack's accounting
    by the limits the configuration's file gives under ``held``."""
    want = offered(pool, total_rows, n_endpoints)
    want_f, want_d = want.pod_series()
    got_f, got_d = scrape.pod_series()
    bad_f, bad_d = mismatched(got_f, want_f), mismatched(got_d, want_d)
    verdict.hold("pod_forward_series_mismatched", len(bad_f), 0)
    verdict.hold("pod_drop_series_mismatched", len(bad_d), 0)
    counted = int(scrape.total("adv_forward_count")
                  + scrape.total("adv_drop_count"))
    verdict.hold("events_unaccounted", abs(want.events - counted), 0)
    distinct, top = flows(pool, total_rows, n_endpoints, 50)
    scraped = scrape.heavy_flows()
    est = scrape.total("sketch_distinct_flows")
    verdict.hold("heavy_hitter_recall_at_50",
                 sum(t in scraped for t in top) / max(len(top), 1),
                 held["heavy_hitter_recall_at_50_min"], ">=")
    verdict.hold("hll_distinct_flows_rel_err",
                 abs(est - distinct) / max(distinct, 1),
                 held["hll_distinct_flows_rel_err_max"])
    if "conntrack_packets_share_min" in held:
        verdict.hold("conntrack_packets_share",
                     scrape.total("conntrack_packets", direction="total")
                     / max(want.events, 1),
                     held["conntrack_packets_share_min"], ">=")
    verdict.notes.update(
        forward_series=len(want_f), drop_series=len(want_d),
        reference_events=want.events, scraped_events=counted,
        first_mismatches=[
            {"key": list(k), "agent": g, "reference": w}
            for k, g, w in (bad_f + bad_d)[:5]],
        distinct_flows=distinct, hll_estimate=est,
        heavy_series_scraped=len(scraped),
        active_connections=scrape.total("active_connections"),
    )
