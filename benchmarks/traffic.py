"""The benchmark's one traffic generator.

A traffic mix is a data file, ``benchmarks/traffic/<name>.json``; this
module is the only code that reads one. It makes the pool of event
records from ``--seed`` (a copy of ``retina_tpu.events.synthetic``'s
sampler, kept here so that no later PR can change the yardstick by
changing the program's generator), and it paces the pool into the agent
open loop: at times fixed by the schedule, never by the agent.

Record layout (``retina_tpu/events/schema.py``; 16 uint32 lanes, 64
bytes an event) is the system's input format, restated here as
constants.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Column indices of one event record.
TS_LO, TS_HI, SRC_IP, DST_IP, PORTS, META, BYTES, PACKETS = range(8)
VERDICT, DROP_REASON, TSVAL, TSECR, DNS, DNS_QHASH, EVENT_TYPE, IFINDEX = (
    range(8, 16))
NUM_FIELDS = 16
OP_FROM_NETWORK, OP_TO_NETWORK = 2, 3
DIR_INGRESS, DIR_EGRESS = 1, 2
VERDICT_FORWARDED, VERDICT_DROPPED = 1, 2
EV_FORWARD, EV_DROP, EV_DNS_REQ, EV_DNS_RESP = 0, 1, 2, 3
PROTO_TCP, PROTO_UDP = 6, 17
TCP_SYN, TCP_ACK = 1 << 1, 1 << 4
POD_NET = 0x0A000000  # 10.0.0.0: endpoint i owns POD_NET + i


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic file. Every field is data; nothing here is a cell's
    name."""

    name: str
    n_flows: int
    n_endpoints: int
    zipf_a: float
    drop_fraction: float
    dns_fraction: float
    pool_events: int  # fresh events made at set-up, walked lap after lap
    rate_events_per_s: int  # offered, fixed: found once by the ladder
    ticks_per_s: float  # hand-overs to the source a second
    block_rows: int  # largest block handed to the source at once
    warmup_windows: int  # windows of load before the measured one opens
    poll_interval_s: float  # the scrape cadence of the poller


def load_mix(name: str, rehearse: bool = False,
             rate: int | None = None) -> Mix:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        doc = json.load(f)
    if rehearse:
        doc.update(doc.get("rehearse", {}))
    if rate is not None:
        doc["rate_events_per_s"] = rate
    fields = {f.name for f in dataclasses.fields(Mix)} - {"name"}
    missing = sorted(fields - set(doc))
    if missing:
        raise ValueError(f"traffic file {name}.json lacks {missing}")
    mix = Mix(name=name, **{k: doc[k] for k in fields})
    if mix.rate_events_per_s < mix.ticks_per_s:
        raise ValueError("rate_events_per_s gives a tick no rows")
    return mix


def make_pool(mix: Mix, seed: int) -> np.ndarray:
    """``(pool_events, 16)`` uint32 records from the seed: a table of
    ``n_flows`` 5-tuples between the registered endpoints, sampled with
    Zipf popularity; drops, DNS, sizes and directions drawn per event."""
    rng = np.random.default_rng(seed)
    n = mix.n_flows
    src_ip = (POD_NET + rng.integers(1, mix.n_endpoints, n)).astype(np.uint32)
    dst_ip = (POD_NET + rng.integers(1, mix.n_endpoints, n)).astype(np.uint32)
    sport = rng.integers(1024, 65536, n).astype(np.uint32)
    dport = rng.choice(np.array([80, 443, 53, 8080, 5432], np.uint32), n)
    proto = np.where(rng.random(n) < 0.8, PROTO_TCP, PROTO_UDP).astype(
        np.uint32)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-mix.zipf_a)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    m = mix.pool_events
    rec = np.zeros((m, NUM_FIELDS), np.uint32)
    # In pieces, so that the temporaries stay small beside the pool.
    for a in range(0, m, 1 << 20):
        b = min(m, a + (1 << 20))
        _fill(rec[a:b], rng, cdf, src_ip, dst_ip, sport, dport, proto, mix)
    return rec


def _fill(rec, rng, cdf, src_ip, dst_ip, sport, dport, proto, mix) -> None:
    k = len(rec)
    fid = np.searchsorted(cdf, rng.random(k), side="right")
    rec[:, SRC_IP] = src_ip[fid]
    rec[:, DST_IP] = dst_ip[fid]
    rec[:, PORTS] = (sport[fid] << np.uint32(16)) | dport[fid]
    flags = np.where(rng.random(k) < 0.05, TCP_SYN, TCP_ACK).astype(np.uint32)
    obs = np.where(rng.random(k) < 0.5, OP_FROM_NETWORK,
                   OP_TO_NETWORK).astype(np.uint32)
    direction = np.where(obs == OP_FROM_NETWORK, DIR_INGRESS,
                         DIR_EGRESS).astype(np.uint32)
    rec[:, META] = ((proto[fid] << np.uint32(24)) | (flags << np.uint32(16))
                    | (obs << np.uint32(8)) | (direction << np.uint32(4)))
    rec[:, BYTES] = rng.integers(64, 1500, k).astype(np.uint32)
    rec[:, PACKETS] = 1
    dropped = rng.random(k) < mix.drop_fraction
    rec[:, VERDICT] = np.where(dropped, VERDICT_DROPPED, VERDICT_FORWARDED)
    rec[:, DROP_REASON] = np.where(dropped, rng.integers(1, 8, k), 0)
    rec[:, EVENT_TYPE] = np.where(dropped, EV_DROP, EV_FORWARD)
    is_dns = rng.random(k) < mix.dns_fraction
    is_resp = is_dns & (rng.random(k) < 0.5)
    rec[is_dns, EVENT_TYPE] = np.where(is_resp[is_dns], EV_DNS_RESP,
                                       EV_DNS_REQ)
    qtype = rng.choice(np.array([1, 28, 5], np.uint32), k)
    qlen = rng.integers(8, 17, k).astype(np.uint32)
    rec[is_dns, DNS] = (qtype[is_dns] << np.uint32(16)) | qlen[is_dns]
    rec[is_dns, DNS_QHASH] = (fid[is_dns] & 0xFFFF).astype(np.uint32)


@dataclasses.dataclass
class Schedule:
    """The load as it is due: tick ``i`` is due ``i / ticks_per_s``
    seconds after the start and carries ``rows_per_tick`` rows. Ticks
    ``[0, warm_ticks)`` are warm-up, the rest the measured window."""

    rows_per_tick: int
    tick_s: float
    warm_ticks: int
    window_ticks: int

    @classmethod
    def of(cls, mix: Mix, window_seconds: float, seconds: float) -> "Schedule":
        return cls(
            rows_per_tick=int(round(mix.rate_events_per_s / mix.ticks_per_s)),
            tick_s=1.0 / mix.ticks_per_s,
            warm_ticks=int(round(
                mix.warmup_windows * window_seconds * mix.ticks_per_s)),
            window_ticks=int(round(seconds * mix.ticks_per_s)),
        )

    @property
    def n_ticks(self) -> int:
        return self.warm_ticks + self.window_ticks

    @property
    def window_rows(self) -> int:
        return self.window_ticks * self.rows_per_tick

    @property
    def total_rows(self) -> int:
        return self.n_ticks * self.rows_per_tick


class Pacer(threading.Thread):
    """Walks the pool in order, lap after lap, and hands each tick's
    rows to ``put`` when they are due, in blocks of at most
    ``block_rows``. Rows are restamped as a capture ring drained at each
    tick would hold them: spread evenly, at the offered rate, over the
    tick interval that ends when the tick is due. It waits for nothing
    but the clock, and records how late each block left."""

    def __init__(self, pool: np.ndarray, sched: Schedule, block_rows: int,
                 put, rate: int, pos: int = 0):
        super().__init__(name="pacer", daemon=True)
        self.pool, self.sched, self.block_rows = pool, sched, block_rows
        self.put = put
        self.pos = pos  # where the walk stands; read it after join()
        self.step_ns = max(1, 1_000_000_000 // max(rate, 1))
        self.t_start = 0.0  # monotonic time of tick 0, set by begin()
        # Per block: (tick, seconds after the tick was due, rows).
        self.late: list[tuple[int, float, int]] = []
        self.error: BaseException | None = None
        self._go = threading.Event()

    def begin(self, t_start: float) -> None:
        self.t_start = t_start
        # Wall-clock nanoseconds of the first row of tick 0.
        self.wall0_ns = time.time_ns() + int(
            (t_start - time.monotonic() - self.sched.tick_s) * 1e9)
        self._go.set()

    def due(self, tick: int) -> float:
        return self.t_start + tick * self.sched.tick_s

    def run(self) -> None:
        try:
            self._go.wait()
            self._walk()
        except BaseException as e:  # noqa: BLE001 — reported by the harness
            self.error = e
            raise

    def _walk(self) -> None:
        n_pool = len(self.pool)
        ramp = np.arange(self.block_rows, dtype=np.int64) * self.step_ns
        pos = self.pos
        row = 0  # rows handed over so far
        for tick in range(self.sched.n_ticks):
            due = self.due(tick)
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            left = self.sched.rows_per_tick
            while left:
                n = min(left, self.block_rows, n_pool - pos)
                block = self.pool[pos:pos + n].copy()
                ts = self.wall0_ns + row * self.step_ns + ramp[:n]
                block[:, TS_LO] = (ts & 0xFFFFFFFF).astype(np.uint32)
                block[:, TS_HI] = (ts >> 32).astype(np.uint32)
                self.put(block)
                self.late.append((tick, time.monotonic() - due, n))
                pos = (pos + n) % n_pool
                row += n
                left -= n
        self.pos = pos
