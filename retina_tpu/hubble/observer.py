"""Flow observer: bounded flow ring with follow readers.

Reference analog: the Hubble observer's ring buffer of decoded flows that
``GetFlows`` serves, with follow semantics (new flows stream as they
arrive) — the same structure the enricher uses internally (Cilium
container.Ring, enricher.go:45-52: bounded, overwrite-oldest, per-reader
cursors that observe loss rather than block the writer).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Iterator, Optional

import numpy as np

from retina_tpu.hubble.flow import FlowFilter, record_to_flow
from retina_tpu.log import logger
from retina_tpu.obs.recorder import get_recorder
from retina_tpu.utils import metric_names as mn


class FlowObserver:
    def __init__(self, capacity: int = 4096, cache: Any = None,
                 dns_resolver: Any = None):
        assert capacity & (capacity - 1) == 0
        self._log = logger("observer")
        self._cap = capacity
        # The ring is the last ``capacity`` sequence numbers, held as
        # segments, oldest first: (first sequence number, rows), rows
        # a raw record block or a list of decoded flows. A slot thus
        # names (block, row) by arithmetic: the writer appends one
        # segment a block and never iterates over records, nor touches
        # an array (a numpy pass over thousands of slots gives up the
        # interpreter lock, and getting it back costs the writer
        # milliseconds whenever another thread is busy).
        self._segs: deque[tuple[int, Any]] = deque()
        # Raw rows decoded so far, by sequence number (lazy decode's
        # memo); lapped entries are pruned by the writer.
        self._memo: dict[int, dict] = {}
        self._seq = 0  # total flows ever written
        self._lock = threading.Condition()
        self.cache = cache
        self.dns_resolver = dns_resolver
        self.flows_seen = 0
        # Ring entries skipped by lagging readers, summed across readers
        # (per-reader loss is ALSO surfaced in-stream as LostEvent
        # markers; this aggregate only feeds the self-metric gauge).
        self.lost_observed = 0

    # -- writer side (monitoragent consumer) ---------------------------
    def consume(self, records: np.ndarray) -> None:
        """Write raw record rows; decode is LAZY (on read).

        The writer sits on the hot mirror path (every flow the engine
        sees), while readers are few and slow (gRPC streams), so a
        write costs per BLOCK, not per record: only the last
        ``capacity`` rows of a block can ever be read, and they enter
        the ring as one segment. The ~µs decode is the reader's, which
        only ever materializes the ≤capacity flows it serves."""
        n = len(records)
        if n == 0:
            return
        # The span's seconds include any wait for the interpreter lock
        # inside it; its ``cpu_s`` is what the write itself cost.
        with get_recorder().span(mn.STAGE_HUBBLE_CONSUME, rows=n):
            with self._lock:
                self._write(n, records[-self._cap:])

    def consume_flows(self, flows: list[dict]) -> None:
        """Write already-decoded flow dicts (relay peer ingestion)."""
        if flows:
            with self._lock:
                self._write(len(flows), flows[-self._cap:])

    def _write(self, n: int, rows) -> None:
        """(lock held) ``n`` flows arrive, of which ``rows`` (the last
        ``capacity`` at most) can ever be read."""
        self._seq += n
        self._segs.append((self._seq - len(rows), rows))
        floor = self._seq - self._cap
        while self._segs[0][0] + len(self._segs[0][1]) <= floor:
            self._segs.popleft()
        if self._memo:
            for seq in [q for q in self._memo if q < floor]:
                del self._memo[seq]
        self.flows_seen = self._seq
        self._lock.notify_all()

    def raw_slots(self) -> int:
        """Slots that still name a raw row (nothing has read them)."""
        with self._lock:
            return sum(isinstance(e, tuple) for _, e in self._entries(
                max(0, self._seq - self._cap), self._seq))

    # -- lazy decode ----------------------------------------------------
    def _entries(self, a: int, b: int) -> list[tuple[int, Any]]:
        """(lock held) ``(seq, entry)`` for the slots ``a <= seq < b``
        still in the ring, oldest first: a decoded flow, or the
        ``(block, row)`` pair that :meth:`_materialize` decodes."""
        out = []
        memo = self._memo
        a = max(a, self._seq - self._cap)
        for first, rows in self._segs:
            lo, hi = max(a, first), min(b, first + len(rows))
            if lo >= hi:
                continue
            if isinstance(rows, list):
                out += [(q, rows[q - first]) for q in range(lo, hi)]
            else:
                out += [(q, memo.get(q) or (rows, q - first))
                        for q in range(lo, hi)]
        return out

    def _materialize(self, entry, seq: Optional[int] = None) -> dict:
        """Decode a raw ring entry to a flow dict, memoizing the result
        (decode once, however many readers).

        Semantics note: identity/DNS enrichment happens at FIRST READ,
        not at arrival — if a pod IP is recycled while a flow sits
        unread in the ring, the flow gets the current owner's identity.
        The skew window is bounded by ring residency (capacity flows,
        well under a second at production rates); upstream Hubble has
        the same property between its own ring and its ipcache."""
        if isinstance(entry, tuple):  # (records_block, row_index)
            block, i = entry
            f = record_to_flow(block[i], self.cache, self.dns_resolver)
            if seq is not None:
                with self._lock:
                    if seq >= self._seq - self._cap:  # not lapped since
                        f = self._memo.setdefault(seq, f)
            return f
        return entry

    # -- reader side ---------------------------------------------------
    def snapshot_flows(self) -> tuple[list[dict], int]:
        """All currently-buffered flows (oldest first) + the sequence
        cursor to continue from with :meth:`follow_from`. Servers filter
        this list THEN apply last-N windowing, matching upstream Hubble's
        'N most recent matching flows' semantics."""
        with self._lock:
            end = self._seq
            window = min(end, self._cap)
            entries = self._entries(end - window, end)
        # Materialize OUTSIDE the lock: decode must never stall writers.
        return [self._materialize(e, seq) for seq, e in entries], end

    def follow_from(
        self,
        cursor: int,
        stop: Optional[threading.Event] = None,
    ) -> Iterator[tuple[str, Any]]:
        """Follow the ring from ``cursor``: yields ("flow", flow) items
        and ("lost", n) markers when this reader fell behind (the
        upstream in-stream LostEvent contract)."""
        while stop is None or not stop.is_set():
            batch: list = []
            lost = 0
            with self._lock:
                floor = self._seq - self._cap
                if cursor < floor:
                    lost = floor - cursor
                    self.lost_observed += lost
                    cursor = floor
                batch = self._entries(cursor, self._seq)
                cursor = self._seq
                if not batch and not lost:
                    self._lock.wait(timeout=0.2)
            if lost:
                yield ("lost", lost)
            for seq, f in batch:
                yield ("flow", self._materialize(f, seq))

    def get_flows(
        self,
        filter: Optional[FlowFilter] = None,
        last: int = 0,
        follow: bool = False,
        stop: Optional[threading.Event] = None,
        timeout_s: float = 30.0,
        lost_markers: bool = False,
    ) -> Iterator[dict[str, Any]]:
        """Yield flows: the most recent ``last`` (0 = all buffered), then
        keep following if requested. A slow reader skips overwritten
        entries (loss over blocking, like every ring in this system);
        with ``lost_markers`` each skip also yields a
        ``{"lost_events": n}`` marker (the msgpack analog of the
        protobuf surface's LostEvent response) that bypasses the filter
        — consumers distinguish markers by that key."""
        with self._lock:
            end0 = self._seq
            window = min(end0, self._cap, last if last else self._cap)
            cursor = end0 - window
        # Initial buffered window: one bounded scan (a lap between the
        # snapshot and this scan surfaces as a marker too).
        skipped = 0
        with self._lock:
            floor = self._seq - self._cap
            if cursor < floor:
                skipped = floor - cursor
                self.lost_observed += skipped
                cursor = floor
            batch = self._entries(cursor, end0)
            cursor = max(cursor, end0)
        if skipped and lost_markers:
            yield {"lost_events": int(skipped)}
        for seq, f in batch:
            f = self._materialize(f, seq)
            if filter is None or filter.matches(f):
                yield f
        if not follow:
            return
        # Follow phase: ONE implementation of the skip/account/emit
        # contract lives in follow_from (also the protobuf surface's
        # engine); this just maps its items onto the dict stream.
        for kind, payload in self.follow_from(cursor, stop):
            if stop is not None and stop.is_set():
                return
            if kind == "lost":
                if lost_markers:
                    yield {"lost_events": int(payload)}
            elif filter is None or filter.matches(payload):
                yield payload
