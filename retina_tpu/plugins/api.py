"""Plugin interface + event sink contracts.

Reference analog: pkg/plugin/registry/registry.go:16-34 — every plugin
implements ``Name/Generate/Compile/Init/Start/Stop/SetupChannel``. The TPU
mapping of the lifecycle:

- **generate**: produce derived config (the reference writes dynamic.h
  macros for eBPF, packetparser_linux.go:82-127; here plugins derive their
  static kernel shapes / source settings from Config).
- **compile**: build the compute (reference shells out to clang,
  pkg/loader/compile.go; here: jit-lower/warm the plugin's device code so
  Start never pays first-compile latency).
- **init**: allocate runtime state (reference loads BPF objects; here:
  device buffers / parsers / sockets).
- **start(stop_event)**: blocking feed loop until stop is set (reference
  plugin.Start(ctx) blocking goroutine).
- **stop**: idempotent teardown.
- **setup_channel(queue)**: hand the plugin an external event queue for
  the Hubble-style export path (registry.go:31-33); plugins that emit
  flows mirror them there, dropping (and counting) when full — never
  blocking, like packetparser_linux.go:645-651.

Events flow into an :class:`EventSink` — the seam the enricher/batcher
provides (the ``enricher.Write`` analog, enricher.go:185-187) — as numpy
record blocks, not per-event calls: batches are the unit the device wants.
"""

from __future__ import annotations

import abc
import bisect
import collections
import queue as queue_mod
import threading
import time
from typing import Optional, Protocol

import numpy as np

from retina_tpu.config import Config
from retina_tpu.log import logger


class UnsupportedPlatform(RuntimeError):
    """Raised by plugins that cannot run on this host OS."""


class EventSink(Protocol):
    """Where plugins write decoded event records."""

    def write_records(self, records: np.ndarray, plugin: str) -> int:
        """Append (N, NUM_FIELDS) uint32 rows. Returns rows accepted;
        short writes mean overflow (caller counts lost events)."""
        ...


class NullSink:
    """Discards everything (tests / disabled pipeline)."""

    def write_records(self, records: np.ndarray, plugin: str) -> int:
        return len(records)


class Plugin(abc.ABC):
    """Base plugin (reference registry.Plugin)."""

    name: str = ""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.log = logger(f"plugin.{self.name}")
        self.sink: EventSink = NullSink()
        self.external: Optional[queue_mod.Queue] = None
        self._external_lost = 0

    # -- lifecycle ---------------------------------------------------
    def generate(self) -> None:  # noqa: B027
        """Derive config (dynamic.h analog). Default: nothing."""

    def compile(self) -> None:  # noqa: B027
        """Warm jit caches / build parsers. Default: nothing."""

    def init(self) -> None:  # noqa: B027
        """Allocate runtime resources. Default: nothing."""

    @abc.abstractmethod
    def start(self, stop: threading.Event) -> None:
        """Blocking loop; must return promptly once ``stop`` is set."""

    def stop(self) -> None:  # noqa: B027
        """Idempotent teardown. Default: nothing."""

    # -- wiring ------------------------------------------------------
    def set_sink(self, sink: EventSink) -> None:
        self.sink = sink

    def setup_channel(self, q: queue_mod.Queue) -> None:
        """External (Hubble-path) queue (registry.go:31-33)."""
        self.external = q

    def emit(self, records: np.ndarray) -> int:
        """Write records to sink + mirror to external channel, never
        blocking; losses are counted (packetparser_linux.go:645-651).
        Returns rows the sink accepted so paced sources can yield
        instead of busy-spinning against a full sink."""
        if len(records) == 0:
            return 0
        accepted = self.sink.write_records(records, self.name)
        if accepted < len(records):
            self.count_lost("buffered", len(records) - accepted)
        if self.external is not None:
            try:
                self.external.put_nowait(records)
            except queue_mod.Full:
                self._external_lost += len(records)
                self.count_lost("external", len(records))
        return accepted

    def count_lost(self, stage: str, n: int) -> None:
        from retina_tpu.metrics import get_metrics

        get_metrics().lost_events.labels(stage=stage, plugin=self.name).inc(n)


def oldest_unheld(
    accepts: list[tuple[float, int]], events_held: int
) -> float | None:
    """The watermark arithmetic of :meth:`QueueSink.oldest_unheld` on a
    plain list of (accept time, cumulative events), oldest first."""
    i = bisect.bisect_right(accepts, events_held, key=lambda a: a[1])
    return accepts[i][0] if i < len(accepts) else None


class QueueSink:
    """Bounded sink over a queue of record blocks — the userspace record
    channel analog (10k-deep, drop-on-full; packetparser types_linux.go:38,
    packetparser_linux.go:692-697). The batcher drains it."""

    # Accepts remembered for the publish watermark: at 122 blocks a
    # second (packetparser's 8,192-row blocks at 1M events/s) half a
    # minute, far past any publish lag worth a number.
    ACCEPT_RING = 4096

    def __init__(self, max_blocks: int = 1024):
        self.q: queue_mod.Queue[tuple[np.ndarray, str]] = queue_mod.Queue(
            maxsize=max_blocks
        )
        # (accept time on time.monotonic, events accepted up to and
        # including that block), oldest first; one entry per block, so
        # a lock per write is cheap.
        self._accepts: collections.deque[tuple[float, int]] = (
            collections.deque(maxlen=self.ACCEPT_RING)
        )
        self._accepted = 0
        self._accept_lock = threading.Lock()
        # Set after every accepted block: the drainer parks on it
        # (parallel/feed.park) instead of polling an empty queue.
        self.data = threading.Event()

    def write_records(self, records: np.ndarray, plugin: str) -> int:
        # Stamped under the lock that orders the put, so the ring's
        # counts run in the order the blocks leave the queue.
        with self._accept_lock:
            try:
                self.q.put_nowait((records, plugin))
            except queue_mod.Full:
                return 0
            self._accepted += len(records)
            self._accepts.append((time.monotonic(), self._accepted))
        self.data.set()
        return len(records)

    def oldest_unheld(self, events_held: int) -> float | None:
        """Accept time of the oldest accepted block that a reader
        holding the first ``events_held`` accepted events does not hold
        in full; None when it holds them all. Where the ring has
        forgotten that block, the oldest accept it still knows."""
        with self._accept_lock:
            accepts = list(self._accepts)
        return oldest_unheld(accepts, events_held)

    def drain(self, max_blocks: int = 64) -> list[tuple[np.ndarray, str]]:
        out = []
        for _ in range(max_blocks):
            try:
                out.append(self.q.get_nowait())
            except queue_mod.Empty:
                break
        return out
