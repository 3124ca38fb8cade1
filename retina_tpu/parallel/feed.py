"""Sharded multi-worker host feed: staging, combine/partition workers,
and the double-buffered transfer handoff to the dispatch thread.

The reference agent parallelizes its ingest the same way the kernel
does — per-CPU perf rings drained by independent readers
(packetparser_linux.go:556-652). Here the engine's feed loop (the
*distributor*) drains the plugin sink and deals raw record blocks to
N :class:`FeedWorker` threads: to one worker until its staged rows
reach its quantum, then to the next. Each worker owns a private staging
deque and HOLDS what is dealt to it, raw, until something would release
it: a full quantum, the age bound, a reader (a window tick, a snapshot:
``FeedWorkerPool.request_flush``) or the stop. Then it runs the
CPU-heavy half of a flush — ONE combine over everything it held +
partition — off the distributor thread (the native combiner releases
the GIL, so workers overlap on real cores). Finished
:class:`~retina_tpu.parallel.partition.ShardedBatch` items hand off
to the single dispatch thread through a :class:`TransferQueue`: a
depth-2 (double-buffered) SPSC deque — one batch in flight on the
dispatch side while the next is fully built — with no lock on the hot path (CPython deque append/popleft are atomic;
events only park a side that has nothing to do).

Every wait of the feed path is one idiom, :func:`park`: block on an
event until whoever produces the data or changes the condition sets
it, or until the next deadline that really exists on the engine's
clock (a flush age, a window tick, the controller's tick), and never
on a polling period. A thread woken a few hundred times a second costs
nothing; six threads woken every 2 ms cost the chip host two thirds of
the agent's CPU (PERF.md, PR 30).

What does NOT move off the dispatch thread: flow-dict assignment, wire
build, and the proxy submission. The wire ordering contract (a new
descriptor row must reach the device table before any known row
references its slot — engine._dispatch_flowdict) requires ONE
serialization point, and the dispatch thread is it.

Backpressure contract (same as everywhere else in the tree): never
block a producer. A block that finds every worker's staging full is
dropped and counted (per-worker drop counters + the lost_events
``handoff`` stage); a worker whose handoff queue stays full because the
dispatch thread died drops the finished batch through the pool's
``drop`` callback (the engine's dead-worker path), which counts it
under the lost_events ``dispatch`` stage.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from retina_tpu.log import logger
from retina_tpu.utils import metric_names as mn

_log = logger("feed")

# Longest single wait where no deadline exists: a lost wake-up costs at
# most this (and tools/lint.py's RT400 wants every wait bounded).
PARK_MAX_S = 1.0
# Shortest: a deadline nearer than this is waited for this long.
PARK_MIN_S = 0.001
# The engine's feed loop is the one waiter its stop signal cannot reach
# (the caller's own Event): with the overload controller off and no
# window tick near, this bounds how late it notices a stop.
FEED_PARK_MAX_S = 0.25


def park(evt: threading.Event, thread: str,
         clock: Callable[[], float] = time.monotonic,
         deadline: float | None = None,
         max_s: float | None = None) -> bool:
    """Block until ``evt`` is set or ``clock`` reaches ``deadline``,
    for ``max_s`` at most (None: ``PARK_MAX_S``), clear the event, and
    count the wake-up under its cause. True if it was the event.

    The caller re-reads its state AFTER this returns (the clear comes
    before the re-read, so a ``set`` that lands in between is kept for
    the next wait); a producer changes the state first and sets after.
    The timeout is the distance to the deadline as ``clock`` reads it
    now: a clock that is not the wall clock wakes its waiters when it
    is advanced (``SketchEngine.wake``, which tests/clockdrive's
    ``FakeClock.advance`` calls), and a waiter nobody wakes re-reads a
    still clock once per that distance, not once per 2 ms."""
    timeout = PARK_MAX_S if max_s is None else max_s
    if deadline is not None:
        # Never under PARK_MIN_S: a clock that stands an ulp short of
        # the deadline (one advanced by hand) must not make this a spin.
        timeout = min(timeout, max(PARK_MIN_S, deadline - clock()))
    hit = evt.wait(timeout)
    evt.clear()
    from retina_tpu.metrics import get_metrics

    get_metrics().feed_wakeups.labels(
        thread=thread, cause=mn.CAUSE_DATA if hit else mn.CAUSE_DEADLINE
    ).inc()
    return hit


# Handoff queue depth: double buffering. One batch being consumed, one
# built and waiting. Deeper queues only add host memory and latency —
# the engine's _inflight semaphore already bounds device-side overlap.
TRANSFER_DEPTH = 2


class TransferQueue:
    """Bounded SPSC handoff (producer: one feed worker; consumer: the
    dispatch thread via :class:`TransferMux`). append/popleft are the
    only hot-path operations; the events are parking lots, not locks."""

    __slots__ = ("q", "depth", "space", "data", "wait_s", "clock",
                 "waiting_since")

    def __init__(self, depth: int, data: threading.Event,
                 clock: Callable[[], float] = time.monotonic):
        self.q: deque = deque()
        self.depth = depth
        self.space = threading.Event()
        self.data = data  # shared with the mux: any producer wakes it
        self.wait_s = 0.0  # producer-side seconds spent waiting for space
        self.clock = clock  # the engine's (injected in tests)
        # Start of the producer's wait in progress (None: not waiting),
        # so that a consumer that never frees a slot reads as a wait
        # while it lasts, not once it is over (``waited``).
        self.waiting_since: float | None = None

    def put(self, item: Any, alive: Optional[Callable[[], bool]] = None,
            ) -> bool:
        """Enqueue, waiting for a free slot. Returns False (item NOT
        enqueued) once ``alive`` goes falsy — the consumer died and the
        caller must drop + count instead of wedging forever. Liveness
        is checked before the first append too: a queue with free
        slots in front of a dead consumer would otherwise swallow
        ``depth`` items that nobody ever counts."""
        if alive is not None and not alive():
            return False
        t0 = None
        while len(self.q) >= self.depth:
            if alive is not None and not alive():
                if t0 is not None:
                    self.waiting_since = None
                    self.wait_s += self.clock() - t0
                return False
            if t0 is None:
                t0 = self.waiting_since = self.clock()
            # Timeout bounds the one benign race (consumer sets space
            # between our len check and wait).
            self.space.wait(0.02)
            self.space.clear()
        if t0 is not None:
            self.waiting_since = None
            self.wait_s += self.clock() - t0
        self.q.append(item)  # noqa: RT402 — bounded: the loop above spins until len(q) < depth; consumer poplefts via TransferMux.get
        self.data.set()
        return True

    def waited(self) -> float:
        """Seconds the producer has waited for space, the wait in
        progress included. A lock-free read of two fields: the total
        is read first and a finished wait clears ``waiting_since``
        before it adds to the total, so a wait that ends between the
        two reads is missed for this reading, never counted twice."""
        total = self.wait_s
        since = self.waiting_since
        if since is None:
            return total
        return total + max(0.0, self.clock() - since)


class TransferMux:
    """Single-consumer fan-in over every worker's TransferQueue plus a
    control lane (window ticks, shutdown sentinel), consumed by
    engine._dispatch_loop: ``get()`` blocks and returns items; ``None``
    means shut down.

    The control lane has priority — window closes stay on cadence even
    under a step backlog. A close overtaking batches still staged in
    the workers just shifts those events into the next window, exactly
    as if they were still in the sink. The shutdown sentinel is the one
    exception: it is delivered only after EVERY worker queue has
    drained (workers are joined before the sentinel is enqueued, so
    their queues are strictly draining by then).

    The consumer parks on ONE event (:func:`park`) for as long as its
    caller's timeout says, or until ``clock`` reaches the caller's
    ``due`` (the age bound of the flushes the dispatch thread holds):
    every producer sets it after its append (``TransferQueue.put``,
    ``put_ctl``), and ``wake`` sets it for a condition the consumer
    waits on beside the items (the pipeline has room again:
    ``_dispatch_done``; a snapshot is about to read the state; every
    worker has answered a flush request; the clock was advanced by
    hand)."""

    def __init__(self, queues: list[TransferQueue], data: threading.Event,
                 clock: Callable[[], float] = time.monotonic):
        self._qs = queues
        self._ctl: deque = deque()
        self._data = data
        self._clock = clock  # the engine's: what ``due`` is read on
        self._rr = 0
        self._woken = False

    def put_ctl(self, item: Any) -> None:
        self._ctl.append(item)
        self._data.set()

    def wake(self) -> None:
        """Return the consumer from ``get`` with no item
        (``queue.Empty``) if none is there: something it waits on
        beside the mux's items has changed."""
        self._woken = True
        self._data.set()

    def drain_unconsumed(self) -> list:
        """Items still queued after the consumer has exited (it died
        before the sentinel reached it). Only safe once the consumer
        thread is gone; the caller drops + counts them."""
        out = []
        for tq in self._qs:
            while tq.q:
                out.append(tq.q.popleft())
        return out

    def has_steps(self) -> bool:
        """A step item waits in some worker's queue."""
        return any(tq.q for tq in self._qs)

    def get(self, timeout: float | None = None, steps: bool = True,
            due: float | None = None, ctl: bool = True) -> Any:
        """The next item: control lane first, then the workers' queues
        round-robin. With ``steps`` false only the control lane is
        served (the consumer holds all it may and takes no more step
        items, but window ticks stay on cadence); the shutdown sentinel
        then waits, as it does behind any undrained queue. With ``ctl``
        false only step items are served (the consumer is inside a
        window tick, taking what the workers flushed for it). With
        nothing to return it parks until a producer or ``wake`` sets
        the event, for ``timeout`` seconds at most or until the mux's
        clock reads ``due`` (``queue.Empty``)."""
        t_end = None if timeout is None else time.monotonic() + timeout
        while True:
            if ctl and self._ctl and self._ctl[0] is not None:
                return self._ctl.popleft()
            draining = ctl and bool(self._ctl)  # head: the None sentinel
            n = len(self._qs) if steps else 0
            for k in range(n):
                tq = self._qs[(self._rr + k) % n]
                try:
                    item = tq.q.popleft()
                except IndexError:
                    continue
                tq.space.set()
                self._rr = (self._rr + k + 1) % n
                return item
            if draining and steps:
                return self._ctl.popleft()
            if self._woken:
                self._woken = False
                raise queue_mod.Empty
            left = None if t_end is None else t_end - time.monotonic()
            if (left is not None and left <= 0) or (
                    due is not None and self._clock() >= due):
                raise queue_mod.Empty
            park(self._data, mn.WAKE_DISPATCH, self._clock, due, left)


class FeedWorker(threading.Thread):
    """One ingest shard: staging deque -> flush -> handoff.

    Counter discipline (lock-free accounting): ``*_in`` fields are
    written only by the distributor, ``*_out`` and ``acked`` only by
    this worker — all monotonic, so ``pending = in - out`` is always
    consistent without a lock (a torn read can only be momentarily
    stale). Each staged block carries the pool clock's reading when it
    was dealt: the oldest one's is the staging's age."""

    def __init__(self, idx: int, pool: "FeedWorkerPool",
                 data: threading.Event):
        super().__init__(name=f"feed-worker-{idx}", daemon=True)
        self.idx = idx
        self.pool = pool
        self.staging: deque = deque()  # (block, clock when dealt)
        self.outq = TransferQueue(pool.depth, data, pool.clock)
        self.wake = threading.Event()
        self.events_in = 0       # distributor-only
        self.blocks_in = 0       # distributor-only
        self.events_out = 0      # worker-only
        self.blocks_out = 0      # worker-only
        # The last flush request answered (FeedWorkerPool.request_flush):
        # everything dealt before it has been handed off.
        self.acked = 0           # worker-only
        self.fill = 0.0          # last flush's quantum fill ratio
        self.batches = 0
        self.handoff_dropped = 0  # worker-only: items the consumer lost

    # -- distributor side --------------------------------------------
    def pending_blocks(self) -> int:
        return self.blocks_in - self.blocks_out

    def pending_events(self) -> int:
        return self.events_in - self.events_out

    def push(self, block) -> None:  # hot-path: event
        """Stage a block. The worker is woken only where it has to act:
        the first block staged sets its age deadline, the block that
        fills its quantum makes a flush due; a block between changes
        neither."""
        first = self.pending_events() == 0
        self.staging.append((block, self.pool.clock()))
        self.blocks_in += 1
        self.events_in += len(block)
        if first or self.pending_events() >= self.pool.quantum:
            self.wake.set()

    # -- worker side --------------------------------------------------
    def run(self) -> None:
        """Supervised run: the ingest loop restarts under the pool's
        restart policy when it crashes (staging survives — it lives on
        the worker object, not the loop frame); a crash loop gives up
        and lets the distributor's liveness check route blocks to the
        surviving shards."""
        hb = (
            self.pool.register_hb(self.name)
            if self.pool.register_hb is not None else None
        )
        policy = (
            self.pool.restart_policy(self.name)
            if self.pool.restart_policy is not None else None
        )
        try:
            while True:
                try:
                    self._loop(hb)
                    return
                except Exception:
                    from retina_tpu.metrics import get_metrics

                    get_metrics().engine_errors.labels(
                        site="feed_worker"
                    ).inc()
                    delay = (
                        policy.record_failure()
                        if policy is not None else None
                    )
                    if delay is None:
                        _log.exception(
                            "feed worker %d crash-looping; giving up "
                            "(blocks route to surviving shards)",
                            self.idx,
                        )
                        return
                    _log.exception(
                        "feed worker %d crashed; restart in %.2fs",
                        self.idx, delay,
                    )
                    get_metrics().thread_restarts.labels(
                        thread=self.name
                    ).inc()
                    if self.pool.stop_evt.wait(delay):
                        return
        finally:
            if self.pool.deregister_hb is not None:
                self.pool.deregister_hb(self.name)

    def _loop(self, hb) -> None:  # hot-path: event
        """Hold what is staged, raw, and flush it when something would
        release it (``tpu_feed_flushes_counter{cause}``): a flush
        request (``read``: every block dealt before it leaves, then the
        request is acknowledged), the stop (``drain``: the same), a
        full quantum (``full``) or the oldest staged block reaching
        ``flush_max_age_s`` on the pool's clock (``age``). An idle
        dispatch pipeline is no reason: the dispatch thread would only
        hold the rows until one of these. Otherwise park (heartbeat
        parked too) until a block that makes a flush due (``push``), a
        request or the stop (both set ``wake``), or the oldest block's
        age deadline. Nothing staged: no deadline."""
        pool = self.pool
        while True:
            stopping = pool.stop_evt.is_set()
            asked = pool.flush_epoch
            if asked > self.acked or stopping:
                cause = mn.FLUSH_DRAIN if stopping else mn.FLUSH_READ
                target = self.blocks_in
                while self.blocks_out < target:
                    if hb is not None:
                        hb.beat()
                    self._flush(cause)
                if asked > self.acked:
                    # Handed off first, then answered; the last answer
                    # wakes the dispatch thread, which then finds every
                    # item the request released on the mux.
                    self.acked = asked
                    if pool.flushed(asked):
                        pool.mux.wake()
                if stopping and not self.pending_events():
                    return
                continue
            pend = self.pending_events()
            deadline = None
            if pend:
                # Read as the deadline the wait below sleeps to, so
                # that a clock that has reached it flushes.
                age_due = self.staging[0][1] + pool.flush_max_age_s
                if pend >= pool.quantum or pool.clock() >= age_due:
                    if hb is not None:
                        hb.beat()
                    self._flush(mn.FLUSH_FULL if pend >= pool.quantum
                                else mn.FLUSH_AGE)
                    continue
                deadline = age_due
            if hb is not None:
                hb.park()
            park(self.wake, mn.WAKE_WORKER, pool.clock, deadline)

    def _flush(self, cause: str) -> None:
        """Combine and partition up to a quantum of the staged blocks,
        oldest first, in one ``build_steps`` call, and hand the items
        off, each carrying when its first block was staged (the
        dispatch thread's hold counts its age from there)."""
        blocks = []
        n_raw = 0
        t_first = None
        while n_raw < self.pool.quantum:
            try:
                b, t = self.staging.popleft()
            except IndexError:
                break
            if t_first is None:
                t_first = t
            blocks.append(b)
            n_raw += len(b)
        if not blocks:
            return
        # Release staging capacity BEFORE the (long) combine: the
        # backpressure signal tracks what is staged, not what is being
        # crunched.
        self.blocks_out += len(blocks)
        self.events_out += n_raw
        self.fill = n_raw / max(self.pool.quantum, 1)
        from retina_tpu.metrics import get_metrics
        from retina_tpu.obs.recorder import get_recorder

        get_metrics().feed_flushes.labels(cause=cause).inc()
        rec = get_recorder()
        with rec.span(mn.STAGE_FEED_FILL):
            items = self.pool.build_steps(blocks, n_raw, int(time.time()))
        with rec.span(mn.STAGE_STAGING_HANDOFF):
            for it in items:
                it = it + (t_first,)
                if not self.outq.put(it, alive=self.pool.alive):
                    self.handoff_dropped += 1
                    self.pool.drop(it)
        self.batches += 1
        self._publish_metrics()

    def _publish_metrics(self) -> None:
        from retina_tpu.metrics import get_metrics

        m = get_metrics()
        w = str(self.idx)
        m.feed_worker_fill.labels(worker=w).set(self.fill)
        # Counters are cumulative; publish the delta since last flush
        # by tracking the high-water mark locally.
        m.feed_handoff_wait.labels(worker=w).inc(
            max(0.0, self.outq.wait_s - getattr(self, "_wait_pub", 0.0))
        )
        self._wait_pub = self.outq.wait_s

    def stat(self) -> dict[str, Any]:
        return {
            "worker": self.idx,
            "fill": round(self.fill, 3),
            "staged_blocks": self.pending_blocks(),
            "staged_events": self.pending_events(),
            "handoff_wait_s": round(self.outq.wait_s, 3),
            "batches": self.batches,
            "events": self.events_out,
            "handoff_dropped": self.handoff_dropped,
        }


class FeedWorkerPool:
    """N feed workers + the mux the dispatch thread consumes.

    ``build_steps(blocks, n_raw, now_s) -> list[item]`` is the engine's
    combine+partition stage (pure host work, safe concurrently); each
    item reaches the mux with one more field, the pool clock's reading
    when the flush's first block was staged. ``drop(item)`` is called
    for any finished item the dispatch side will never consume (dead
    consumer) so losses are counted, never silent; ``alive()`` reports
    dispatch-thread liveness."""

    def __init__(
        self,
        n_workers: int,
        quantum: int,
        staging_blocks: int,
        flush_max_age_s: float,
        build_steps: Callable[[list, int, int], list],
        drop: Callable[[Any], None],
        alive: Callable[[], bool] = lambda: True,
        depth: int = TRANSFER_DEPTH,
        register_hb: Optional[Callable[[str], Any]] = None,
        deregister_hb: Optional[Callable[[str], None]] = None,
        restart_policy: Optional[Callable[[str], Any]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        # Block ages, their flush deadlines and handoff waits are read
        # on the engine's clock (injected in tests).
        self.clock = clock
        self.quantum = max(1, int(quantum))
        self.staging_blocks = max(1, int(staging_blocks))
        self.flush_max_age_s = flush_max_age_s
        self.build_steps = build_steps
        self.drop = drop
        self.alive = alive
        self.depth = max(1, int(depth))
        # Supervision seams (engine passes its heartbeat registrar and
        # config-derived restart policy factory; bare pools run
        # unsupervised exactly as before).
        self.register_hb = register_hb
        self.deregister_hb = deregister_hb
        self.restart_policy = restart_policy
        self.stop_evt = threading.Event()
        data = threading.Event()
        self.workers = [
            FeedWorker(i, self, data) for i in range(max(1, n_workers))
        ]
        self.mux = TransferMux([w.outq for w in self.workers], data, clock)
        self._cur = 0  # distributor-only: the worker being dealt to
        # Flush requests made (request_flush); the workers answer each.
        self._epoch_lock = threading.Lock()
        self.flush_epoch = 0  # guarded-by: self._epoch_lock (writes)
        # Distributor-only counters: blocks no worker could take.
        self.staging_dropped_blocks = 0
        self.staging_dropped_events = 0

    def start(self) -> None:
        for w in self.workers:
            w.start()

    def stage(self, block) -> bool:
        """Deal one raw block to the worker being dealt to, or, where
        that one is dead or its staging full, to the next live one with
        room; once the worker's staged rows reach its quantum the next
        block goes to the next worker. At a ring's cadence what one
        release will read is then held, and combined, in one worker; at
        saturation the workers fill and flush in turn, in parallel.
        Returns False — caller drops + counts — only when EVERY worker
        is saturated or gone."""
        n = len(self.workers)
        for k in range(n):
            i = (self._cur + k) % n
            w = self.workers[i]
            if w.is_alive() and w.pending_blocks() < self.staging_blocks:
                w.push(block)
                if w.pending_events() >= self.quantum:
                    i = (i + 1) % n
                self._cur = i
                return True
        return False

    def request_flush(self) -> int:
        """Ask every worker to flush all it has staged, hand it off,
        and then acknowledge; returns the request's epoch, which
        ``flushed`` answers. A reader asks before it reads the state
        (the distributor before a window tick, a snapshot through the
        engine), so that what was dealt before it is in what it reads."""
        with self._epoch_lock:
            self.flush_epoch += 1
            epoch = self.flush_epoch
        for w in self.workers:
            w.wake.set()
        return epoch

    def flushed(self, epoch: int) -> bool:
        """Every live worker has handed off what was dealt to it before
        request ``epoch`` (a dead worker holds nothing it could hand
        off)."""
        return all(w.acked >= epoch for w in self.workers if w.is_alive())

    def wake_all(self) -> None:
        """Every worker re-reads its clock (it was advanced by hand)."""
        for w in self.workers:
            w.wake.set()

    def count_drop(self, n_events: int) -> None:
        """Distributor-side drop accounting for a block no worker could
        take (the caller also counts it into lost_events)."""
        from retina_tpu.metrics import get_metrics

        self.staging_dropped_blocks += 1
        self.staging_dropped_events += n_events
        get_metrics().feed_blocks_dropped.labels(
            worker=str(self._cur)
        ).inc()

    def stop(self, timeout: float = 30.0) -> None:
        """Signal stop and join the workers; each final-flushes its
        staged quantum first (handoffs still drain: the dispatch thread
        keeps consuming until it sees the mux sentinel, which the
        engine enqueues only after this returns)."""
        self.stop_evt.set()
        deadline = time.monotonic() + timeout
        self.wake_all()
        for w in self.workers:
            w.join(max(0.0, deadline - time.monotonic()))
            if w.is_alive():
                _log.error("feed worker %d did not stop in time", w.idx)

    # -- pressure signals (overload controller, runtime/overload.py) ---
    def max_staging_fill(self) -> float:
        """Worst per-worker staging occupancy in [0, 1] — the leading
        saturation signal: 1.0 means the NEXT block dealt to that shard
        is one skip away from a raw handoff drop."""
        if not self.workers:
            return 0.0
        return max(
            w.pending_blocks() / self.staging_blocks for w in self.workers
        )

    def handoff_wait_total(self) -> float:
        """Cumulative producer seconds spent waiting on a full transfer
        slot, summed over workers; the controller turns the delta into
        a wait rate (seconds waited per wall second)."""
        return sum(w.outq.waited() for w in self.workers)

    def stats(self) -> dict[str, Any]:
        return {
            "workers": len(self.workers),
            "quantum": self.quantum,
            "dropped_blocks": self.staging_dropped_blocks,
            "dropped_events": self.staging_dropped_events,
            "per_worker": [w.stat() for w in self.workers],
        }
