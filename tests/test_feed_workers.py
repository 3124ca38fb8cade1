"""Sharded multi-worker host feed (parallel/feed.py): the bounded
double-buffered handoff primitives, the worker pool's staging/flush/
backpressure contract, and engine-level agreement between pools of
different sizes (the engine's one feed shape).

The reference analog is per-CPU perf rings drained by independent
readers (packetparser_linux.go:556-652) with the same loss rule
everywhere: drop and count, never block a producer."""

from __future__ import annotations

import queue as queue_mod
import threading
import time

import numpy as np
import pytest

from retina_tpu.config import Config
from retina_tpu.engine import SketchEngine
from retina_tpu.events.synthetic import POD_NET, TrafficGen
from retina_tpu.parallel.feed import (
    TRANSFER_DEPTH,
    FeedWorkerPool,
    TransferMux,
    TransferQueue,
)


def small_cfg(**kw) -> Config:
    cfg = Config()
    cfg.mesh_devices = kw.pop("mesh_devices", 2)
    cfg.batch_capacity = 1 << 10
    cfg.n_pods = 1 << 8
    cfg.cms_width = 1 << 10
    cfg.topk_slots = 1 << 7
    cfg.hll_precision = 8
    cfg.entropy_buckets = 1 << 8
    cfg.conntrack_slots = 1 << 10
    cfg.identity_slots = 1 << 10
    cfg.window_seconds = 0.2
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


# -- handoff primitives ----------------------------------------------


def test_transfer_queue_is_double_buffered_and_never_wedges():
    data = threading.Event()
    tq = TransferQueue(TRANSFER_DEPTH, data)
    assert tq.put("a")
    assert tq.put("b")
    assert len(tq.q) == TRANSFER_DEPTH
    # Full queue + dead consumer: put must refuse (caller drops and
    # counts), not block forever.
    t0 = time.monotonic()
    assert not tq.put("c", alive=lambda: False)
    assert time.monotonic() - t0 < 5.0
    assert list(tq.q) == ["a", "b"]


def test_transfer_queue_accounts_handoff_wait():
    data = threading.Event()
    tq = TransferQueue(1, data)
    assert tq.put("a")
    t = threading.Thread(target=lambda: (time.sleep(0.1),
                                         tq.q.popleft(),
                                         tq.space.set()))
    t.start()
    assert tq.put("b", alive=lambda: True)
    t.join()
    assert tq.wait_s > 0.0


def test_a_handoff_wait_in_progress_is_read_on_the_injected_clock():
    """A consumer that never frees a slot reads as a wait while it
    lasts (the overload controller's ``handoff_wait``), on the clock
    the queue was given."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    tq = TransferQueue(1, threading.Event(), clock)
    assert tq.put("a") and tq.waited() == 0.0
    alive = [True]
    t = threading.Thread(target=lambda: tq.put("b", alive=lambda: alive[0]))
    t.start()
    wait_until(lambda: tq.waiting_since is not None, "the producer waits")
    clock.advance(2.5)
    assert tq.waited() == pytest.approx(2.5) and tq.wait_s == 0.0
    tq.q.popleft()
    tq.space.set()
    t.join(10.0)
    assert not t.is_alive() and list(tq.q) == ["b"]
    assert tq.waiting_since is None
    assert tq.wait_s == tq.waited() == pytest.approx(2.5)


def test_mux_serves_only_the_control_lane_when_asked():
    """The dispatch thread, holding all one transfer may carry, takes
    window ticks but no more step items; the shutdown sentinel waits
    behind them."""
    data = threading.Event()
    q0 = TransferQueue(2, data)
    mux = TransferMux([q0], data)
    q0.put("s0")
    mux.put_ctl("win")
    mux.put_ctl(None)
    assert mux.get(timeout=1.0, steps=False) == "win"
    with pytest.raises(queue_mod.Empty):
        mux.get(timeout=0.05, steps=False)
    assert mux.get(timeout=1.0) == "s0"
    assert mux.get(timeout=1.0) is None


def test_mux_control_lane_has_priority_and_sentinel_drains_last():
    data = threading.Event()
    q0 = TransferQueue(2, data)
    q1 = TransferQueue(2, data)
    mux = TransferMux([q0, q1], data)
    q0.put("s0")
    q1.put("s1")
    mux.put_ctl("win")
    # Window ticks overtake staged steps (close cadence holds under a
    # step backlog)...
    assert mux.get(timeout=1.0) == "win"
    # ...but the shutdown sentinel is delivered only after every worker
    # queue drains — nothing staged at shutdown is silently lost.
    mux.put_ctl(None)
    got = [mux.get(timeout=1.0) for _ in range(3)]
    assert got[:2] == ["s0", "s1"]
    assert got[2] is None


def test_mux_get_times_out_empty():
    mux = TransferMux([], threading.Event())
    with pytest.raises(queue_mod.Empty):
        mux.get(timeout=0.05)


# -- worker pool ------------------------------------------------------


def _mk_pool(**kw):
    defaults = dict(
        n_workers=2, quantum=100, staging_blocks=8,
        flush_max_age_s=0.05,
        build_steps=lambda blocks, n_raw, now_s: [
            ("step", np.concatenate(blocks), now_s, n_raw)
        ],
        drop=lambda item: None,
    )
    defaults.update(kw)
    return FeedWorkerPool(**defaults)


def test_pool_end_to_end_delivers_every_event():
    pool = _mk_pool()
    pool.start()
    total = 0
    for i in range(10):
        assert pool.stage(np.full((30, 2), i, np.uint32))
        total += 30
    got = 0
    deadline = time.monotonic() + 10.0
    while got < total and time.monotonic() < deadline:
        try:
            item = pool.mux.get(timeout=0.1)
        except queue_mod.Empty:
            continue
        got += len(item[1])
    pool.stop()
    assert got == total
    st = pool.stats()
    assert st["workers"] == 2
    assert st["dropped_blocks"] == 0
    assert sum(w["events"] for w in st["per_worker"]) == total


def test_pool_stop_flushes_staged_remainder():
    pool = _mk_pool(quantum=10_000, flush_max_age_s=60.0)
    pool.start()
    assert pool.stage(np.zeros((7, 2), np.uint32))
    stopper = threading.Thread(target=pool.stop, daemon=True)
    stopper.start()
    item = pool.mux.get(timeout=5.0)  # final flush, sub-quantum
    stopper.join(10.0)
    assert not stopper.is_alive()
    assert len(item[1]) == 7


def test_stage_refuses_when_every_worker_saturated():
    pool = _mk_pool(n_workers=1, staging_blocks=2, quantum=10_000,
                    flush_max_age_s=60.0)
    pool.start()
    assert pool.stage(np.zeros((5, 2), np.uint32))
    assert pool.stage(np.zeros((5, 2), np.uint32))
    # Staging full and nothing flushing: the distributor must get an
    # immediate refusal (drop + count), never a blocking wait.
    assert not pool.stage(np.zeros((5, 2), np.uint32))
    pool.count_drop(5)
    st = pool.stats()
    assert st["dropped_blocks"] == 1
    assert st["dropped_events"] == 5
    pool.stop()


def test_dead_consumer_drops_are_counted_not_wedged():
    dropped = []
    pool = _mk_pool(n_workers=1, quantum=10, flush_max_age_s=0.02,
                    drop=dropped.append, alive=lambda: False)
    pool.start()
    # Depth-2 handoff + dead consumer: the third finished batch cannot
    # enqueue; the worker must drop it through the pool callback and
    # keep running.
    for i in range(6):
        assert pool.stage(np.full((10, 2), i, np.uint32))
    deadline = time.monotonic() + 10.0
    while not dropped and time.monotonic() < deadline:
        time.sleep(0.01)
    pool.stop()
    assert dropped, "dead-consumer handoff never dropped"
    st = pool.stats()
    assert sum(w["handoff_dropped"] for w in st["per_worker"]) >= 1


# -- engine integration ----------------------------------------------


def _run_feed(cfg, n_events=1600, dt=0.03):
    """One feed of ``n_events``, a block of 400 every ``dt`` seconds,
    by an injected clock (clockdrive): on
    the wall clock a loaded machine reads as a late device (with
    ``feed_pipeline_depth=2`` one cold ingest key compiling inline
    used to be the whole in-flight budget), the controller samples,
    and the totals are estimates."""
    from clockdrive import Drive, FakeClock

    clock = FakeClock()
    eng = SketchEngine(cfg, clock=clock)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(30.0)
    drive = Drive(eng, clock)
    gen = TrafficGen(n_flows=50, n_pods=16, seed=3)
    for _ in range(n_events // 400):
        drive.hand_over(gen.batch(400), dt)
    drive.settle()
    assert eng.overload.stats()["transitions"] == 0
    snap = eng.snapshot(max_age_s=0)
    stats = eng.feed_stats()
    stop.set()
    t.join(30.0)
    assert not t.is_alive()
    return eng, snap, stats


def test_sharded_feed_agrees_with_inline():
    """A pool of two lands exactly the events a pool of one lands —
    dealing blocks over workers is lossless and the dispatch thread
    still serializes flow-dict/wire/submit."""
    _, snap_one, st_one = _run_feed(
        small_cfg(feed_pipeline_depth=2, feed_workers=1)
    )
    _, snap_pool, st_pool = _run_feed(
        small_cfg(feed_pipeline_depth=2, feed_workers=2)
    )
    assert st_one["workers"] == 1
    assert st_pool["workers"] == 2
    assert st_one["dropped_blocks"] == st_pool["dropped_blocks"] == 0
    assert int(snap_pool["totals"][0]) == 1600
    assert int(snap_pool["totals"][0]) == int(snap_one["totals"][0])
    assert int(snap_pool["totals"][1]) == int(
        np.asarray(snap_pool["pod_forward"])[:, :, 0].sum()
    )
    # Per-worker accounting covers the full stream.
    assert sum(w["events"] for w in st_pool["per_worker"]) == 1600


def test_one_core_host_boots_pool_of_one(monkeypatch):
    """There is one feed shape: a host with one core auto-sizes to a
    pool of ONE worker (not to a feed loop that builds its own
    quanta), and that pool lands every event exactly."""
    import os

    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    cfg = small_cfg(feed_pipeline_depth=2)
    assert cfg.feed_workers == 0  # auto
    eng, snap, st = _run_feed(cfg)
    assert eng._resolve_feed_workers() == 1
    assert st["workers"] == 1 and len(st["per_worker"]) == 1
    assert st["dropped_blocks"] == 0
    assert st["per_worker"][0]["events"] == 1600
    assert int(snap["totals"][0]) == 1600
    assert int(snap["totals"][1]) == int(
        np.asarray(snap["pod_forward"])[:, :, 0].sum()
    )


def test_yaml_with_removed_feed_and_wire_keys_runs_the_one_path(tmp_path):
    """A deployed YAML that still carries ``transfer_packed``,
    ``wire_dense_known`` or ``host_combine`` (removed: each chose a
    path nothing ran) loads — unknown keys are ignored, like viper —
    and the agent runs the one path: combined, packed, dense known
    rows under the dictionary, every event exact."""
    from retina_tpu.config import load_config
    from retina_tpu.metrics import get_metrics

    p = tmp_path / "config.yaml"
    p.write_text(
        "transfer_packed: false\n"
        "wire_dense_known: false\n"
        "host_combine: false\n"
        "feed_pipeline_depth: 2\n"
        "feed_workers: 2\n"
        "transfer_min_bucket: 16\n"
    )
    cfg = load_config(str(p), env={})
    for gone in ("transfer_packed", "wire_dense_known", "host_combine"):
        assert not hasattr(cfg, gone)
    for k, v in vars(small_cfg()).items():
        if k not in ("feed_pipeline_depth", "feed_workers",
                     "transfer_min_bucket"):
            setattr(cfg, k, v)
    m = get_metrics()
    known0 = m.wire_rows.labels(kind="known")._value.get()
    # A block a window (0.2 s): each window's tick releases its own
    # flush, so the flows of the first come back as known rows.
    eng, snap, st = _run_feed(cfg, dt=0.25)
    assert int(snap["totals"][0]) == 1600
    # Combined (50 flows in 400-event blocks) and carried as dense
    # known rows: the dictionary's ingest pair is what compiled.
    assert m.combine_ratio._value.get() > 1.0
    assert m.wire_rows.labels(kind="known")._value.get() > known0
    assert any(
        isinstance(k, tuple) and k[0] == "known" for k in eng._pad_cache
    )


def test_paced_feed_no_subfloor_windows_with_workers():
    """With the warm complete and the sharded feed on, a paced feed
    never sees a stalled ingest span: every sampling window moves
    events (the stall-free acceptance shape of the bench e2e, scaled to
    a unit test)."""
    cfg = small_cfg(
        feed_pipeline_depth=2, feed_workers=2, warm_duty_cycle=0.95,
        feed_coalesce_windows=1, window_seconds=0.25,
    )
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(5.0)
    warm = eng.start_background_warm(stop)
    gen = TrafficGen(n_flows=200, n_pods=32, seed=5)
    assert eng.bucket_warm_done.wait(300.0), "warm never completed"
    samples = []
    last = eng._events_in
    next_sample = time.monotonic() + 0.3
    t_end = time.monotonic() + 1.5
    while time.monotonic() < t_end:
        eng.sink.write_records(gen.batch(256), "test")
        time.sleep(0.02)
        if time.monotonic() >= next_sample:
            cur = eng._events_in
            samples.append(cur - last)
            last = cur
            next_sample += 0.3
    stop.set()
    t.join(30.0)
    warm.join(30.0)
    assert not t.is_alive()
    assert samples, "no ingest samples collected"
    assert all(s > 0 for s in samples), samples


# -- waits: on data and on real deadlines, never on a period -----------
# (ISSUE 30). Where a test has to tell a signalled wake from the safety
# bound of an idle wait, conftest's ``long_parks`` moves that bound out
# of reach: a wake-up that is lost then hangs the test instead of
# costing a second.

from clockdrive import wakeups  # noqa: E402


def _take(pool, timeout_s: float = 10.0):
    """The next item off the pool's mux, as the dispatch thread takes
    it: a ``wake`` returns it empty-handed and it asks again."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            return pool.mux.get(timeout=deadline - time.monotonic())
        except queue_mod.Empty:
            continue
    raise AssertionError("nothing reached the mux")


def _clocked_pool(clock, **kw):
    pool = _mk_pool(clock=clock, **kw)
    clock.on_advance(pool.wake_all)
    return pool


def test_park_counts_a_wake_by_its_cause_and_keeps_a_set_for_the_next():
    from retina_tpu.parallel.feed import park

    evt = threading.Event()
    d0, t0 = wakeups("worker", "data"), wakeups("worker", "deadline")
    assert park(evt, "worker", deadline=time.monotonic() + 0.01) is False
    evt.set()
    assert park(evt, "worker") is True and not evt.is_set()
    # A deadline already past on the caller's clock is no wait at all.
    t = time.monotonic()
    assert park(evt, "worker", lambda: 100.0, deadline=99.0) is False
    assert time.monotonic() - t < 0.5
    assert wakeups("worker", "data") - d0 == 1
    assert wakeups("worker", "deadline") - t0 == 2


def test_mux_wake_returns_the_consumer_with_no_item(long_parks):
    """``wake`` is for a condition the consumer waits on beside the
    items: ``get`` comes back empty at once, and an item that is there
    is still served first."""
    data = threading.Event()
    q0 = TransferQueue(2, data)
    mux = TransferMux([q0], data)
    got = []

    def consume():
        try:
            got.append(mux.get(timeout=30.0))
        except queue_mod.Empty:
            got.append("empty")

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.05)
    mux.wake()
    t.join(10.0)
    assert got == ["empty"]
    q0.put("s0")
    mux.wake()
    assert mux.get(timeout=1.0) == "s0"
    with pytest.raises(queue_mod.Empty):
        mux.get(timeout=30.0)  # the wake that was kept


def test_a_parked_worker_flushes_at_the_interval_with_no_poll_between(
        long_parks):
    """(b) The first block pushed to a parked worker wakes it once; it
    then sleeps to that block's age deadline (the clock when it was
    dealt + ``flush_max_age_s``) on the injected clock. A second block
    short of the quantum wakes nobody. The worker is woken when that
    clock is advanced, and flushes both blocks as one when the age has
    passed: three wake-ups, however long the wall clock runs."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=1, quantum=10_000,
                         flush_max_age_s=30.0)
    pool.start()
    w = pool.workers[0]
    w0 = wakeups("worker")
    t_dealt = clock()
    assert pool.stage(np.zeros((7, 2), np.uint32))
    wait_until(lambda: wakeups("worker") - w0 == 1, "the push wakes it")
    assert pool.stage(np.ones((5, 2), np.uint32))
    time.sleep(0.3)  # 150 polls of 2 ms
    assert wakeups("worker") - w0 == 1 and w.batches == 0
    clock.advance(29.9)
    wait_until(lambda: wakeups("worker") - w0 == 2, "the clock wakes it")
    time.sleep(0.05)
    assert w.batches == 0 and w.pending_events() == 12
    clock.advance(0.2)
    item = _take(pool)
    assert len(item[1]) == 12 and wakeups("worker") - w0 == 3
    # The flush carries when its first block was dealt.
    assert item[4] == t_dealt
    pool.stop()


def test_a_partial_quantum_behind_a_busy_pipeline_leaves_when_it_goes_idle(
        long_parks):
    """(c) A partial quantum is held by its worker whatever the
    pipeline does: the pipeline going idle (the engine's
    ``_dispatch_done`` wakes the mux) wakes the dispatch thread and no
    worker. The quantum leaves when a reader asks
    (``request_flush``), on a clock that never reaches the age bound;
    every worker answers the request, the holder once its flush is on
    the mux, and the last answer wakes the mux."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=2, quantum=10_000,
                         flush_max_age_s=3600.0)
    pool.start()
    w0 = wakeups("worker")
    assert pool.stage(np.zeros((7, 2), np.uint32))
    wait_until(lambda: wakeups("worker") - w0 == 1, "the push wakes it")
    holder = pool.workers[0]
    pool.mux.wake()  # a completion: the mux is woken, no worker
    with pytest.raises(queue_mod.Empty):
        pool.mux.get(timeout=30.0)
    time.sleep(0.05)
    assert wakeups("worker") - w0 == 1
    assert holder.batches == 0 and holder.pending_events() == 7
    epoch = pool.request_flush()
    item = _take(pool)
    assert len(item[1]) == 7
    wait_until(lambda: pool.flushed(epoch), "every worker answers")
    assert [w.acked for w in pool.workers] == [epoch, epoch]
    # The last answer woke the mux: its consumer comes back empty.
    with pytest.raises(queue_mod.Empty):
        pool.mux.get(timeout=30.0)
    assert clock() - 1000.0 < 0.1  # nowhere near the age bound
    # The request's wake of each worker, and no other.
    assert wakeups("worker") - w0 == 3
    pool.stop()


def _flush_causes() -> dict[str, float]:
    """``tpu_feed_flushes_counter`` by cause, so far in this test."""
    from retina_tpu.metrics import get_metrics

    return {s.labels["cause"]: s.value
            for mf in get_metrics().feed_flushes.collect()
            for s in mf.samples if s.name.endswith("_total")}


def test_a_ring_cadence_is_held_by_one_worker_and_flushed_once():
    """Blocks short of a quantum are all dealt to one worker, which
    holds them raw; a reader's request then makes ONE flush of them, in
    the order they were dealt, however many workers the pool has."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=4, quantum=100,
                         flush_max_age_s=3600.0)
    pool.start()
    for i in range(5):
        assert pool.stage(np.full((10, 2), i, np.uint32))
    assert [w.pending_blocks() for w in pool.workers] == [5, 0, 0, 0]
    epoch = pool.request_flush()
    item = _take(pool)
    assert item[1][:, 0].tolist() == [i for i in range(5) for _ in range(10)]
    wait_until(lambda: pool.flushed(epoch), "every worker answers")
    assert [w.batches for w in pool.workers] == [1, 0, 0, 0]
    pool.stop()


def test_at_saturation_the_deal_spills_to_the_next_worker_and_all_four_flush(
        long_parks):
    """The deal stays with a worker until its staged rows reach its
    quantum and then moves on, so a saturated feed fills the four
    workers in turn and each flushes a full quantum (``full``), in
    parallel, as round-robin dealing did."""
    from clockdrive import FakeClock

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=4, quantum=100,
                         flush_max_age_s=3600.0)
    c0 = _flush_causes()
    pool.start()
    for i in range(8):
        assert pool.stage(np.full((50, 2), i, np.uint32))
    items = [_take(pool) for _ in range(4)]
    got = sorted(sorted(set(it[1][:, 0].tolist())) for it in items)
    assert got == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert [w.batches for w in pool.workers] == [1, 1, 1, 1]
    assert all(w.pending_events() == 0 for w in pool.workers)
    assert _flush_causes().get("full", 0) - c0.get("full", 0) == 4
    # A worker whose staging is full is passed over however few rows
    # it holds.
    w = pool.workers[pool._cur]
    w.blocks_in += pool.staging_blocks  # as if that many were staged
    assert pool.stage(np.zeros((1, 2), np.uint32))
    w.blocks_in -= pool.staging_blocks
    assert w.pending_blocks() == 0
    pool.stop()


def test_the_flush_counter_counts_each_cause(long_parks):
    """``tpu_feed_flushes_counter{cause}``: a full quantum, the age
    bound, a reader's request and the stop each make one flush of
    their own cause."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=1, quantum=100,
                         flush_max_age_s=10.0)
    c0 = _flush_causes()

    def since() -> dict[str, float]:
        now = _flush_causes()
        return {k: v - c0.get(k, 0) for k, v in now.items()
                if v != c0.get(k, 0)}

    pool.start()
    assert pool.stage(np.zeros((100, 2), np.uint32))
    assert len(_take(pool)[1]) == 100
    assert since() == {"full": 1}
    assert pool.stage(np.zeros((3, 2), np.uint32))
    clock.advance(10.0)
    assert len(_take(pool)[1]) == 3
    assert since() == {"full": 1, "age": 1}
    assert pool.stage(np.zeros((4, 2), np.uint32))
    epoch = pool.request_flush()
    assert len(_take(pool)[1]) == 4
    wait_until(lambda: pool.flushed(epoch), "the worker answers")
    assert since() == {"full": 1, "age": 1, "read": 1}
    assert pool.stage(np.zeros((5, 2), np.uint32))
    pool.stop()
    assert len(_take(pool)[1]) == 5
    assert since() == {"full": 1, "age": 1, "read": 1, "drain": 1}


def test_a_request_behind_a_full_hand_off_is_answered_once_it_drains(
        long_parks):
    """A worker whose hand-off queue is full waits on it and cannot
    answer a flush request: the request stays open, nothing is lost,
    and once the consumer takes the items every block dealt before the
    request has reached the mux and the request is answered."""
    from clockdrive import FakeClock, wait_until

    clock = FakeClock()
    pool = _clocked_pool(clock, n_workers=1, quantum=10,
                         flush_max_age_s=3600.0)
    pool.start()
    w = pool.workers[0]
    for i in range(3):
        assert pool.stage(np.full((10, 2), i, np.uint32))
    wait_until(lambda: w.outq.waiting_since is not None,
               "the third flush waits for room")
    assert pool.stage(np.full((4, 2), 3, np.uint32))
    epoch = pool.request_flush()
    time.sleep(0.1)
    assert not pool.flushed(epoch) and len(w.outq.q) == TRANSFER_DEPTH
    got = [_take(pool)[1][0, 0] for _ in range(4)]
    assert got == [0, 1, 2, 3]
    wait_until(lambda: pool.flushed(epoch), "the worker answers")
    assert w.pending_events() == 0 and w.handoff_dropped == 0
    pool.stop()


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_many_producers_and_a_stop_at_any_moment_lose_no_block_and_no_wake(
        long_parks, seed):
    """(g) Producers write a sink from many threads; a distributor
    parks on the sink's event and deals to the pool as the engine's
    feed loop does; a consumer takes the mux as the dispatch thread
    does; the stop falls at a random moment. Every wait is bounded
    only by a bound out of reach, so one lost wake-up hangs the round.
    Every accepted event is consumed, counted as dropped by the pool,
    or still in the sink the distributor left."""
    import random

    from retina_tpu.parallel.feed import park
    from retina_tpu.plugins.api import QueueSink

    rng = random.Random(seed)
    sink = QueueSink(max_blocks=64)
    pool = _mk_pool(n_workers=3, quantum=40, staging_blocks=4,
                    flush_max_age_s=0.01)
    stop = threading.Event()
    consumed = [0]
    accepted = [0] * 6

    def distributor():
        while not stop.is_set():
            blocks = sink.drain()
            for rec, _ in blocks:
                if not pool.stage(rec):
                    pool.count_drop(len(rec))
            if not blocks:
                park(sink.data, "feed", max_s=0.05)  # its stop bound
        pool.stop()
        pool.mux.put_ctl(None)

    def consumer():
        while True:
            try:
                item = pool.mux.get(timeout=60.0)
            except queue_mod.Empty:
                continue
            if item is None:
                return
            consumed[0] += len(item[1])
            if rng.random() < 0.3:
                pool.mux.wake()  # completions come at any moment
            if rng.random() < 0.2:
                pool.request_flush()  # and readers

    def producer(k, n, gaps):
        for i in range(n):
            accepted[k] += sink.write_records(
                np.full((1 + (i % 9), 2), k, np.uint32), "p")
            if gaps[i]:
                time.sleep(gaps[i])

    pool.start()
    plans = [(k, rng.randrange(20, 60),
              [rng.choice((0, 0, 0.0005, 0.003)) for _ in range(60)])
             for k in range(6)]
    threads = [threading.Thread(target=distributor, daemon=True),
               threading.Thread(target=consumer, daemon=True)]
    threads += [threading.Thread(target=producer, args=p, daemon=True)
                for p in plans]
    for t in threads:
        t.start()
    time.sleep(rng.uniform(0.0, 0.15))
    stop.set()
    for t in threads:
        t.join(30.0)
        assert not t.is_alive(), "a waiter was never woken"
    left = sum(len(rec) for rec, _ in sink.drain(max_blocks=10_000))
    assert consumed[0] + pool.staging_dropped_events + left \
        == sum(accepted) > 0
    assert sum(w.events_out for w in pool.workers) == consumed[0]


# -- the engine's six waiters ------------------------------------------

WAITERS = ("engine-feed", "engine-dispatch", "feed-worker-0",
           "feed-worker-1", "feed-worker-2", "feed-worker-3")


class _Started:
    """A started engine of the pool's size (four workers, the feed
    loop, the dispatch thread) with its heartbeats in reach."""

    def __init__(self, clock=None, **cfg_kw):
        from retina_tpu.runtime.supervisor import Supervisor

        cfg = small_cfg(feed_pipeline_depth=2, feed_workers=4,
                        window_seconds=1.0, **cfg_kw)
        self.sup = Supervisor(deadline_s=cfg.watchdog_deadline_s)
        kw = {} if clock is None else {"clock": clock}
        self.eng = SketchEngine(cfg, supervisor=self.sup, **kw)
        self.eng.compile()
        self.stop = threading.Event()
        self.thread = threading.Thread(
            target=self.eng.start, args=(self.stop,), daemon=True)

    def __enter__(self):
        from clockdrive import wait_until

        self.thread.start()
        assert self.eng.started.wait(30.0)
        wait_until(lambda: all(self.parked(n) for n in WAITERS),
                   "all six threads park")
        return self

    def parked(self, name: str) -> bool:
        hb = self.sup.heartbeat(name)
        return hb is not None and hb.parked

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join(30.0)
        assert not self.thread.is_alive()


@pytest.mark.parametrize("which_clock", ["injected", "wall"])
def test_an_idle_engine_wakes_under_30_times_a_second(which_clock):
    """(a) Nothing to do: the feed loop wakes for the controller's
    ticks and the window's, the rest for their safety bound. Six
    pollers of 2 ms made 3,000 wake-ups of this second."""
    from clockdrive import FakeClock

    clock = FakeClock() if which_clock == "injected" else None
    with _Started(clock):
        time.sleep(0.2)
        w0 = {t: wakeups(t) for t in ("feed", "worker", "dispatch")}
        t0 = time.monotonic()
        time.sleep(1.0)
        dt = time.monotonic() - t0
        got = {t: wakeups(t) - w0[t] for t in w0}
    assert sum(got.values()) / dt < 30, got
    assert got["feed"] >= 5, got  # the controller's ticks are kept


def test_stop_wakes_every_waiter(long_parks):
    """(e) All six threads parked, four of them with no deadline in
    reach: the stop reaches each (the pool's stop sets the workers'
    events, the sentinel the mux's; the feed loop's own bound is a
    quarter of a second) and the engine is down well inside the limits
    its shutdown allows (30 s a join)."""
    s = _Started()
    with s:
        time.sleep(0.3)
        assert all(s.parked(n) for n in WAITERS)
        t0 = time.monotonic()
        s.stop.set()
        s.thread.join(10.0)
        took = time.monotonic() - t0
    assert not s.thread.is_alive() and took < 5.0, took
    assert all(s.sup.heartbeat(n) is None for n in WAITERS)


def test_a_thread_parked_past_the_watchdog_deadline_is_not_stalled(
        long_parks):
    """(f) The waits are long now: a worker with nothing staged and
    the dispatch thread with nothing to take sleep for as long as
    nothing happens. They park their heartbeats first, so a watchdog
    whose deadline they outsleep many times over reports nothing."""
    from retina_tpu.metrics import get_metrics

    with _Started(watchdog_deadline_s=0.05) as s:
        w0 = wakeups("worker") + wakeups("dispatch")
        for _ in range(10):
            time.sleep(0.06)
            assert s.sup.scan_once() == []
        # Nobody woke them meanwhile: they really slept through it.
        assert wakeups("worker") + wakeups("dispatch") == w0
        assert all(s.sup.heartbeat(n).stalls == 0 for n in WAITERS)
        stalls = [smp.value for mf in
                  get_metrics().watchdog_stalls.collect()
                  for smp in mf.samples if smp.name.endswith("_total")]
        assert sum(stalls) == 0
