"""Sharded multi-worker host feed (parallel/feed.py): the bounded
double-buffered handoff primitives, the worker pool's staging/flush/
backpressure contract, and engine-level agreement between the sharded
and inline feed paths.

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
    cfg.flush_interval_s = 0.01
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
        flush_interval_s=0.01, flush_max_age_s=0.05,
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
    assert st["mode"] == "sharded"
    assert st["dropped_blocks"] == 0
    assert sum(w["events"] for w in st["per_worker"]) == total


def test_pool_stop_flushes_staged_remainder():
    pool = _mk_pool(quantum=10_000, flush_interval_s=60.0,
                    flush_max_age_s=60.0)
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
                    flush_interval_s=60.0, flush_max_age_s=60.0)
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


def _run_feed(cfg, n_events=1600):
    """One feed of ``n_events`` by an injected clock (clockdrive): on
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
        drive.hand_over(gen.batch(400), 0.03)
    drive.settle()
    assert eng.overload.stats()["transitions"] == 0
    snap = eng.snapshot(max_age_s=0)
    stats = eng.feed_stats()
    stop.set()
    t.join(30.0)
    assert not t.is_alive()
    return eng, snap, stats


def test_sharded_feed_agrees_with_inline():
    """The sharded pool lands exactly the events the inline pipelined
    feed lands — combining/partitioning in workers is lossless and the
    dispatch thread still serializes flow-dict/wire/submit."""
    _, snap_inline, st_inline = _run_feed(
        small_cfg(feed_pipeline_depth=2, feed_workers=1)
    )
    _, snap_pool, st_pool = _run_feed(
        small_cfg(feed_pipeline_depth=2, feed_workers=2)
    )
    assert st_inline["mode"] == "inline"
    assert st_pool["mode"] == "sharded"
    assert st_pool["workers"] == 2
    assert st_pool["dropped_blocks"] == 0
    assert int(snap_pool["totals"][0]) == 1600
    assert int(snap_pool["totals"][0]) == int(snap_inline["totals"][0])
    assert int(snap_pool["totals"][1]) == int(
        np.asarray(snap_pool["pod_forward"])[:, :, 0].sum()
    )
    # Per-worker accounting covers the full stream.
    assert sum(w["events"] for w in st_pool["per_worker"]) == 1600


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
