"""The documented deployment under a capture ring's cadence (ISSUE 29).

At rehearsal sizes, on the CPU, by an injected clock (clockdrive): the
same seeded events handed over as 1, 16 and 32 blocks a window give the
per-pod counters of the benchmark's plain reference with the overload
controller NOMINAL throughout, with Hubble's mirror on; the feed
workers hold the blocks dealt to them raw and the dispatch thread
holds and folds the flushes until a step's worth is held, the oldest
has aged out (counted from when it was dealt) or a reader asks, never
because the device fell idle; and a device that cannot keep up still takes the
controller to DEGRADED. The v5e-4 host's layout (ISSUE 33): the same
events over a four-device mesh give the reference's counters and the
one-device mesh's sketches.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import agent as bench_agent  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

from clockdrive import (  # noqa: E402
    Drive, FakeClock, controller_after, wait_until, wakeups,
)

from retina_tpu.engine import SketchEngine  # noqa: E402
from retina_tpu.hubble import FlowObserver, MonitorAgent  # noqa: E402
from retina_tpu.hubble.flow import record_to_flow  # noqa: E402
from retina_tpu.metrics import get_metrics  # noqa: E402
from retina_tpu.plugins.api import Plugin  # noqa: E402
from retina_tpu.runtime import faults  # noqa: E402
from retina_tpu.runtime import overload as ov  # noqa: E402
from retina_tpu.utils import metric_names as mn  # noqa: E402

WINDOWS = 3
# Seconds of the engine's clock a test that holds flushes keeps between
# itself and the next window tick (``Rig.to_before_a_window_tick``).
ROOM = 0.95


class _Source(Plugin):
    """Stands where the benchmark's seeded source stands: ``emit``
    writes the sink and mirrors into the monitor agent's channel."""

    name = "seededsource"

    def start(self, stop):  # pragma: no cover - never started
        pass


class Rig:
    """The configmap's engine at rehearsal sizes with the Hubble mirror
    wired as the daemon wires it, on an injected clock."""

    def __init__(self, tmp: str, **over):
        with open(os.path.join(
                BENCH, "configs", "advanced-pod-hubble.json")) as f:
            self.config = json.load(f)
        cfg = bench_agent.build_config(self.config, tmp, "", "", True)
        cfg.feed_workers = 2  # the pool, whatever the machine's cores
        cfg.mesh_devices = 2  # of the test session's eight virtual ones
        for k, v in over.items():
            setattr(cfg, k, v)
        self.mix = traffic.load_mix("zipf1m-steady", rehearse=True)
        self.clock = FakeClock()
        self.eng = SketchEngine(cfg, clock=self.clock)
        self.eng.update_identities({
            bench_agent.POD_NET + i: i
            for i in range(1, self.mix.n_endpoints)})
        self.eng.compile()
        self.stop = threading.Event()
        self.monitor = MonitorAgent()
        self.observer = FlowObserver(capacity=cfg.hubble_ring_capacity)
        self.monitor.register_consumer(self.observer.consume)
        self.source = _Source(cfg)
        self.source.set_sink(self.eng.sink)
        self.source.setup_channel(self.monitor.channel)
        self.monitor.start(self.stop)
        # The feed loop counts its window boundaries from here.
        self.t_started, self.closes0 = self.clock(), _window_ticks_answered()
        self.thread = threading.Thread(
            target=self.eng.start, args=(self.stop,), daemon=True)
        self.thread.start()
        assert self.eng.started.wait(30.0)
        # The feed loop has read the clock for its first boundary once
        # it has waited (its wait clears the event it waits on): only
        # then may the clock move.
        self.eng.sink.data.set()
        wait_until(lambda: not self.eng.sink.data.is_set(),
                   "the feed loop waits")
        self.drive = Drive(self.eng, self.clock, write=self.source.emit)

    def counters(self) -> tuple[np.ndarray, np.ndarray]:
        snap = self.eng.snapshot(max_age_s=0)
        n = self.mix.n_endpoints
        return (np.asarray(snap["pod_forward"])[:n].astype(np.int64),
                np.asarray(snap["pod_drop"])[:n].astype(np.int64))

    def close_lane_follows(self) -> None:
        """Wait until every window boundary the clock has passed has
        been answered (closed, or deferred and counted) and read back.
        A test that walks the clock on by ``controller_after`` alone
        hands the agent a window every few ticks and waits for none: on
        a loaded machine the closes and their readbacks then pile up
        behind the clock, and ``harvest`` (closed windows not yet read
        back, over four) reads as the pressure of a device that cannot
        keep up. ``Drive.tick`` waits for the same before it lets time
        pass. (No other engine of the session closes windows meanwhile:
        the module's rig stands on a clock nobody advances.)"""
        eng = self.eng

        def followed() -> bool:
            due = int((self.clock() - self.t_started)
                      / eng.cfg.window_seconds)
            return _window_ticks_answered() - self.closes0 >= due \
                and not eng._harvest_q.unfinished_tasks

        wait_until(followed, "the close lane follows the clock")

    def staged(self) -> int:
        """Blocks the feed workers hold, raw."""
        return sum(w.pending_blocks() for w in self.eng._feed_pool.workers)

    def hold(self, block) -> None:
        """Hand a block over: a feed worker holds it, raw, until its
        age, a reader or the stop releases it."""
        n = self.staged()
        self.drive.stage(block)
        assert self.staged() == n + 1

    def to_before_a_window_tick(self, before: float) -> None:
        """Let the clock run to ``before`` seconds short of a window
        boundary of the feed loop: a test that holds flushes by the
        clock decides where its window tick falls, because the tick
        reads, and a read releases what is held. A boundary in the way
        is passed first, and its close and readback waited for."""
        eng, ws, margin = self.eng, self.eng.cfg.window_seconds, 0.01
        assert before <= ws - 2 * margin

        def left() -> float:
            return ws - (self.clock() - self.t_started) % ws

        if left() < before + margin:
            # This engine's own close: the counters are the process's,
            # and an engine some earlier test left running closes
            # windows too.
            w0 = eng.last_window
            self.drive.tick(left() + margin)
            wait_until(lambda: eng.last_window is not w0
                       and not eng._harvest_q.unfinished_tasks,
                       "the window in the way closes")
        self.drive.tick(left() - before)

    def close(self) -> None:
        self.stop.set()
        self.thread.join(60.0)
        assert not self.thread.is_alive()


def _window_ticks_answered() -> float:
    m = get_metrics()
    return m.windows_closed._value.get() + m.windows_deferred._value.get()


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    r = Rig(str(tmp_path_factory.mktemp("ring")))
    yield r
    r.close()


@pytest.fixture(scope="module")
def rig1(tmp_path_factory):
    """A pipeline of one slot: one dispatch in flight fills it."""
    r = Rig(str(tmp_path_factory.mktemp("ring1")), feed_pipeline_depth=1)
    yield r
    r.close()


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


def _dispatches() -> dict[str, float]:
    """``tpu_dispatches_counter`` by cause, and ``all``: the count of
    ``tpu_step_seconds`` (a dispatch is one observation of it, made
    when the device has finished it)."""
    m = get_metrics()
    out = {c: m.dispatches.labels(cause=c)._value.get()
           for c in (mn.DISPATCH_FULL, mn.DISPATCH_AGE, mn.DISPATCH_READ,
                     mn.DISPATCH_DRAIN)}
    out["all"] = _count(m.device_step_seconds)
    return out


def _since(d0: dict[str, float]) -> dict[str, float]:
    return {k: v - d0[k] for k, v in _dispatches().items() if v != d0[k]}


def _flushes() -> dict[str, float]:
    """``tpu_feed_flushes_counter`` by cause."""
    m = get_metrics()
    return {c: m.feed_flushes.labels(cause=c)._value.get()
            for c in (mn.FLUSH_FULL, mn.FLUSH_AGE, mn.FLUSH_READ,
                      mn.FLUSH_DRAIN)}


def _flushes_since(f0: dict[str, float]) -> dict[str, float]:
    return {k: v - f0[k] for k, v in _flushes().items() if v != f0[k]}


@pytest.mark.parametrize("handovers", [1, 16, 32])
def test_handovers_a_window_agree_with_the_plain_reference(rig, handovers):
    """1, 16 and 32 hand-overs a window of the same seeded events: the
    per-pod counters are the plain reference's (so the three agree),
    nothing is sampled and the controller makes no transition; the
    observer's ring holds the reference's last ``capacity`` records as
    ``record_to_flow`` decodes them."""
    eng, mix, drive = rig.eng, rig.mix, rig.drive
    pool = traffic.make_pool(mix, seed=4000002901)
    rows = int(mix.rate_events_per_s * eng.cfg.window_seconds)
    total = WINDOWS * rows
    assert total <= len(pool) and rows % handovers == 0
    fwd0, drop0 = rig.counters()
    sampled0 = get_metrics().events_sampled._value.get()
    flushes0 = get_metrics().dispatch_flushes._value.get()
    steps0 = get_metrics().steps._value.get()
    d0 = _dispatches()
    tick = eng.cfg.window_seconds / handovers
    per = rows // handovers
    for a in range(0, total, per):
        drive.hand_over(pool[a:a + per].copy(), tick)
    drive.settle(tick)
    drive.close_a_window()
    st = eng.overload.stats()
    assert st["state"] == "NOMINAL" and st["transitions"] == 0, st
    assert get_metrics().events_sampled._value.get() == sampled0
    want = reference.offered(pool, total, mix.n_endpoints)
    fwd1, drop1 = rig.counters()
    assert np.array_equal(fwd1 - fwd0, want.fwd)
    assert np.array_equal(
        (drop1 - drop0)[:, :reference.N_REASONS], want.drop)
    assert want.fwd.sum() > 0 and want.drop.sum() > 0
    # Steps follow the rows, not the hand-overs: no more steps than
    # flushes, and every flush reached a dispatch.
    flushes = get_metrics().dispatch_flushes._value.get() - flushes0
    steps = get_metrics().steps._value.get() - steps0
    assert 0 < steps <= flushes
    # Every dispatch had one cause: a step's worth held, its age, or a
    # reader (a window's tick, this test's snapshots); none because
    # the device fell idle.
    by = _since(d0)
    assert by.pop("all") == sum(by.values()) > 0, by
    assert mn.DISPATCH_DRAIN not in by
    # The mirror: the monitor agent has drained its channel.
    wait_until(lambda: rig.monitor.channel.empty()
               and rig.observer.flows_seen % total == 0,
               "the mirror drains")
    cap = rig.observer._cap
    flows, end = rig.observer.snapshot_flows()
    assert end == rig.observer.flows_seen
    assert flows == [record_to_flow(r) for r in pool[total - cap:total]]
    lost = get_metrics().lost_events.labels(
        stage="external", plugin="seededsource")._value.get()
    assert lost == 0


def _shard_rows(n_devices: int) -> np.ndarray:
    m = get_metrics().shard_rows
    return np.array([m.labels(device=str(d))._value.get()
                     for d in range(n_devices)])


def _ring_cadence_on_a_mesh(tmp: str, n_devices: int, pool) -> dict:
    """WINDOWS windows of ``pool`` at 16 hand-overs a window over a mesh
    of ``n_devices``: what the agent answers, and what it dispatched."""
    r = Rig(tmp, mesh_devices=n_devices)
    try:
        eng, mix, drive = r.eng, r.mix, r.drive
        assert eng.n_devices == n_devices
        m = get_metrics()
        rows = int(mix.rate_events_per_s * eng.cfg.window_seconds)
        tick, per = eng.cfg.window_seconds / 16, rows // 16
        shard0, step0 = _shard_rows(n_devices), m.step_rows._value.get()
        lost0 = _lost_events()
        for a in range(0, WINDOWS * rows, per):
            drive.hand_over(pool[a:a + per].copy(), tick)
        drive.settle(tick)
        drive.close_a_window()
        fwd, drop = r.counters()
        snap = eng.snapshot(max_age_s=0)
        keys, counts = eng.top_flows(10)
        return {
            "fwd": fwd, "drop": drop[:, :reference.N_REASONS],
            "overload": eng.overload.stats(),
            "hll": np.asarray(snap["hll_flows"]).tolist(),
            "heavy": dict(zip(map(tuple, keys.tolist()), counts.tolist())),
            "shard_rows": _shard_rows(n_devices) - shard0,
            "step_rows": m.step_rows._value.get() - step0,
            "lost": _lost_events() - lost0,
        }
    finally:
        r.close()


def _lost_events() -> float:
    return sum(s.value for mf in get_metrics().lost_events.collect()
               for s in mf.samples if s.name.endswith("_total"))


def test_a_four_device_mesh_gives_the_reference_and_one_devices_answers(
        tmp_path):
    """The v5e-4 host's layout at a ring's cadence: the events are
    partitioned over four devices by connection and the answers merged
    on the device. The per-pod counters are the plain reference's, the
    heaviest flows and the HLL estimate those of the same events on a
    one-device mesh, nothing is lost or sampled, and
    ``tpu_shard_rows_counter`` says where the rows went: every device
    served, the shares summing to ``tpu_step_rows_counter``."""
    mix = traffic.load_mix("zipf1m-steady", rehearse=True)
    pool = traffic.make_pool(mix, seed=4000003301)
    four = _ring_cadence_on_a_mesh(str(tmp_path / "four"), 4, pool)
    one = _ring_cadence_on_a_mesh(str(tmp_path / "one"), 1, pool)
    total = WINDOWS * mix.rate_events_per_s
    want = reference.offered(pool, total, mix.n_endpoints)
    for got in (four, one):
        assert np.array_equal(got["fwd"], want.fwd)
        assert np.array_equal(got["drop"], want.drop)
        st = got["overload"]
        assert st["state"] == "NOMINAL" and st["transitions"] == 0, st
        assert got["lost"] == 0
        assert got["shard_rows"].sum() == got["step_rows"] > 0
    assert (four["shard_rows"] > 0).all()
    assert len(one["shard_rows"]) == 1
    # pmax of the devices' registers is the union stream's registers.
    assert four["hll"] == one["hll"]
    # A connection lives on one device, so its flow's candidates do:
    # the ten heaviest flows are the same ones. Their counts are what
    # conntrack had reported when the snapshot was taken, which the
    # flushes' timing moves by a few packets.
    assert four["heavy"].keys() == one["heavy"].keys()
    for key, n in one["heavy"].items():
        assert four["heavy"][key] == pytest.approx(n, rel=0.15), key


def test_the_dispatch_thread_folds_what_accumulates_behind_a_busy_device(
        rig):
    """One dispatch hangs on the proxy; the blocks dealt behind it are
    held raw by ONE worker (the deal stays with a worker short of its
    quantum), its completion releases none of them (an idle device is
    no reason to flush or to step), and they leave as ONE flush,
    combined once, and ONE dispatch when a reader asks."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=2902)
    m = get_metrics()
    fwd0, drop0 = rig.counters()
    d0, fl0 = _dispatches(), _flushes()
    f0 = m.dispatch_flushes._value.get()
    faults.configure("transfer:hang@1")
    per = 512
    # The first block goes by its age and hangs.
    rig.hold(pool[:per].copy())
    clock.advance(eng.cfg.flush_max_age_s)
    wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
    assert _flushes_since(fl0) == {mn.FLUSH_AGE: 1}
    # Six more, short of their age: one worker holds them all.
    for k in range(1, 7):
        rig.hold(pool[k * per:(k + 1) * per].copy())
    holders = [w for w in eng._feed_pool.workers if w.pending_events()]
    assert len(holders) == 1 and holders[0].pending_blocks() == 6
    b0 = holders[0].batches
    assert eng._held_flushes == 0
    assert _since(d0) == {}  # the one in flight has not finished
    assert eng._dispatches_landed(0.05) is False
    w0 = wakeups("dispatch", "data")
    faults.release_hangs()
    # The completion wakes the dispatch thread, which finds nothing
    # due: the device is idle and the blocks stay in their worker.
    wait_until(lambda: eng._busy_count() == 0
               and wakeups("dispatch", "data") > w0,
               "the completion wakes the dispatch thread")
    assert eng._dispatches_landed(0.0) is True
    assert holders[0].pending_blocks() == 6 and holders[0].batches == b0
    assert _since(d0) == {mn.DISPATCH_AGE: 1, "all": 1}
    st = eng.feed_stats()
    assert st["dispatch"] == {"in_flight": 0, "held_flushes": 0,
                              "held_age_s": 0.0}
    assert sum(w["staged_blocks"] for w in st["per_worker"]) == 6
    want = reference.Counts(mix.n_endpoints).add(pool[:7 * per])
    fwd1, drop1 = rig.counters()  # a snapshot: a reader
    assert np.array_equal(fwd1 - fwd0, want.fwd)
    drive.settle()
    assert _since(d0) == {mn.DISPATCH_AGE: 1, mn.DISPATCH_READ: 1, "all": 2}
    assert m.dispatch_flushes._value.get() - f0 == 2
    assert _flushes_since(fl0) == {mn.FLUSH_AGE: 1, mn.FLUSH_READ: 1}
    assert holders[0].batches == b0 + 1
    # A stall of 0.4 s of the engine's time with nothing piling up
    # behind it is not pressure: no transition.
    assert eng.feed_stats()["dispatch"] == {
        "in_flight": 0, "held_flushes": 0, "held_age_s": 0.0}
    assert eng.overload.stats()["transitions"] == 0


def test_a_completion_wakes_the_holders_of_rows_not_the_clock(
        rig1, long_parks):
    """(c), (d) With the pipeline's one slot taken by a dispatch hung
    on the proxy, the dispatch thread holds a flush whose first block
    was dealt ``flush_max_age_s`` ago (for a slot), and a worker holds
    a block short of its age: neither waits by the clock (their idle
    bound is out of reach and the clock stands still). The completion
    (``_dispatch_done``) wakes the dispatch thread, which dispatches
    the overdue flush with no further tick; it wakes no worker, and the
    block stays held until a reader asks."""
    rig = rig1
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    # The window's tick falls where the pipeline is full: its close
    # overtakes, and releases nothing.
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=2904)
    fwd0, _ = rig.counters()
    d0 = _dispatches()
    faults.configure("transfer:hang@1")
    per = 512
    rig.hold(pool[:per].copy())
    clock.advance(eng.cfg.flush_max_age_s)
    wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
    # A flush for the dispatch thread to hold past its age: it leaves
    # its worker by age, with no slot to go to.
    rig.hold(pool[per:2 * per].copy())
    clock.advance(eng.cfg.flush_max_age_s)
    wait_until(lambda: eng._held_flushes == 1, "a flush is held")
    # A block for a worker to hold, short of its age.
    rig.hold(pool[2 * per:3 * per].copy())
    holders = [w for w in eng._feed_pool.workers if w.pending_events()]
    assert len(holders) == 1
    p0 = wakeups("dispatch")
    clock.advance(0.01)
    wait_until(lambda: wakeups("dispatch") > p0, "the clock wakes it")
    t0 = clock()
    assert holders[0].pending_events() == per
    assert eng._held_flushes == 1 and eng._busy_count() == 1
    assert eng.feed_stats()["dispatch"]["held_age_s"] \
        >= eng.cfg.flush_max_age_s
    assert _since(d0) == {}
    b0 = holders[0].batches
    faults.release_hangs()
    # The overdue flush goes by its age as the slot comes back.
    wait_until(lambda: _since(d0) == {mn.DISPATCH_AGE: 2, "all": 2}
               and eng._busy_count() == 0,
               "the completion wakes the dispatch thread")
    assert clock() == t0  # no tick did it
    assert holders[0].batches == b0 and holders[0].pending_events() == per
    want = reference.Counts(mix.n_endpoints).add(pool[:3 * per])
    fwd1, _ = rig.counters()  # a reader releases the held block
    assert np.array_equal(fwd1 - fwd0, want.fwd)
    assert holders[0].batches == b0 + 1
    assert clock() == t0
    wait_until(lambda: _since(d0) == {mn.DISPATCH_AGE: 2,
                                      mn.DISPATCH_READ: 1, "all": 3},
               "the read's dispatch is seen finished")
    assert eng.overload.stats()["transitions"] == 0


def test_an_idle_pipeline_holds_a_small_flush_until_it_ages_out(
        rig, long_parks):
    """Nothing in flight and a block of a few hundred rows: its worker
    holds it, raw. It is flushed, and dispatched at once, when the
    engine's clock reaches ``flush_max_age_s`` from the moment it was
    dealt, and not an instant before; the wait for that moment is a
    ``deadline`` wake-up of the worker (its idle bound is out of reach
    here)."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=3401)
    fwd0, _ = rig.counters()
    d0, fl0, e0 = _dispatches(), _flushes(), eng._events_in
    dealt = clock()
    rig.hold(pool[:512].copy())
    assert eng._busy_count() == 0
    holder, = [w for w in eng._feed_pool.workers if w.pending_events()]
    assert holder.staging[0][1] == dealt
    # Just short of the bound: woken (the clock was advanced) and the
    # rows stay.
    w0 = wakeups("worker")
    clock.advance(eng.cfg.flush_max_age_s - 0.002)
    wait_until(lambda: wakeups("worker") > w0, "the advance wakes it")
    p0 = wakeups("worker", "deadline")
    wait_until(lambda: wakeups("worker", "deadline") > p0,
               "it sleeps to the age bound, two milliseconds away")
    assert holder.pending_events() == 512 and eng._events_in == e0
    assert _since(d0) == {} and _flushes_since(fl0) == {}
    clock.advance(0.004)  # past the bound, whatever the sums round to
    wait_until(lambda: eng._events_in == e0 + 512 and eng._busy_count() == 0,
               "the block goes at its age")
    assert clock() >= dealt + eng.cfg.flush_max_age_s
    assert eng._held_flushes == 0
    assert _since(d0) == {mn.DISPATCH_AGE: 1, "all": 1}
    assert _flushes_since(fl0) == {mn.FLUSH_AGE: 1}
    want = reference.Counts(mix.n_endpoints).add(pool[:512])
    assert np.array_equal(rig.counters()[0] - fwd0, want.fwd)
    assert _since(d0) == {mn.DISPATCH_AGE: 1, "all": 1}  # nothing held


def test_a_steps_worth_of_held_rows_goes_at_once(rig, monkeypatch):
    """Flushes are held by the dispatch thread while the fullest
    device's rows are short of ``batch_capacity``; the flush that makes
    a step's worth releases them all as one dispatch, the clock far
    from their age. (Each block here fills its worker's quantum, so it
    leaves its worker at once, ``full``: a saturated feed's flushes.)"""
    from retina_tpu.parallel.combine import combine_blocks
    from retina_tpu.parallel.partition import partition_events

    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=3402)
    fwd0, _ = rig.counters()
    d0, fl0, e0, t0 = _dispatches(), _flushes(), eng._events_in, clock()
    # A block of 8,192 events combines to about 670 rows a device:
    # which one makes a step's worth on the fullest.
    per, n, rows = 8192, 0, np.zeros((eng.n_devices,), np.int64)
    while rows.max() < eng.cfg.batch_capacity:
        rows += partition_events(
            combine_blocks([pool[n * per:(n + 1) * per].copy()]),
            eng.n_devices, eng.cfg.batch_capacity).n_valid
        n += 1
    assert 2 <= n <= 6
    monkeypatch.setattr(eng._feed_pool, "quantum", per)
    for k in range(n - 1):
        drive.stage(pool[k * per:(k + 1) * per].copy())
        wait_until(lambda: eng._held_flushes == k + 1,
                   "the dispatch thread holds the flush")
    assert _since(d0) == {}
    drive.stage(pool[(n - 1) * per:n * per].copy())
    wait_until(lambda: eng._events_in == e0 + n * per
               and eng._busy_count() == 0, "they go together")
    assert clock() == t0
    assert eng._held_flushes == 0
    assert _since(d0) == {mn.DISPATCH_FULL: 1, "all": 1}
    assert _flushes_since(fl0) == {mn.FLUSH_FULL: n}
    want = reference.Counts(mix.n_endpoints).add(pool[:n * per])
    assert np.array_equal(rig.counters()[0] - fwd0, want.fwd)


def test_no_row_waits_longer_than_the_age_bound_in_staging_and_hold_together(
        rig, monkeypatch, long_parks):
    """A flush that leaves its worker before its age (here a full
    quantum) and is held by the dispatch thread short of a step's
    worth goes when its first block was dealt ``flush_max_age_s`` ago:
    the bound counts the staging and the hold together, and does not
    start again at the hand-off."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=3407)
    fwd0, _ = rig.counters()
    age = eng.cfg.flush_max_age_s
    d0, e0, dealt = _dispatches(), eng._events_in, clock()
    monkeypatch.setattr(eng._feed_pool, "quantum", 512)
    drive.stage(pool[:512].copy())
    wait_until(lambda: eng._held_flushes == 1,
               "the dispatch thread holds the flush")
    assert eng._held_since == dealt
    clock.advance(age / 2)  # its worker's part of the bound
    p0 = wakeups("dispatch", "deadline")
    clock.advance(age / 2 - 0.002)
    wait_until(lambda: wakeups("dispatch", "deadline") > p0,
               "it sleeps to the age bound, two milliseconds away")
    assert eng._held_flushes == 1 and eng._events_in == e0
    assert _since(d0) == {}
    clock.advance(0.004)
    wait_until(lambda: eng._events_in == e0 + 512 and eng._busy_count() == 0,
               "the flush goes at the age of its first block")
    assert clock() - dealt < age + 0.01
    assert _since(d0) == {mn.DISPATCH_AGE: 1, "all": 1}
    want = reference.Counts(mix.n_endpoints).add(pool[:512])
    assert np.array_equal(rig.counters()[0] - fwd0, want.fwd)


def test_sixteen_hand_overs_a_second_make_one_flush_a_release(rig):
    """At a ring's cadence on an idle pipeline the blocks one release
    will read are held raw by one worker and combined once: two windows
    of 16 hand-overs make a flush for each release by the oldest
    block's age or by the window's tick, not one a hand-over, and every
    flush reaches a dispatch (two, where a tick finds an aged flush
    still on the mux, fold into one); the counters are the plain
    reference's."""
    eng, mix, drive = rig.eng, rig.mix, rig.drive
    drive.settle()
    pool = traffic.make_pool(mix, seed=4301)
    m = get_metrics()
    rows = int(mix.rate_events_per_s * eng.cfg.window_seconds)
    per, total = rows // 16, 2 * rows
    fwd0, _ = rig.counters()
    d0, fl0 = _dispatches(), _flushes()
    f0 = m.dispatch_flushes._value.get()
    for a in range(0, total, per):
        drive.hand_over(pool[a:a + per].copy(), eng.cfg.window_seconds / 16)
    drive.settle(eng.cfg.window_seconds / 16)
    flushes = _flushes_since(fl0)
    n = sum(flushes.values())
    # A block's age (0.4 s) or a tick releases it: about three flushes
    # a window, against 16 hand-overs.
    assert 4 <= n <= 8, flushes
    assert set(flushes) <= {mn.FLUSH_AGE, mn.FLUSH_READ}, flushes
    assert m.dispatch_flushes._value.get() - f0 == n
    by = _since(d0)
    assert by.pop("all") == sum(by.values()) <= n, by
    want = reference.offered(pool, total, mix.n_endpoints)
    assert np.array_equal(rig.counters()[0] - fwd0, want.fwd)
    assert eng.overload.stats()["transitions"] == 0


def _closed_window_events(eng) -> int:
    return eng.last_window["overload"]["events"]


def test_a_window_tick_dispatches_what_is_held_before_its_close(rig):
    """Blocks dealt before a window's tick are flushed for it (the tick
    carries the feed's flush request, which the dispatch thread waits
    on) and go to the device before its close is submitted: their rows
    land in the window they were dealt in (the close's annotation
    counts them), short of their age."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    pool = traffic.make_pool(mix, seed=3403)
    rig.to_before_a_window_tick(0.2)
    d0, fl0, e0 = _dispatches(), _flushes(), eng._events_in
    open0 = e0 - eng._closed_events_in  # earlier tests'
    rig.hold(pool[:512].copy())
    rig.hold(pool[512:1024].copy())
    assert _since(d0) == {}
    clock.advance(0.2 + 0.01)  # the boundary
    assert 0.21 < eng.cfg.flush_max_age_s
    wait_until(lambda: eng._closed_events_in == e0 + 1024
               and not eng._harvest_q.unfinished_tasks
               and eng._busy_count() == 0, "the window closes")
    assert _since(d0) == {mn.DISPATCH_READ: 1, "all": 1}
    assert _flushes_since(fl0) == {mn.FLUSH_READ: 1}
    assert _closed_window_events(eng) == open0 + 1024
    assert eng._held_flushes == 0 and rig.staged() == 0


def test_a_close_overtakes_what_is_held_when_the_pipeline_is_full(rig1):
    """The pipeline's one slot is taken by a dispatch hung on the
    proxy and a flush is held for it: the window's tick does not wait,
    its close is submitted ahead of the held rows, which land in the
    next window (overdue, they go by their age as soon as the slot is
    free)."""
    rig = rig1
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    pool = traffic.make_pool(mix, seed=3404)
    age, gap = eng.cfg.flush_max_age_s, 0.1
    before = 2 * age + gap
    assert before < eng.cfg.window_seconds
    rig.to_before_a_window_tick(before)
    d0, e0 = _dispatches(), eng._events_in
    open0 = e0 - eng._closed_events_in  # earlier tests'
    faults.configure("transfer:hang@1")
    rig.hold(pool[:512].copy())
    clock.advance(age)
    wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
    rig.hold(pool[512:1280].copy())
    clock.advance(age)
    wait_until(lambda: eng._held_flushes == 1, "a flush is held")
    # The boundary: the tick comes off the mux with no slot free.
    clock.advance(gap + 0.01)
    wait_until(lambda: eng._close_inflight._value < 2,
               "the close is submitted")
    assert eng._held_flushes == 1 and eng._events_in == e0
    faults.release_hangs()
    # (This engine's own closes: the process's counters also count
    # those of an engine some earlier test left running.)
    wait_until(lambda: eng._closed_events_in == e0 + 512
               and not eng._harvest_q.unfinished_tasks
               and eng._events_in == e0 + 1280
               and eng._busy_count() == 0, "the window closes")
    assert _closed_window_events(eng) == open0 + 512
    assert eng._held_flushes == 0
    assert _since(d0) == {mn.DISPATCH_AGE: 2, "all": 2}
    # The next tick closes the window the held rows landed in.
    clock.advance(eng.cfg.window_seconds)
    wait_until(lambda: eng._closed_events_in == e0 + 1280
               and not eng._harvest_q.unfinished_tasks,
               "the next window closes")
    assert _closed_window_events(eng) == 768
    by = _since(d0)
    assert by.pop("all") == sum(by.values()) == 2


def test_a_snapshot_holds_every_event_flushed_before_it(rig):
    """``engine.snapshot()`` about to submit a readback has the feed
    workers flush what they hold and the dispatch thread submit it:
    the snapshot holds every event dealt before the call, and nothing
    the sink accepted lags it; its readback is submitted once the
    device has finished what was released, not on its heels. A
    snapshot served from the cache asks for nothing and releases
    nothing."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    rig.to_before_a_window_tick(ROOM)
    pool = traffic.make_pool(mix, seed=3405)
    # Once unasserted, so that no program of this shape compiles below.
    rig.hold(pool[2048:2560].copy())
    rig.hold(pool[2560:3072].copy())
    fwd0, _ = rig.counters()
    wait_until(lambda: eng._busy_count() == 0, "the device finishes")
    d0, fl0, e0 = _dispatches(), _flushes(), eng._events_in
    rig.hold(pool[:512].copy())
    rig.hold(pool[512:1024].copy())
    asked = eng._reads_asked
    snap = eng.snapshot(max_age_s=0)
    assert snap["events_in"] == e0 + 1024 == eng._events_in
    assert eng.publish_lag_s(snap) == (0.0, e0 + 1024)
    assert eng._reads_asked == asked + 1 == eng._reads_served
    assert eng._held_flushes == 0 and rig.staged() == 0
    # The readback was submitted behind a dispatch that had landed.
    assert eng._busy_count() == 0
    want = reference.Counts(mix.n_endpoints).add(pool[:1024])
    n = mix.n_endpoints
    assert np.array_equal(
        np.asarray(snap["pod_forward"])[:n].astype(np.int64) - fwd0,
        want.fwd)
    wait_until(lambda: eng._busy_count() == 0, "the device finishes")
    assert _since(d0) == {mn.DISPATCH_READ: 1, "all": 1}
    assert _flushes_since(fl0) == {mn.FLUSH_READ: 1}  # one worker's two
    # From the cache: the same snapshot, and the block stays held.
    rig.hold(pool[1024:1536].copy())
    assert eng.snapshot(max_age_s=3600.0) is snap
    assert eng._reads_asked == asked + 1 and rig.staged() == 1
    assert eng.publish_lag_s(snap)[0] > 0.0
    assert _since(d0) == {mn.DISPATCH_READ: 1, "all": 1}
    drive.settle()


def test_shutdown_drains_what_is_held_and_no_snapshot_waits_for_the_dead(
        tmp_path):
    """The stop finds a block held: its worker flushes it (``drain``)
    and it is dispatched (``drain``) before the dispatch thread ends,
    and the engine's totals hold it. With the
    thread gone, by the sentinel or by a fault that killed it, a
    snapshot asks nobody and returns. Over the rig's life every
    dispatch had exactly one cause."""
    r = Rig(str(tmp_path))
    eng = r.eng
    d0 = _dispatches()
    try:
        pool = traffic.make_pool(r.mix, seed=3406)
        fwd0, _ = r.counters()
        r.hold(pool[:512].copy())
        clock_t = r.clock()
    finally:
        r.close()
    assert r.clock() == clock_t  # no age: the drain did it
    assert eng._events_in == 512 and eng._held_flushes == 0
    assert r.staged() == 0
    wait_until(lambda: _since(d0) == {mn.DISPATCH_DRAIN: 1, "all": 1},
               "the completion thread has seen the drained dispatch")
    assert eng._dispatch_thread is None
    t0 = time.monotonic()
    want = reference.Counts(r.mix.n_endpoints).add(pool[:512])
    assert np.array_equal(r.counters()[0] - fwd0, want.fwd)
    # A thread that died without its farewell (an error escaped it).
    dead = threading.Thread(target=lambda: None)
    dead.start()
    dead.join()
    eng._dispatch_thread = dead
    asked = eng._reads_asked
    assert eng.snapshot(max_age_s=0)["events_in"] == 512
    assert eng._reads_asked == asked
    assert time.monotonic() - t0 < 30.0  # two readbacks, no wait


def test_a_read_while_a_transfers_worth_is_held_neither_deadlocks_nor_loses(
        tmp_path, monkeypatch):
    """The pipeline's one slot is taken by a dispatch hung on the proxy;
    the dispatch thread holds a transfer's worth and takes no more step
    items, so the workers' hand-off queues fill and a worker waits on
    its own. A snapshot asks the workers to flush: the waiting worker
    cannot answer, the snapshot's wait is bounded, and the dispatch
    thread goes on. Once the device returns, everything drains: the
    request is answered, every block dealt reaches the device exactly
    once, and nothing is lost or sampled."""
    r = Rig(str(tmp_path), feed_pipeline_depth=1, feed_coalesce_windows=1)
    try:
        eng, mix, drive, clock = r.eng, r.mix, r.drive, r.clock
        pool = traffic.make_pool(mix, seed=3408)
        lost0 = _lost_events()
        per = 8192
        blocks = [pool[:512].copy()]
        faults.configure("transfer:hang@1")
        r.hold(blocks[0])
        clock.advance(eng.cfg.flush_max_age_s)
        wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
        # Each block fills its worker's quantum and leaves it at once.
        monkeypatch.setattr(eng._feed_pool, "quantum", per)
        workers = eng._feed_pool.workers
        for k in range(40):
            if any(w.outq.waiting_since is not None for w in workers):
                break
            blocks.append(pool[(k % 8) * per:(k % 8 + 1) * per].copy())
            drive.stage(blocks[-1])
            time.sleep(0.05)
        wait_until(lambda: any(w.outq.waiting_since is not None
                               for w in workers), "a worker waits")
        assert eng._held_flushes >= 2 and eng._busy_count() == 1
        reader = threading.Thread(
            target=eng.snapshot, kwargs={"max_age_s": 0}, daemon=True)
        asked = eng._reads_asked
        reader.start()
        wait_until(lambda: eng._reads_asked == asked + 1, "the reader asks")
        epoch = eng._read_epoch
        time.sleep(2 * eng.cfg.flush_max_age_s)  # past the reader's bound
        assert not eng._feed_pool.flushed(epoch)
        assert eng._dispatch_thread.is_alive()
        held = eng._held_flushes
        assert held >= 2 and eng.feed_stats()["dispatch"]["in_flight"] == 1
        faults.release_hangs()
        reader.join(60.0)
        assert not reader.is_alive()
        wait_until(lambda: eng._feed_pool.flushed(epoch),
                   "the request is answered")
        drive.settle()
        want = reference.Counts(mix.n_endpoints)
        for b in blocks:
            want.add(b)
        fwd, drop = r.counters()
        assert np.array_equal(fwd, want.fwd)
        assert np.array_equal(drop[:, :reference.N_REASONS], want.drop)
        assert eng._events_in == sum(len(b) for b in blocks)
        assert _lost_events() == lost0
        st = eng.overload.stats()
        assert st["state"] == "NOMINAL" and st["transitions"] == 0, st
    finally:
        faults.clear()
        r.close()


def test_fold_of_two_side_windows_is_their_valid_rows_in_order():
    from retina_tpu.engine import fold_side_windows

    rng = np.random.default_rng(7)
    a = rng.integers(1, 1 << 30, (3, 16, 4), dtype=np.uint32)
    b = rng.integers(1, 1 << 30, (3, 16, 4), dtype=np.uint32)
    na = np.array([0, 5, 16], np.uint32)
    nb = np.array([16, 7, 0], np.uint32)
    out, n = fold_side_windows(a, na, b, nb)
    out, n = np.asarray(out), np.asarray(n)
    assert n.tolist() == [16, 12, 16]
    for d in range(3):
        want = np.concatenate([a[d, :na[d]], b[d, :nb[d]]])
        assert np.array_equal(out[d, :n[d]], want)


def _count(hist) -> float:
    return sum(s.value for mf in hist.collect() for s in mf.samples
               if s.name.endswith("_count"))


def test_a_device_that_cannot_keep_up_takes_the_controller_to_degraded(
        tmp_path):
    """A full pipeline that keeps up is not pressure; a device that
    cannot finish a dispatch is, through what piles up behind it.
    ``feed.backpressure`` alone pins SHEDDING (0.95, as documented).
    With ``transfer`` hung on the proxy the one dispatch in flight
    never ends, the feed holds what arrives (here for as long as it
    likes: the age bound is set out of the way, and the window's tick,
    a read that flushes the staging, out of the steps' reach, so that
    the pile is the workers' staging, which fills a block at a time),
    and the arc NOMINAL -> SAMPLING -> SHEDDING -> DEGRADED is walked,
    then walked back once the device returns."""
    # The controller's cadence is made finer than the steps this test
    # takes and the staging smaller; no threshold is touched.
    r = Rig(str(tmp_path), overload_tick_s=0.01, feed_staging_blocks=64,
            flush_max_age_s=3600.0, window_seconds=10.0)
    try:
        eng, clock, drive = r.eng, r.clock, r.drive
        pool = traffic.make_pool(r.mix, seed=2903)
        faults.configure("transfer:hang@1")
        # Held by the dispatch thread (its age bound is out of the way
        # too) until the window's tick reads: dispatched, it hangs.
        r.hold(pool[:512].copy())
        clock.advance(eng.cfg.window_seconds)
        wait_until(lambda: eng._busy_count() == 1, "the dispatch hangs")
        seen = [eng.overload.state]
        fills = {}
        for k in range(8, 8 + 130):
            drive.stage(pool[64 * k:64 * (k + 1)].copy())
            seen.append(controller_after(eng, clock, 0.02))
            fills.setdefault(seen[-1], eng.overload.stats()[
                "signals"]["staging"])
            if seen[-1] == ov.DEGRADED:
                break
        assert seen[-1] == ov.DEGRADED
        # Each level entered at its own threshold, by the staging fill.
        assert 0.75 <= fills[ov.SAMPLING] < 0.90 <= fills[ov.SHEDDING] \
            < 0.98 <= fills[ov.DEGRADED]
        sig = eng.overload.stats()["signals"]
        assert "inflight" not in sig and "lateness" not in sig
        # The gauges the poller scrapes say the same.
        m = get_metrics()
        assert m.overload_pressure._value.get() >= 0.98
        assert m.overload_signal.labels(
            signal="staging")._value.get() >= 0.98
        assert eng.feed_stats()["dispatch"]["in_flight"] == 1
        # The device returns: the pile drains (a reader, as the
        # publisher is once a second, has the workers flush what they
        # stage and the dispatch thread submit it), one level down a
        # dwell.
        faults.release_hangs()
        faults.clear()
        def drained() -> bool:
            r.counters()
            return eng._busy_count() == 0 \
                and eng._events_in >= drive.offered

        wait_until(drained, "the pile drains")
        for _ in range(200):
            # A quarter of a dwell is half a window: let the close lane
            # follow, or its backlog is the pressure that is read.
            r.close_lane_follows()
            seen.append(controller_after(
                eng, clock, eng.cfg.overload_dwell_s / 4))
            if seen[-1] == ov.NOMINAL:
                break
        assert eng.overload.state == ov.NOMINAL
        arc = [s for i, s in enumerate(seen) if i == 0 or s != seen[i - 1]]
        assert arc == [ov.NOMINAL, ov.SAMPLING, ov.SHEDDING, ov.DEGRADED,
                       ov.SHEDDING, ov.SAMPLING, ov.NOMINAL]
        # feed.backpressure by itself: SHEDDING, and no further.
        r.close_lane_follows()
        faults.configure("feed.backpressure:press")
        assert controller_after(
            eng, clock, 2 * eng.cfg.overload_tick_s) == ov.SHEDDING
        assert eng.overload.stats()["signals"]["fault"] == 0.95
    finally:
        faults.clear()
        r.close()


def test_fold_batches_takes_the_prefix_that_fits_one_transfer():
    from retina_tpu.parallel.partition import ShardedBatch, fold_batches

    def sb(n0, n1, k=1, seed=0):
        rng = np.random.default_rng(seed)
        rec = np.zeros((2, 8, 16), np.uint32)
        rec[0, :n0] = rng.integers(1, 99, (n0, 16))
        rec[1, :n1] = rng.integers(1, 99, (n1, 16))
        return ShardedBatch(records=rec, n_valid=np.array([n0, n1],
                            np.uint32), lost=1, events=n0 + n1, sample_k=k)

    a, b, c, d = sb(3, 1, seed=1), sb(2, 4, seed=2), sb(4, 4, seed=3), \
        sb(1, 1, k=8, seed=4)
    # One batch is handed back as it is.
    assert fold_batches([a], 8)[0] is a
    # a + b fit 8 rows a device; c would make 9 on device 1.
    got, took = fold_batches([a, b, c], 8, min_bucket=4)
    assert took == 2 and got.n_valid.tolist() == [5, 5]
    assert got.records.shape == (2, 6, 16)  # the bucket above 5 rows
    assert np.array_equal(got.records[0, :5],
                          np.concatenate([a.records[0, :3],
                                          b.records[0, :2]]))
    assert np.array_equal(got.records[1, :5],
                          np.concatenate([a.records[1, :1],
                                          b.records[1, :4]]))
    assert (got.lost, got.events, got.sample_k) == (2, 10, 1)
    # A batch sampled at another rate is not folded in.
    assert fold_batches([a, d], 8)[1] == 1
    assert fold_batches([a, b], 8)[0].records.shape == (2, 8, 16)


def test_one_frozen_enqueue_is_one_sample_of_dispatch_latency(rig):
    """The process freezing for seconds inside one enqueue is not an
    overload: a sample weighs at most the budget it is read against,
    so one reads 0.2 and only enqueues slow one after another reach
    the thresholds."""
    eng, clock = rig.eng, rig.clock
    rig.drive.settle()
    eng._dispatch_lat_ewma = 0.0
    none = np.zeros((eng.n_devices,), np.uint32)
    eng._note_dispatched(clock() - 5.0, none, 1, 0, 1)
    assert eng._overload_signals()["dispatch_lat"] == pytest.approx(0.2)
    for _ in range(12):
        eng._note_dispatched(clock() - 5.0, none, 1, 0, 1)
    assert eng._overload_signals()["dispatch_lat"] > 0.9
    eng._dispatch_lat_ewma = 0.0
