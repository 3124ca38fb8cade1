"""The documented deployment under a capture ring's cadence (ISSUE 29).

At rehearsal sizes, on the CPU, by an injected clock (clockdrive): the
same seeded events handed over as 1, 16 and 32 blocks a window give the
per-pod counters of the benchmark's plain reference with the overload
controller NOMINAL throughout, with Hubble's mirror on; the dispatch
thread folds what accumulates behind a busy device; a device that
cannot keep up still takes the controller to DEGRADED. The v5e-4 host's
layout (ISSUE 33): the same events over a four-device mesh give the
reference's counters and the one-device mesh's sketches.
"""

from __future__ import annotations

import json
import os
import sys
import threading

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

WINDOWS = 3


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


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


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
    """One dispatch hangs on the proxy; the flushes that arrive behind
    it are held, and leave as ONE dispatch when it completes."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    pool = traffic.make_pool(mix, seed=2902)
    m = get_metrics()
    fwd0, drop0 = rig.counters()
    d0 = m.device_step_seconds._sum.get(), _count(m.device_step_seconds)
    f0 = m.dispatch_flushes._value.get()
    faults.configure("transfer:hang@1")
    per = 512
    # The first block flushes at once (the pipeline is idle) and hangs.
    drive.stage(pool[:per].copy())
    clock.advance(0.06)
    wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
    # Six more, each old enough to flush at the feed's max age: the
    # workers hand them to the dispatch thread, which holds them.
    for k in range(1, 7):
        drive.stage(pool[k * per:(k + 1) * per].copy())
        clock.advance(0.05)
    clock.advance(eng.cfg.flush_max_age_s)
    wait_until(lambda: eng._held_flushes > 0
               and sum(w["events"] for w in
                       eng.feed_stats()["per_worker"]) >= drive.offered
               and all(not w.outq.q for w in eng._feed_pool.workers)
               and eng._busy_count() == 1,
               "the flushes behind it are held")
    assert _count(m.device_step_seconds) == d0[1]
    faults.release_hangs()
    drive.settle()
    dispatches = _count(m.device_step_seconds) - d0[1]
    flushes = m.dispatch_flushes._value.get() - f0
    assert dispatches == 2, (dispatches, flushes)
    assert flushes >= 3  # the first alone, the rest folded
    want = reference.Counts(mix.n_endpoints).add(pool[:7 * per])
    fwd1, drop1 = rig.counters()
    assert np.array_equal(fwd1 - fwd0, want.fwd)
    # A stall of 0.7 s of the engine's time with nothing piling up
    # behind it is not pressure: no transition.
    assert eng.feed_stats()["dispatch"] == {"in_flight": 0,
                                           "held_flushes": 0}
    assert eng.overload.stats()["transitions"] == 0


def test_a_completion_wakes_the_holders_of_rows_not_the_clock(
        rig, long_parks):
    """(c), (d) With a dispatch hung on the proxy, one worker holds a
    partial quantum past ``flush_interval_s`` and the dispatch thread
    holds a flush: both wait for the pipeline, and neither waits by
    the clock (their idle bound is out of reach and the clock stands
    still). The completion (``_dispatch_done``) wakes both: the held
    flush and the partial quantum are dispatched with no further tick,
    far from ``flush_max_age_s``."""
    eng, mix, drive, clock = rig.eng, rig.mix, rig.drive, rig.clock
    drive.settle()
    pool = traffic.make_pool(mix, seed=2904)
    fwd0, _ = rig.counters()
    m = get_metrics()
    d0 = _count(m.device_step_seconds)
    faults.configure("transfer:hang@1")
    per = 512
    drive.stage(pool[:per].copy())
    clock.advance(0.06)
    wait_until(lambda: eng._busy_count() == 1, "the first dispatch hangs")
    # A flush for the dispatch thread to hold: old enough to leave its
    # worker by age.
    drive.stage(pool[per:2 * per].copy())
    clock.advance(eng.cfg.flush_max_age_s)
    wait_until(lambda: eng._held_flushes == 1, "a flush is held")
    # A partial quantum for a worker to hold: past the interval only.
    drive.stage(pool[2 * per:3 * per].copy())
    clock.advance(eng.cfg.flush_interval_s + 0.01)
    holders = [w for w in eng._feed_pool.workers if w.pending_events()]
    assert len(holders) == 1
    w0, t0 = wakeups("worker"), clock()
    wait_until(lambda: wakeups("worker") > w0, "the clock wakes them")
    assert holders[0].pending_events() == per
    assert eng._held_flushes == 1 and eng._busy_count() == 1
    assert _count(m.device_step_seconds) == d0
    faults.release_hangs()
    wait_until(lambda: eng._events_in >= drive.offered
               and eng._busy_count() == 0 and eng._held_flushes == 0,
               "the completion wakes the holders")
    assert clock() == t0  # no tick did it
    want = reference.Counts(mix.n_endpoints).add(pool[:3 * per])
    fwd1, _ = rig.counters()
    assert np.array_equal(fwd1 - fwd0, want.fwd)
    assert eng.overload.stats()["transitions"] == 0


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
    likes: the age bound is set out of the way so that the pile is
    the workers' staging, which fills a block at a time), and the arc
    NOMINAL -> SAMPLING -> SHEDDING -> DEGRADED is walked, then walked
    back once the device returns."""
    # The controller's cadence is made finer than the steps this test
    # takes and the staging smaller; no threshold is touched.
    r = Rig(str(tmp_path), overload_tick_s=0.01, feed_staging_blocks=64,
            flush_max_age_s=3600.0)
    try:
        eng, clock, drive = r.eng, r.clock, r.drive
        pool = traffic.make_pool(r.mix, seed=2903)
        faults.configure("transfer:hang@1")
        drive.stage(pool[:512].copy())
        clock.advance(0.06)
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
        # The device returns: the pile drains, one level down a dwell.
        faults.release_hangs()
        faults.clear()
        wait_until(lambda: eng._busy_count() == 0
                   and eng._events_in >= drive.offered, "the pile drains")
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
