"""Supervision-tree contract tests: heartbeat/stall detection, backoff
schedule determinism, circuit breaker transitions, supervised spawn
restarts, and the fault-injection grammar — all clock-driven through
``scan_once(now)`` / seeded policies, no sleeps beyond short waits."""

import threading
import time

import pytest

from retina_tpu.config import Config
from retina_tpu.runtime import faults
from retina_tpu.metrics import get_metrics
from retina_tpu.obs.recorder import get_recorder, initialize_recorder
from retina_tpu.runtime.supervisor import (
    NEVER,
    THREAD_SILENT_S,
    WAKE_LATE_STALL_S,
    Heartbeat,
    RestartPolicy,
    Supervisor,
    policy_from_config,
)
from retina_tpu.utils import metric_names as mn
from tests.clockdrive import FakeClock, FakeCpu


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.clear()


# ------------------------------------------------------------ heartbeat
def test_watchdog_detects_stall_and_escalates_once_per_deadline():
    sup = Supervisor(deadline_s=10.0, interval_s=0.1)
    fired = []
    hb = sup.register("worker", on_stall=lambda: fired.append(1))
    t0 = time.monotonic()
    hb.beat()
    # Fresh beat: no stall.
    assert sup.scan_once(now=t0 + 5.0) == []
    # Past the deadline: escalates exactly once...
    assert sup.scan_once(now=t0 + 11.0) == ["worker"]
    assert fired == [1]
    # ...and not again within the same deadline window...
    assert sup.scan_once(now=t0 + 12.0) == []
    # ...but re-fires after another full deadline of silence.
    assert sup.scan_once(now=t0 + 22.0) == ["worker"]
    assert hb.stalls == 2
    # A beat clears the stall state entirely.
    hb.beat()
    assert sup.scan_once(now=time.monotonic() + 5.0) == []
    assert sup.summary()["stalled"] == 0
    assert sup.summary()["stalls_total"] == 2


def test_parked_heartbeat_never_counts_as_stalled():
    sup = Supervisor(deadline_s=1.0)
    hb = sup.register("idle")
    hb.park()  # intentional blocking wait (queue.get etc.)
    assert sup.scan_once(now=time.monotonic() + 3600.0) == []
    assert hb.stalls == 0


def test_register_is_takeover_and_preserves_stall_count():
    sup = Supervisor(deadline_s=1.0)
    hb1 = sup.register("t")
    hb1.stalls = 3
    hb2 = sup.register("t")  # replacement thread takes the cell over
    assert hb2 is not hb1 and hb2.stalls == 3
    assert sup.heartbeat("t") is hb2


def test_on_stall_exception_does_not_kill_the_scan():
    sup = Supervisor(deadline_s=0.5)

    def boom():
        raise RuntimeError("escalation handler bug")

    hb = sup.register("bad", on_stall=boom)
    hb.beat()
    assert sup.scan_once(now=time.monotonic() + 2.0) == ["bad"]


# ------------------------------------------------- stalls of seconds
class FakeAccount:
    """Stands where the CPU account does: counts the samples it is
    asked for and names a thread."""

    def __init__(self):
        self.asked = 0

    def sample_top(self) -> dict:
        self.asked += 1
        return {"top_role": "foreign", "top_role_cpu_s": 2.05,
                "top_thread": "spinner", "top_thread_cpu_s": 2.0}


class Rig:
    """A supervisor on an injected clock and an injected CPU reading,
    scanned the way the watch loop scans it: nothing sleeps."""

    def __init__(self, interval_s: float = 0.5):
        initialize_recorder()
        self.clock, self.cpu = FakeClock(), FakeCpu()
        self.account = FakeAccount()
        self.fired: list = []
        self.sup = Supervisor(
            deadline_s=30.0, interval_s=interval_s, clock=self.clock,
            cpu_times=self.cpu, cpu_account=self.account)
        self.sup._to_sleep()

    def scan(self, late_s: float = 0.0, user: float = 0.0,
             system: float = 0.0) -> list:
        """The interval passes, and ``late_s`` more; the process burns
        what it is given meanwhile; the scan wakes and goes back to
        sleep."""
        self.clock.advance(self.sup.interval_s + late_s)
        self.cpu.burn(user, system)
        escalated = self.sup.scan_once()
        self.sup._to_sleep()
        return escalated

    def stalls(self) -> list:
        return [s for s in get_recorder().spans()
                if s["stage"] == mn.STAGE_STALL]

    @staticmethod
    def counter(c, **labels) -> float:
        return (c.labels(**labels) if labels else c)._value.get()


def test_a_scan_that_wakes_late_with_no_cpu_burnt_is_a_paused_stall():
    rig = Rig()
    busy = rig.sup.register("engine-dispatch")
    idle = rig.sup.register("window-harvest")
    proxy = rig.sup.adopt(Heartbeat("device-proxy", NEVER,
                                    clock=rig.clock, parked=True))
    for _ in range(3):
        busy.beat(), idle.park()
        assert rig.scan() == []
    proxy.beat(what=mn.KIND_STEP)
    busy.beat()
    slept = rig.clock()
    rig.scan(late_s=2.3, user=0.01)
    (st,) = rig.stalls()
    assert st["t0"] == pytest.approx(slept + 0.5)
    assert st["t1"] == pytest.approx(slept + 2.8)
    a = st["args"]
    assert a["cause"] == mn.STALL_PAUSED
    assert a["gap_s"] == pytest.approx(2.3)
    assert a["cpu_user_s"] == pytest.approx(0.01) and a["cpu_sys_s"] == 0.0
    assert a["unparked"] == ["device-proxy:step", "engine-dispatch"]
    assert "top_thread" not in a and rig.account.asked == 0
    m = get_metrics()
    assert rig.counter(m.stall_seconds, cause=mn.STALL_PAUSED) == \
        pytest.approx(2.3)
    assert rig.counter(m.watchdog_scans) == 4
    assert rig.counter(m.wake_late_seconds) == pytest.approx(2.3)
    # Nothing escalates under the deadline, whatever the scan records.
    assert rig.counter(m.watchdog_stalls, thread="engine-dispatch") == 0
    # /debug/vars: the record, beside the cells.
    stats = rig.sup.stats()
    assert stats["engine-dispatch"]["parked"] is False
    assert stats["device-proxy"]["deadline_s"] is None
    (rec,) = stats["stalls"]
    assert rec["cause"] == mn.STALL_PAUSED and rec["id"] == st["id"]
    assert rec["straddling"] == []


def test_a_late_scan_while_somebody_ran_is_held_and_asks_the_account_once():
    rig = Rig()
    rig.scan()
    rig.scan(late_s=2.3, user=2.0, system=0.1)
    (st,) = rig.stalls()
    a = st["args"]
    assert a["cause"] == mn.STALL_HELD and a["gap_s"] == pytest.approx(2.3)
    assert a["cpu_user_s"] == pytest.approx(2.0)
    assert a["cpu_sys_s"] == pytest.approx(0.1)
    assert (a["top_role"], a["top_thread"]) == ("foreign", "spinner")
    assert a["top_thread_cpu_s"] == 2.0
    assert rig.account.asked == 1
    rig.scan()
    assert rig.account.asked == 1 and len(rig.stalls()) == 1
    assert rig.counter(get_metrics().stall_seconds,
                       cause=mn.STALL_HELD) == pytest.approx(2.3)


def test_the_cpu_of_the_sleep_before_the_gap_is_not_the_gaps():
    """The reading before a late scan was taken as it went to sleep:
    a process that burns half a core all the time has burnt a quarter
    of a second before the pause began, and that is not the pause's."""
    rig = Rig()
    for _ in range(60):
        rig.scan(user=0.2, system=0.05)
    rig.scan(late_s=2.0, user=0.2, system=0.05)
    (st,) = rig.stalls()
    assert st["args"]["cause"] == mn.STALL_PAUSED
    assert st["args"]["cpu_user_s"] < 0.01


def test_lateness_under_the_threshold_is_counted_and_is_no_stall():
    rig = Rig()
    assert 0.2 < WAKE_LATE_STALL_S
    rig.scan(late_s=0.2, user=0.3)
    rig.scan()
    assert rig.stalls() == [] and rig.sup.stats()["stalls"] == []
    m = get_metrics()
    assert rig.counter(m.wake_late_seconds) == pytest.approx(0.2)
    assert rig.counter(m.watchdog_scans) == 2


def test_a_thread_mid_work_and_silent_is_one_span_with_its_own_ends():
    rig = Rig()
    hb = rig.sup.register("window-harvest")
    parked = rig.sup.register("engine-feed")
    parked.park()
    rig.scan()
    rig.clock.advance(0.1)
    began = rig.clock()
    hb.beat()
    for _ in range(10):  # ten scans find it silent: one record
        rig.scan()
    assert rig.stalls() == [] and list(rig.sup._open) == ["window-harvest"]
    rig.clock.advance(0.07)
    ended = rig.clock()
    hb.park()
    rig.scan(), rig.scan()  # the scans after see it parked
    (st,) = rig.stalls()
    assert (st["t0"], st["t1"]) == (began, ended)
    assert st["args"] == {"cause": mn.STALL_THREAD, "thread":
                          "window-harvest",
                          "gap_s": pytest.approx(ended - began)}
    assert ended - began > 10 * 0.5
    assert rig.counter(get_metrics().stall_seconds,
                       cause=mn.STALL_THREAD) == pytest.approx(ended - began)
    assert rig.sup._open == {}


def test_a_thread_silent_for_a_second_and_a_half_is_found_and_a_parked_one_never():
    rig = Rig()
    hb = rig.sup.register("checkpointer")
    idle = rig.sup.register("engine-feed")
    idle.park()
    hb.beat()
    t0 = rig.clock()
    rig.scan(), rig.scan()  # silent for 1.0 s: not yet over it
    assert rig.sup._open == {}
    rig.scan()
    assert list(rig.sup._open) == ["checkpointer"]
    rig.clock.advance(0.1)
    hb.beat()  # 1.6 s after the beat before
    rig.scan()
    (st,) = rig.stalls()
    assert st["args"]["thread"] == "checkpointer"
    assert (st["t0"], st["t1"]) == (t0, pytest.approx(t0 + 1.6))
    assert THREAD_SILENT_S < 1.6


def test_a_process_stall_is_not_told_again_as_every_threads():
    """After a pause every cell mid-work looks silent for its length:
    a thread stall counts from the pause's end."""
    rig = Rig()
    hb = rig.sup.register("engine-dispatch")
    hb.beat()
    rig.scan(late_s=3.0)
    rig.scan()
    assert [s["args"]["cause"] for s in rig.stalls()] == [mn.STALL_PAUSED]
    assert rig.sup._open == {}
    rig.scan(), rig.scan()  # still silent a second after it: its own
    assert list(rig.sup._open) == ["engine-dispatch"]


def test_a_cell_that_goes_away_mid_stall_closes_its_record():
    rig = Rig()
    rig.sup.register("feed-worker-0").beat()
    rig.scan(), rig.scan(), rig.scan()
    assert list(rig.sup._open) == ["feed-worker-0"]
    rig.sup.register("feed-worker-0").park()  # a new thread's cell
    rig.scan()
    (st,) = rig.stalls()
    assert st["args"]["thread"] == "feed-worker-0"
    assert st["t1"] == rig.clock() and rig.sup._open == {}


def test_the_deadline_and_its_escalation_are_as_they_were():
    rig = Rig()
    hb = rig.sup.register("worker", on_stall=lambda: rig.fired.append(1))
    watched = rig.sup.adopt(Heartbeat("device-proxy", NEVER,
                                      clock=rig.clock))
    hb.beat(), watched.beat(what=mn.KIND_OTHER)
    m = get_metrics()
    for _ in range(59):  # 29.5 s: recorded as a stall, not escalated
        assert rig.scan() == []
    assert rig.fired == [] and hb.stalls == 0
    assert rig.counter(m.watchdog_stalls, thread="worker") == 0
    assert sorted(rig.sup._open) == ["device-proxy", "worker"]
    rig.scan()
    assert rig.scan() == ["worker"]  # past 30 s, once
    assert rig.fired == [1] and hb.stalls == 1
    assert rig.counter(m.watchdog_stalls, thread="worker") == 1
    for _ in range(30):
        assert rig.scan() == []
    # A cell that is observed only never escalates, however long.
    assert watched.stalls == 0
    assert rig.counter(m.watchdog_stalls, thread="device-proxy") == 0
    hb.beat()
    rig.scan()
    assert rig.sup.summary()["stalled"] == 0


def test_a_stall_record_names_the_spans_that_straddle_it():
    rig = Rig()
    rec = get_recorder()
    rig.scan()
    t = rig.clock()
    rig.scan(late_s=2.3)
    # Written after the hole, as a span that was open across it is.
    rec._commit(mn.STAGE_PROXY_RUN, t + 0.4, t + 2.9, 7, next(rec._ids),
                0, {"kind": mn.KIND_STEP})
    rec._commit(mn.STAGE_RENDER, t + 0.6, t + 0.7, 7, next(rec._ids), 0,
                None)
    # Open for nine tenths of it straddles it too: a thread's own stall
    # begins at its beat, a hair before the span of its call opens.
    rec._commit(mn.STAGE_TRANSFER_ENQUEUE, t + 0.5001, t + 2.85, 7,
                next(rec._ids), 0, {"bucket": 4096, "first": True})
    rec._commit(mn.STAGE_HARVEST, t + 1.9, t + 3.0, 7, next(rec._ids), 0,
                None)  # the hole's second half only
    (st,) = rig.sup.stats()["stalls"]
    hair, inside = st["straddling"]  # by their ends
    assert inside["stage"] == mn.STAGE_PROXY_RUN
    assert inside["args"] == {"kind": mn.KIND_STEP}
    assert inside["began_before_s"] == pytest.approx(0.1)
    assert inside["ended_after_s"] == pytest.approx(0.1)
    assert hair["stage"] == mn.STAGE_TRANSFER_ENQUEUE
    assert hair["args"]["first"] is True
    assert hair["began_before_s"] == pytest.approx(-0.0001)


def test_the_stall_line_is_logged_and_a_compile_on_the_proxy_quietly(caplog):
    import logging

    rig = Rig()
    proxy = rig.sup.adopt(Heartbeat("device-proxy", NEVER,
                                    clock=rig.clock, parked=True))
    log = logging.getLogger("retina.supervisor")  # propagates nowhere
    log.addHandler(caplog.handler)
    try:
        proxy.beat(what=mn.KIND_OTHER)
        for _ in range(4):
            rig.scan()
        proxy.park()
        rig.scan()
        rig.scan(late_s=1.0)
    finally:
        log.removeHandler(caplog.handler)
    lines = [(r.levelno, r.getMessage()) for r in caplog.records
             if r.getMessage().startswith("stall ")]
    assert [lv for lv, _ in lines] == [logging.INFO, logging.WARNING]
    assert "cause=thread" in lines[0][1] and "kind=other" in lines[0][1]
    assert "thread=device-proxy" in lines[0][1]
    assert "cause=paused gap_s=1.0" in lines[1][1]


# --------------------------------------------------------- restart policy
def test_backoff_schedule_is_exponential_and_capped():
    p = RestartPolicy(base_s=0.1, max_s=0.5, jitter=0.0, max_failures=10)
    delays = []
    for _ in range(5):
        p.note_start()
        delays.append(p.record_failure())
    assert delays == [0.1, 0.2, 0.4, 0.5, 0.5]


def test_backoff_jitter_is_seeded_and_reproducible():
    cfg = Config()
    a = policy_from_config(cfg, seed_key="thread-x")
    b = policy_from_config(cfg, seed_key="thread-x")
    for _ in range(3):
        a.note_start(), b.note_start()
        assert a.record_failure() == b.record_failure()


def test_circuit_opens_after_max_consecutive_failures():
    p = RestartPolicy(base_s=0.01, jitter=0.0, max_failures=3)
    p.note_start()
    assert p.record_failure() is not None
    p.note_start()
    assert p.record_failure() is not None
    p.note_start()
    assert p.record_failure() is None  # third consecutive crash: OPEN
    assert p.state == "open"


def test_circuit_half_open_probe_then_reopen_on_crash():
    p = RestartPolicy(base_s=0.01, jitter=0.0, max_failures=1,
                      half_open_after_s=0.05)
    p.note_start()
    assert p.record_failure() is None
    assert p.state == "open"
    stop = threading.Event()
    assert p.wait_half_open(stop) is True
    assert p.state == "half_open"
    # The probe crashes: straight back to open, no delay.
    p.note_start()
    assert p.record_failure() is None
    assert p.state == "open"


def test_circuit_closes_after_healthy_window():
    p = RestartPolicy(base_s=0.01, jitter=0.0, max_failures=1,
                      window_s=0.05, half_open_after_s=0.01)
    p.note_start()
    assert p.record_failure() is None
    assert p.wait_half_open(threading.Event())
    p.note_start()  # probe run starts...
    time.sleep(0.08)  # ...and stays healthy past window_s
    assert p.state == "closed"


def test_long_lived_runs_reset_the_consecutive_count():
    p = RestartPolicy(base_s=0.1, max_s=10.0, jitter=0.0, max_failures=3,
                      window_s=0.0)  # any run counts as long-lived
    for _ in range(10):  # sporadic crashes never open the circuit
        p.note_start()
        assert p.record_failure() == 0.1  # streak resets every time
    assert p.state == "closed"


def test_wait_half_open_interrupted_by_stop():
    p = RestartPolicy(max_failures=1, half_open_after_s=60.0)
    p.note_start()
    p.record_failure()
    stop = threading.Event()
    stop.set()
    assert p.wait_half_open(stop) is False


# ------------------------------------------------------- supervised spawn
def test_spawn_restarts_crashing_target_until_clean_exit():
    sup = Supervisor()
    stop = threading.Event()
    runs = []
    done = threading.Event()

    def flaky():
        runs.append(1)
        if len(runs) < 3:
            raise RuntimeError("transient")
        done.set()

    pol = RestartPolicy(base_s=0.01, jitter=0.0, max_failures=10)
    t = sup.spawn("flaky", flaky, stop, pol)
    assert done.wait(5.0)
    t.join(timeout=2.0)
    assert len(runs) == 3
    from retina_tpu.metrics import get_metrics

    v = get_metrics().thread_restarts.labels(thread="flaky")._value.get()
    assert v == 2


def test_spawn_respects_stop_during_backoff():
    sup = Supervisor()
    stop = threading.Event()

    def crash():
        raise RuntimeError("always")

    pol = RestartPolicy(base_s=30.0, jitter=0.0, max_failures=10)
    t = sup.spawn("crashy", crash, stop, pol)
    time.sleep(0.1)
    stop.set()
    t.join(timeout=2.0)
    assert not t.is_alive()


# ------------------------------------------------------- fault injection
def test_fault_spec_grammar_and_nth_hit():
    faults.configure("transfer:raise@2,checkpoint:corrupt")
    faults.inject("transfer")  # hit 1: pass
    with pytest.raises(faults.InjectedFault):
        faults.inject("transfer")  # hit 2: fire
    faults.inject("transfer")  # later hits pass again (one-shot @N)
    assert faults.should_corrupt("checkpoint")
    assert not faults.should_corrupt("transfer")
    st = faults.stats()
    assert st["armed"] and st["rules"]["transfer"]["fired"] == 1


def test_fault_hang_released_by_clear():
    faults.configure("loop:hang60")
    t0 = time.monotonic()
    done = threading.Event()

    def hanger():
        faults.inject("loop")
        done.set()

    threading.Thread(target=hanger, daemon=True).start()
    time.sleep(0.05)
    faults.clear()  # frees the hung thread immediately
    assert done.wait(5.0)
    assert time.monotonic() - t0 < 10.0


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError):
        faults.configure("transfer;raise")
    with pytest.raises(ValueError):
        faults.configure("transfer:explode")


def test_config_validates_fault_spec_and_deadlines():
    cfg = Config()
    cfg.fault_spec = "transfer:raise@3,plugin.mock:hang2.5"
    cfg.validate()  # well-formed spec passes
    cfg.fault_spec = "not a spec"
    with pytest.raises(ValueError):
        cfg.validate()
    cfg.fault_spec = ""
    cfg.watchdog_deadline_s = 0.0
    with pytest.raises(ValueError):
        cfg.validate()
