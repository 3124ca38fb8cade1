"""An injected clock and a driver for engine tests that hand blocks over
by it: no wall-clock sleep decides an outcome.

The engine reads flush ages, window ticks and every duration the
overload controller is told on the clock it is given
(``SketchEngine(cfg, clock=...)``). A test that advances that clock by
hand decides how much time the agent has seen, whatever the machine's
load: a slow compile or a starved thread costs the test real seconds and
the agent none. The engine's feed threads sleep to deadlines on that
clock and poll nothing, so ``FakeClock.advance`` wakes them
(``on_advance``: the engine registers its ``wake``). Waiting for a
condition (``wait_until``) is not a sleep: it bounds nothing but the
test's patience.
"""

from __future__ import annotations

import threading
import time

# A device that keeps up finishes what it is given within this much of
# the engine's time: the driver lets its clock run no further past the
# moment it first saw the pipeline busy.
KEEPS_UP_S = 0.15


class FakeClock:
    """Seconds that pass only when the test says so."""

    def __init__(self, start: float = 1000.0):
        self._t = start
        self._lock = threading.Lock()
        self._hooks: list = []

    def __call__(self) -> float:
        return self._t

    def on_advance(self, hook) -> None:
        """Call ``hook()`` after every ``advance``: whoever sleeps to a
        deadline on this clock registers the wake of its sleepers."""
        self._hooks.append(hook)

    def advance(self, dt: float) -> None:
        with self._lock:
            self._t += dt
        for hook in list(self._hooks):
            hook()


class FakeCpu:
    """The process's CPU seconds as ``os.times()`` gives them (user
    and system first), burnt only when the test says so: what a
    ``Supervisor(cpu_times=...)`` classifies a late scan by."""

    def __init__(self) -> None:
        self.user = self.system = 0.0

    def __call__(self) -> tuple:
        return (self.user, self.system, 0.0, 0.0, 0.0)

    def burn(self, user: float = 0.0, system: float = 0.0) -> None:
        self.user += user
        self.system += system


def wakeups(thread: str | None = None, cause: str | None = None) -> float:
    """``tpu_feed_wakeups_counter`` summed over the labels not given:
    returns from a wait of the feed path so far in this test."""
    from retina_tpu.metrics import get_metrics

    return sum(
        s.value for mf in get_metrics().feed_wakeups.collect()
        for s in mf.samples if s.name.endswith("_total")
        and thread in (None, s.labels["thread"])
        and cause in (None, s.labels["cause"]))


def wait_until(pred, what: str, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            raise AssertionError(f"never happened: {what}")
        time.sleep(0.001)


class Drive:
    """Hands blocks to a started engine and advances its clock the way
    a device that keeps up lets time pass."""

    def __init__(self, eng, clock: FakeClock, write=None):
        self.eng, self.clock = eng, clock
        self.write = write or (
            lambda block: eng.sink.write_records(block, "test"))
        self.offered = 0
        self._busy_since: float | None = None

    def tick(self, dt: float) -> None:
        """Let ``dt`` seconds pass, once the pipeline has followed: it
        is idle, or has been busy (a dispatch in flight, blocks staged
        or flushes held past ``flush_max_age_s`` from when they were
        dealt, or flushes handed off that the dispatch thread has not
        taken yet) for less than KEEPS_UP_S of the clock, and at most
        one closed window awaits its readback. Blocks the workers stage
        and flushes the dispatch thread holds that are not yet due are
        no backlog: they are held by design, for a quantum or a step's
        worth, their age or a reader. Without the third, a dispatch
        thread starved of the machine's CPU reads as idle while the
        workers' hand-off queues fill behind it, and the seconds then
        let pass are a hand-off wait of 1.0 to the controller."""
        eng = self.eng
        age = eng.cfg.flush_max_age_s

        def staged_since(w) -> float | None:
            try:
                return w.staging[0][1]
            except IndexError:
                return None

        def followed() -> bool:
            pool = eng._feed_pool
            handed_off = pool is not None and any(
                w.outq.q for w in pool.workers)
            since = [eng._held_since] + ([
                staged_since(w) for w in pool.workers]
                if pool is not None else [])
            overdue = any(t is not None and self.clock() >= t + age
                          for t in since)
            if not overdue and eng._busy_count() == 0 and not handed_off:
                self._busy_since = None
            elif self._busy_since is None:
                self._busy_since = self.clock()
            return (self._busy_since is None
                    or self.clock() + dt - self._busy_since < KEEPS_UP_S) \
                and eng._harvest_q.unfinished_tasks <= 1

        wait_until(followed, "the pipeline follows the clock")
        self.clock.advance(dt)

    def stage(self, block) -> None:
        """Hand a block over and wait until the feed holds it, so that
        its age starts at the clock's present reading."""
        self.write(block)
        self.offered += len(block)
        pool = self.eng._feed_pool
        if pool is not None:
            wait_until(
                lambda: sum(w.events_in for w in pool.workers)
                >= self.offered, "the feed takes the block")

    def hand_over(self, block, dt: float) -> None:
        self.stage(block)
        self.tick(dt)

    def settle(self, dt: float = 0.05, max_ticks: int = 2000) -> None:
        """Tick until every offered event has been dispatched and the
        device has finished (flushes are due by age, so time must
        pass)."""
        eng = self.eng
        for _ in range(max_ticks):
            if eng._events_in >= self.offered and eng._busy_count() == 0:
                return
            self.tick(dt)
            # The tick woke the feed's sleepers: let them run.
            time.sleep(0.002)
        raise AssertionError(
            f"never settled: {eng._events_in} of {self.offered} events")

    def close_a_window(self) -> None:
        """Pass a window boundary and wait for its close and readback."""
        eng = self.eng
        ws = eng.cfg.window_seconds
        from retina_tpu.metrics import get_metrics

        closed = get_metrics().windows_closed
        n0 = closed._value.get()
        for _ in range(200):
            self.tick(ws / 4)
            time.sleep(0.002)
            if closed._value.get() > n0:
                # The clock stands: no later window closes behind it.
                wait_until(lambda: not eng._harvest_q.unfinished_tasks,
                           "the closed window is read back")
                return
        raise AssertionError("no window closed")


def controller_after(eng, clock: FakeClock, dt: float) -> int:
    """Let ``dt`` (at least a controller tick) pass and return the
    overload controller's state once the feed loop's tick has read the
    signals at the new time and advanced the state machine."""
    ctl = eng.overload
    prev = ctl._sigvals
    clock.advance(dt)
    want = clock()
    # The tick stores a fresh signals dict inside the lock it advances
    # the state under.
    wait_until(lambda: ctl._last_tick >= want - 1e-9
               and ctl._sigvals is not prev, "the controller ticks")
    with ctl._lock:
        return ctl._state
