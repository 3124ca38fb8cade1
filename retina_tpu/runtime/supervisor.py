"""Supervision tree for the agent's long-lived threads.

Three cooperating pieces, kept deliberately dependency-light so the
engine can use them standalone (tests construct a SketchEngine without
a ControllerManager):

  Heartbeat      — a per-thread liveness cell. The owning thread calls
                   ``beat()`` each loop iteration and ``park()`` right
                   before an intentional blocking wait (queue.get,
                   Event.wait, a device fence) so the watchdog does not
                   mistake idleness for a stall.
  Supervisor     — the registry + watchdog scan thread. A heartbeat
                   whose age exceeds its deadline while not parked is a
                   stall: logged, counted in ``watchdog_stalls`` and
                   escalated through the heartbeat's ``on_stall``
                   callback (e.g. the engine replaces a hung harvest
                   thread). Escalation re-fires once per deadline while
                   the stall persists and re-arms on the next beat.
  RestartPolicy  — exponential backoff + jitter with a crash-loop
                   circuit breaker (closed → open after
                   ``max_failures`` consecutive crashes → half_open
                   probe after ``half_open_after_s`` → closed again
                   once a probe run stays healthy for ``window_s``).

``Supervisor.spawn`` ties them together into a supervised thread: the
target is restarted under the policy until it returns cleanly, the
stop event fires, or the circuit gives up to half-open probing.

Since PR 37 the scan also records every stall of seconds, far under the
deadline, as a ``stall`` span of the flight recorder with its cause
(``docs/observability.md``, "Stalls"). It times itself: a scan that
wakes ``WAKE_LATE_STALL_S`` or more after it was due is a *process*
stall, ``paused`` where the process burnt hardly any CPU meanwhile
(nobody ran: the node took the process away) and ``held`` where it did
(somebody ran while no Python thread could wake: the CPU account is
asked for one sample and names the thread). A scan on time that finds
a cell mid-work and silent for ``THREAD_SILENT_S`` opens a *thread*
stall and closes it, as one span, when the cell beats or parks again,
at the thread's own clock reads. Nothing of this escalates: the
deadline, ``on_stall`` and ``watchdog_stalls`` are as they were. It
adds no thread and no wake-up; a scan costs one ``os.times()`` more.
The cells read ``time.perf_counter``, the recorder's clock (on Linux
the same clock as ``time.monotonic``).
"""

from __future__ import annotations

import collections
import logging
import math
import os
import random
import threading
import time
import zlib
from typing import Any, Callable, Dict, Optional

from retina_tpu.log import logger
from retina_tpu.utils import metric_names as mn

_log = logger("supervisor")


# A scan that wakes this much after it was due is a process stall (half
# the default interval); a cell mid-work and silent for longer than
# THREAD_SILENT_S is a thread stall. A process stall is ``paused``
# where the process burnt under PAUSED_CPU_SHARE of the gap in CPU.
WAKE_LATE_STALL_S = 0.25
THREAD_SILENT_S = 1.0
PAUSED_CPU_SHARE = 0.1
STALLS_KEPT = 32  # the last stall records, for /debug/vars
STRADDLE_SHARE = 0.9  # of a stall a span is open for, to straddle it
# Weight of one on-time scan in the process's usual CPU rate, which is
# taken off the sleep before a late scan's gap.
_CPU_RATE_ALPHA = 0.1
NEVER = math.inf  # the deadline of a cell that is observed, not escalated


class Heartbeat:
    """Liveness cell for one long-lived thread.

    ``beat()`` is wait-free for the owner (a clock store);
    the watchdog reads it from its own thread. ``park()`` marks the
    thread as intentionally blocked so idle waits never count as
    stalls — only work that *started* (a beat after the last park) and
    then stopped making progress does. A thread that has just read the
    clock hands that reading in (``now``); ``what`` says what the work
    is (the device proxy: its call's kind) and ``span`` the recorder
    span it runs inside, for the stall record of a thread stuck there.
    """

    __slots__ = ("name", "deadline_s", "on_stall", "_last", "_parked",
                 "_stalled_since", "_last_escalation", "stalls",
                 "_clock", "what", "span", "_watched", "_resumed")

    def __init__(self, name: str, deadline_s: float = 30.0,
                 on_stall: Optional[Callable[[], None]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 parked: bool = False):
        self.name = name
        self.deadline_s = float(deadline_s)
        self.on_stall = on_stall
        self._clock = clock
        self._last = clock()
        self._parked = parked
        self._stalled_since: Optional[float] = None
        self._last_escalation = 0.0
        self.stalls = 0
        self.what: Optional[str] = None
        self.span = 0
        # The scan has an open stall record on this cell: the owner's
        # next beat or park is its end, and says when that was.
        self._watched = False
        self._resumed: Optional[float] = None

    def beat(self, now: Optional[float] = None,
             what: Optional[str] = None) -> None:
        if now is None:
            now = self._clock()
        if self._watched:
            self._watched = False
            self._resumed = now
        self.what = what
        self.span = 0
        self._last = now
        self._parked = False
        self._stalled_since = None

    def park(self, now: Optional[float] = None) -> None:
        """Declare an intentional blocking wait (queue.get / Event.wait
        / device fence). The watchdog skips parked heartbeats."""
        if now is None:
            now = self._clock()
        if self._watched:
            self._watched = False
            self._resumed = now
        self._last = now
        self._parked = True

    @property
    def parked(self) -> bool:
        return self._parked

    def age(self, now: Optional[float] = None) -> float:
        return (self._clock() if now is None else now) - self._last

    def label(self) -> str:
        """The cell's name, with what its thread says it is in."""
        what = self.what
        return f"{self.name}:{what}" if what else self.name

    def stats(self) -> dict:
        return {
            "age_s": round(self.age(), 3),
            # JSON has no infinity: a cell that never escalates says so.
            "deadline_s": self.deadline_s
            if self.deadline_s != NEVER else None,
            "parked": self._parked,
            "stalled": self._stalled_since is not None,
            "stalls": self.stalls,
        }


class RestartPolicy:
    """Exponential backoff + crash-loop circuit breaker.

    States: ``closed`` (normal; crashes get a backoff delay),
    ``open`` (``max_failures`` consecutive crashes — the caller should
    stop hammering and surface unhealthy), ``half_open`` (one probe
    run allowed; a crash re-opens, staying healthy for ``window_s``
    closes). A run that lives longer than ``window_s`` resets the
    consecutive-failure count, so sporadic crashes spread over time
    never open the circuit.
    """

    def __init__(self, base_s: float = 0.2, max_s: float = 30.0,
                 jitter: float = 0.2, max_failures: int = 5,
                 window_s: float = 60.0, half_open_after_s: float = 30.0,
                 seed: Optional[int] = None):
        self.base_s = float(base_s)
        self.max_s = float(max_s)
        self.jitter = float(jitter)
        self.max_failures = int(max_failures)
        self.window_s = float(window_s)
        self.half_open_after_s = float(half_open_after_s)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._state = "closed"
        self._consecutive = 0
        self._started: Optional[float] = None
        self.restarts = 0  # total crashes recorded over the lifetime

    # -- state ---------------------------------------------------------
    @property
    def state(self) -> str:
        with self._lock:
            self._maybe_close_locked(time.monotonic())
            return self._state

    def _maybe_close_locked(self, now: float) -> None:
        # A half-open probe that has stayed up past the healthy window
        # closes the circuit; same window resets closed-state streaks.
        if self._started is None:
            return
        if now - self._started >= self.window_s:
            self._consecutive = 0
            if self._state == "half_open":
                self._state = "closed"

    def note_start(self) -> None:
        """Record that a supervised run (or probe) just started."""
        with self._lock:
            self._started = time.monotonic()

    def record_failure(self) -> Optional[float]:
        """Record a crash. Returns the backoff delay to wait before the
        next attempt, or ``None`` when the circuit just opened (caller
        should go unhealthy and fall back to half-open probing)."""
        now = time.monotonic()
        with self._lock:
            self._maybe_close_locked(now)
            self.restarts += 1
            self._started = None
            if self._state == "half_open":
                self._state = "open"
                return None
            self._consecutive += 1
            if self._consecutive >= self.max_failures:
                self._state = "open"
                return None
            d = min(self.base_s * (2.0 ** (self._consecutive - 1)),
                    self.max_s)
            return d * (1.0 + self.jitter * self._rng.random())

    def wait_half_open(self, stop: threading.Event) -> bool:
        """Block (stop-interruptibly) until the half-open probe window,
        then transition open → half_open. False if stop fired."""
        if stop.wait(self.half_open_after_s):
            return False
        with self._lock:
            if self._state == "open":
                self._state = "half_open"
        return True

    def reset(self) -> None:
        with self._lock:
            self._state = "closed"
            self._consecutive = 0
            self._started = None

    def stats(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "consecutive_failures": self._consecutive,
                "restarts": self.restarts,
            }


def policy_from_config(cfg, seed_key: str = "") -> RestartPolicy:
    """Build a RestartPolicy from the agent Config knobs. ``seed_key``
    derives a stable per-thread jitter seed so backoff schedules are
    reproducible across runs (and decorrelated across threads)."""
    seed = zlib.crc32(seed_key.encode()) if seed_key else None
    return RestartPolicy(
        base_s=cfg.restart_backoff_base_s,
        max_s=cfg.restart_backoff_max_s,
        jitter=cfg.restart_backoff_jitter,
        max_failures=cfg.restart_max_failures,
        window_s=cfg.restart_window_s,
        half_open_after_s=cfg.circuit_half_open_s,
        seed=seed,
    )


class Supervisor:
    """Heartbeat registry + watchdog.

    Threads register once (idempotent by name — a replacement thread
    re-registering under the same name takes over the cell) and beat;
    the watchdog scans every ``interval_s`` and escalates stalls. The
    watchdog itself is crash-proof: a throwing ``on_stall`` callback is
    contained and counted, never kills the scan loop.

    The same scan records stalls of seconds as ``stall`` spans (module
    docstring). ``clock`` and ``cpu_times`` (``os.times``: user and
    system CPU seconds of the process come first) are injected by
    tests; ``cpu_account`` (``obs/cpuaccount.CpuAccount``) is asked
    who ran, once a ``held`` stall and never otherwise.
    """

    def __init__(self, deadline_s: float = 30.0, interval_s: float = 0.5,
                 clock: Callable[[], float] = time.perf_counter,
                 cpu_times: Callable[[], Any] = os.times,
                 cpu_account: Any = None):
        self.deadline_s = float(deadline_s)
        self.interval_s = float(interval_s)
        self.cpu_account = cpu_account
        self._clock = clock
        self._cpu_times = cpu_times
        self._lock = threading.Lock()
        self._beats: Dict[str, Heartbeat] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # The scan's own timing: when it went to sleep (None: this scan
        # was not called from the watch loop), the process's CPU and the
        # clock at the reading before, and the process's usual CPU rate
        # (user, system: seconds a second over on-time scans).
        # One thread scans: the watch loop, or a test that started none.
        self._slept_at: Optional[float] = None
        self._cpu_prev: Optional[tuple] = None  # noqa: RT200 — one scanner, see above
        self._cpu_rate = (0.0, 0.0)  # noqa: RT200 — one scanner
        self._process_stall_end = -math.inf  # noqa: RT200 — one scanner
        self._open: Dict[str, dict] = {}  # thread stalls in progress
        self._stalls: collections.deque = collections.deque(
            maxlen=STALLS_KEPT)

    # -- registry ------------------------------------------------------
    def register(self, name: str, deadline_s: Optional[float] = None,
                 on_stall: Optional[Callable[[], None]] = None,
                 parked: bool = False) -> Heartbeat:
        """``parked``: the cell is made ahead of its thread's first
        beat (by a constructor), and is idle until then."""
        hb = Heartbeat(name, deadline_s or self.deadline_s, on_stall,
                       self._clock, parked)
        return self.adopt(hb)

    def adopt(self, hb: Heartbeat) -> Heartbeat:
        """Scan a cell that somebody else made (the device proxy's
        threads outlive any one supervisor)."""
        with self._lock:
            old = self._beats.get(hb.name)
            if old is not None and old is not hb:
                hb.stalls = old.stalls  # cumulative across replacements
            self._beats[hb.name] = hb
        return hb

    def deregister(self, name: str) -> None:
        with self._lock:
            self._beats.pop(name, None)

    def heartbeat(self, name: str) -> Optional[Heartbeat]:
        with self._lock:
            return self._beats.get(name)

    # -- watchdog ------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._watch, name="watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(1.0, 2 * self.interval_s))
        self._thread = None

    def _watch(self) -> None:
        self._to_sleep()
        while not self._stop.wait(self.interval_s):
            try:
                self.scan_once()
            except Exception:
                _log.exception("watchdog scan failed")
            self._to_sleep()

    def _to_sleep(self) -> None:
        """(watch loop) The scan is about to wait one interval: the
        next one is due ``interval_s`` from this reading."""
        self._slept_at = self._clock()
        if self._cpu_prev is None:
            t = self._cpu_times()
            self._cpu_prev = (self._slept_at, t[0], t[1])

    def scan_once(self, now: Optional[float] = None) -> list:
        """One watchdog pass; returns the names escalated this pass
        (exposed for deterministic tests)."""
        now = self._clock() if now is None else now
        with self._lock:
            beats = list(self._beats.values())
        try:
            late = self._note_wake(now, beats)
            self._thread_stalls(now, beats, late < WAKE_LATE_STALL_S)
        except Exception:
            # The record is an observer: it never costs an escalation.
            _log.exception("watchdog: stall record failed")
        escalated = []
        for hb in beats:
            if hb.parked or hb.age(now) <= hb.deadline_s:
                continue
            # Escalate at most once per deadline while the stall lasts.
            if now - hb._last_escalation < hb.deadline_s:
                continue
            hb._last_escalation = now
            if hb._stalled_since is None:
                hb._stalled_since = now
            hb.stalls += 1
            escalated.append(hb.name)
            _log.error(
                "watchdog: thread %s stalled (no beat for %.1fs, "
                "deadline %.1fs)", hb.name, hb.age(now), hb.deadline_s,
            )
            self._count_stall(hb.name)
            if hb.on_stall is not None:
                try:
                    hb.on_stall()
                except Exception:
                    _log.exception(
                        "watchdog: on_stall for %s failed", hb.name
                    )
        return escalated

    # -- stalls of seconds ----------------------------------------------
    def _note_wake(self, now: float, beats: list) -> float:
        """Book how late this scan woke; a scan WAKE_LATE_STALL_S late
        is a process stall, classified by the CPU the process burnt
        meanwhile. Returns the lateness in seconds (0.0 for a scan the
        watch loop did not time)."""
        slept_at, self._slept_at = self._slept_at, None
        if slept_at is None:
            return 0.0
        from retina_tpu.metrics import get_metrics

        due = slept_at + self.interval_s
        late = max(0.0, now - due)
        m = get_metrics()
        m.watchdog_scans.inc()
        m.wake_late_seconds.inc(late)
        t = self._cpu_times()
        prev_at, prev_user, prev_sys = self._cpu_prev
        self._cpu_prev = (now, t[0], t[1])
        d_user, d_sys = t[0] - prev_user, t[1] - prev_sys
        rate_user, rate_sys = self._cpu_rate
        if late < WAKE_LATE_STALL_S:
            dt = now - prev_at
            if dt > 0:
                a = _CPU_RATE_ALPHA
                self._cpu_rate = (
                    rate_user + a * (d_user / dt - rate_user),
                    rate_sys + a * (d_sys / dt - rate_sys))
            return late
        # The reading before was taken as the scan went to sleep: what
        # the process usually burns in that sleep is not the gap's.
        usual_s = max(0.0, due - prev_at)
        cpu_user = max(0.0, d_user - rate_user * usual_s)
        cpu_sys = max(0.0, d_sys - rate_sys * usual_s)
        paused = cpu_user + cpu_sys < PAUSED_CPU_SHARE * late
        args: Dict[str, Any] = {
            "cpu_user_s": round(cpu_user, 4),
            "cpu_sys_s": round(cpu_sys, 4),
            "unparked": sorted(hb.label() for hb in beats
                               if not hb._parked and hb._last < due),
        }
        if not paused and self.cpu_account is not None:
            try:
                args.update(self.cpu_account.sample_top())
            except Exception:
                _log.exception("stall: the CPU account gave no sample")
        self._process_stall_end = now
        self._record(mn.STALL_PAUSED if paused else mn.STALL_HELD,
                     due, now, args)
        return late

    def _thread_stalls(self, now: float, beats: list,
                       on_time: bool) -> None:
        """Close the thread stalls whose cell has beaten or parked
        again (or is gone), at the cell's own reading; on a scan that
        woke on time, open one for every cell mid-work and silent for
        THREAD_SILENT_S (counted from the end of a process stall, where
        one came between: nobody could beat during it)."""
        if self._open:
            live = {hb.name: hb for hb in beats}
            for name, st in list(self._open.items()):
                hb = st["hb"]
                end = hb._resumed
                if end is None and hb._last != st["t0"]:
                    end = hb._last  # it moved on as the record was opened
                if end is None and live.get(name) is not hb:
                    end = now  # deregistered, or a new thread's cell
                if end is None:
                    continue
                hb._watched = False
                del self._open[name]
                self._record(mn.STALL_THREAD, st["t0"], end, st["args"],
                             parent=st["args"].get("in_span", 0))
        if not on_time:
            return
        for hb in beats:
            if hb._parked or hb.name in self._open:
                continue
            t0 = hb._last
            if now - max(t0, self._process_stall_end) <= THREAD_SILENT_S:
                continue
            args: Dict[str, Any] = {"thread": hb.name}
            if hb.what:
                args["kind"] = hb.what
            if hb.span:
                args["in_span"] = hb.span
            hb._resumed = None
            hb._watched = True
            self._open[hb.name] = {"hb": hb, "t0": t0, "args": args}

    def _record(self, cause: str, t0: float, t1: float,
                args: Dict[str, Any], parent: int = 0) -> None:
        """One stall, now that both its ends are known: the span, the
        record for /debug/vars, the counter, the log line, and an
        instant annotation for a profile taken across it."""
        from retina_tpu.metrics import get_metrics
        from retina_tpu.obs.recorder import annotate, get_recorder

        gap = t1 - t0
        args = {"cause": cause, "gap_s": round(gap, 4), **args}
        sid = get_recorder().post_hoc(mn.STAGE_STALL, t0, t1,
                                      parent=parent, **args)
        self._stalls.append({"id": sid, "t0": t0, "t1": t1, **args})
        get_metrics().stall_seconds.labels(cause=cause).inc(gap)
        # A compile on the proxy (boot, the grid's warm) is a stall of
        # minutes by this measure: true, and nothing to wake anybody.
        quiet = args.get("kind") == mn.KIND_OTHER
        _log.log(
            logging.INFO if quiet else logging.WARNING, "stall %s",
            " ".join(f"{k}={v}" for k, v in args.items()),
        )
        with annotate("stall", cause=cause, gap_s=args["gap_s"]):
            pass

    @staticmethod
    def _count_stall(name: str) -> None:
        # Late import keeps bare unit tests from paying the exporter
        # registry cost until a stall actually happens.
        from retina_tpu.metrics import get_metrics

        get_metrics().watchdog_stalls.labels(thread=name).inc()

    # -- supervised threads -------------------------------------------
    def spawn(self, name: str, target: Callable[[], None],
              stop: threading.Event,
              policy: Optional[RestartPolicy] = None) -> threading.Thread:
        """Run ``target`` on a named daemon thread, restarting it under
        ``policy`` when it raises. A clean return ends supervision; an
        open circuit falls back to half-open probing until stop."""
        pol = policy or RestartPolicy()

        def _runner() -> None:
            while not stop.is_set():
                pol.note_start()
                try:
                    target()
                    return
                except Exception:
                    if stop.is_set():
                        return
                    delay = pol.record_failure()
                    if delay is None:
                        _log.exception(
                            "supervised thread %s crash-looping; circuit "
                            "OPEN (half-open probe in %.0fs)",
                            name, pol.half_open_after_s,
                        )
                        if not pol.wait_half_open(stop):
                            return
                        continue
                    _log.exception(
                        "supervised thread %s crashed; restart in %.2fs",
                        name, delay,
                    )
                    self._count_restart(name)
                    if stop.wait(delay):
                        return

        t = threading.Thread(target=_runner, name=name, daemon=True)
        t.start()
        return t

    @staticmethod
    def _count_restart(name: str) -> None:
        from retina_tpu.metrics import get_metrics

        get_metrics().thread_restarts.labels(thread=name).inc()

    # -- introspection -------------------------------------------------
    def stats(self) -> dict:
        """Every cell by its thread's name, and ``stalls``: the last
        STALLS_KEPT stall records (:meth:`stalls`)."""
        with self._lock:
            out: dict = {name: hb.stats()
                         for name, hb in self._beats.items()}
        out["stalls"] = self.stalls()
        return out

    def stalls(self) -> list:
        """The last stall records, oldest first, each with the closed
        spans of the recorder's rings that straddle it: open for
        STRADDLE_SHARE of it or more (what every thread was in the
        middle of; a thread's own stall begins at its beat, a hair
        before the span of the call it is stuck in opens). Computed
        here, when somebody reads, never at detection."""
        kept = list(self._stalls)
        if not kept:
            return []
        from retina_tpu.obs.recorder import get_recorder

        spans = get_recorder().spans()
        return [{**st, "straddling": [
            {"stage": s["stage"], "thread": s["thread"], "id": s["id"],
             "began_before_s": round(st["t0"] - s["t0"], 4),
             "ended_after_s": round(s["t1"] - st["t1"], 4),
             "args": s["args"]}
            for s in spans if s["id"] != st["id"]
            and min(s["t1"], st["t1"]) - max(s["t0"], st["t0"])
            >= STRADDLE_SHARE * (st["t1"] - st["t0"])
        ]} for st in kept]

    def summary(self) -> dict:
        with self._lock:
            beats = list(self._beats.values())
        return {
            "threads": len(beats),
            "stalled": sum(
                1 for hb in beats if hb._stalled_since is not None
            ),
            "stalls_total": sum(hb.stalls for hb in beats),
        }
