"""Whose the host's CPU is: one account of the agent's process,
exhaustive by construction.

Once a period one sampler thread reads ``getrusage(RUSAGE_SELF)`` and,
at the same instant, walks ``/proc/self/task``: every thread of the
process with its CPU seconds so far (``schedstat``'s first field where
the kernel gives it, else ``stat``'s ``utime + stime`` in clock ticks:
gVisor, which the TPU hosts' sandboxes run, has no ``schedstat``). Each
tid is mapped to a Python thread by its native id and to a role by its
name (``metric_names.THREAD_ROLE_PREFIXES``); a tid that is no Python
thread is the runtime's (XLA's, libtpu's and PJRT's pools). The
*increase* of each thread since the last sample goes to its role's
``tpu_thread_cpu_seconds_counter{role}``, the increase of the process
to ``tpu_process_cpu_seconds_counter``: two readings of one quantity,
so the process less the sum of the roles is what the account could not
name (threads that were born and died between two samples).

Threads that live shorter than a period account for themselves:
:func:`book_own_thread` adds the calling thread's whole
``time.thread_time()`` to its role as the thread ends, and the sampler
skips such threads by their name, so nothing is counted twice.

The sampler's own CPU is booked under its own role (``account``): what
the account costs is on the account. On the TPU hosts (gVisor, ≈ 200
threads beside the runtime) an ``open`` + ``read`` of a procfs file
costs 46-92 µs and a ``pread`` of a descriptor kept open 10 µs, so the
sampler keeps one descriptor a thread, and reads a thread that has
hardly ever run (most of the runtime's pools) only every eighth sample:
what it burns meanwhile is booked when it is next read, and nothing is
lost. A thread with a past (a compile's worker, gone quiet) is read
every sample: its next burst booked seconds late would stand against a
process counter that is on time.
"""

from __future__ import annotations

import dataclasses
import os
import resource
import threading
import time
from typing import Any, Callable, Iterable

from retina_tpu.metrics import get_metrics
from retina_tpu.utils import metric_names as mn

TASK_DIR = "/proc/self/task"
# A sample costs ≈ 1.9 ms of CPU on a TPU host (gVisor, 209 threads;
# PERF.md, PR 36): at 2 s the account stays under 1 ms a second.
PERIOD_S = 2.0
SCHEDSTAT, STAT = "schedstat", "stat"
# A thread that has burnt under IDLE_CPU_S in all and whose reading
# stood still for IDLE_AFTER samples is read every IDLE_STRIDE-th sample.
IDLE_CPU_S, IDLE_AFTER, IDLE_STRIDE = 0.05, 4, 8
# Two samples closer than this are one to sample_top(): the second
# adds its increases to the first's.
FRESH_S = 0.25


def process_cpu_s() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def book_own_thread(role: str) -> None:
    """(a thread of ``SELF_ACCOUNTING_PREFIXES``, as it ends) Add the
    calling thread's CPU seconds, all of them, to ``role``."""
    get_metrics().thread_cpu_seconds.labels(role=role).inc(
        time.thread_time()
    )


def _totals(counter) -> dict[tuple, float]:
    """A counter's value by its label values."""
    return {tuple(s.labels.values()): s.value
            for mf in counter.collect() for s in mf.samples
            if s.name.endswith("_total")}


@dataclasses.dataclass(slots=True)
class _Thread:
    """One thread as the sampler last read it."""

    name: str  # the Python thread's; the runtime's by its comm
    role: str
    cpu_s: float
    still: int = 0  # samples in a row its reading has not moved
    burnt: float = 0.0  # its increase at the last sample


class CpuAccount:
    """The sampler and what it saw last (``/debug/vars`` → ``cpu``)."""

    def __init__(
        self,
        task_dir: str = TASK_DIR,
        process_cpu_s: Callable[[], float] = process_cpu_s,
        threads: Callable[[], Iterable[threading.Thread]]
        = threading.enumerate,
    ) -> None:
        self._task_dir = task_dir
        self._process_cpu_s = process_cpu_s
        self._threads = threads
        self._tick_s = 1.0 / os.sysconf("SC_CLK_TCK")
        self.source: str | None = None  # which file gives the seconds
        self._known: dict[int, _Thread] = {}  # by tid
        self._fds: dict[int, int] = {}  # tid -> its file, kept open
        self._process_s = 0.0
        self.samples = 0
        self._added: dict[str, float] = {}  # by role, at the last sample
        self._sampled_at = float("-inf")
        # Its own thread samples once a period; the watchdog's scan
        # asks for one more at a stall (sample_top).
        self._lock = threading.Lock()

    # -- reading /proc ---------------------------------------------------
    def _tids(self) -> list[int]:
        try:
            return [int(t) for t in os.listdir(self._task_dir)]
        except OSError:
            return []

    def _path(self, tid: int, name: str) -> str:
        return os.path.join(self._task_dir, str(tid), name)

    def _forget(self, tid: int) -> None:
        self._known.pop(tid, None)
        fd = self._fds.pop(tid, None)
        if fd is not None:
            os.close(fd)

    def _cpu_s(self, tid: int) -> float | None:
        """The thread's CPU seconds so far; None where it is gone (or
        the tid is another thread's now: the kept file is the dead
        one's and no longer reads)."""
        try:
            fd = self._fds.get(tid)
            if fd is None:
                fd = self._fds[tid] = os.open(
                    self._path(tid, self.source), os.O_RDONLY)
            text = os.pread(fd, 1024, 0).decode("ascii", "replace")
            if self.source == SCHEDSTAT:
                return int(text.split()[0]) / 1e9
            # "pid (comm) state ..." and comm may hold anything: the
            # fields are counted from its closing bracket.
            fields = text[text.rindex(")") + 2:].split()
            return (int(fields[11]) + int(fields[12])) * self._tick_s
        except (OSError, ValueError, IndexError):
            self._forget(tid)
            return None

    def _who(self, tid: int, name: str | None) -> tuple[str, str]:
        """(name, role): a Python thread's by the role table; a thread
        the interpreter does not list is the runtime's, by its comm."""
        if name is not None:
            return name, mn.thread_role(name)
        try:
            with open(self._path(tid, "comm")) as f:
                return f.read().strip(), mn.ROLE_RUNTIME
        except OSError:
            return "?", mn.ROLE_RUNTIME

    # -- one sample ------------------------------------------------------
    def sample(self) -> None:
        with self._lock:
            self._sample()

    def sample_top(self) -> dict[str, Any]:
        """One sample now, of every thread, and who burnt the most
        since the sample before it: the role and the thread (a Python
        thread by its name, the runtime's by ``comm``) with their CPU
        seconds. What the watchdog's scan asks at a ``held`` stall: the
        thread that held the interpreter lock, or the runtime's that
        would not let go. Where the account's own thread sampled under
        FRESH_S ago (it woke from the same hole, and its sample took
        most of the hole with it) the two samples count as one. The
        sample before is up to a period old, so the seconds are over
        the stall and up to PERIOD_S before it."""
        with self._lock:
            # Every thread, the quiet ones too: the one that held the
            # lock may have slept until it did.
            self._sample(every=True)
            role = max(self._added, key=self._added.get, default="")
            top = max(self._known.values(), key=lambda t: t.burnt,
                      default=None)
            return {
                "top_role": role,
                "top_role_cpu_s": round(self._added.get(role, 0.0), 4),
                "top_thread": top.name if top is not None else "",
                "top_thread_cpu_s": round(top.burnt, 4)
                if top is not None else 0.0,
            }

    def _sample(self, every: bool = False) -> None:
        merge = time.perf_counter() - self._sampled_at < FRESH_S
        process_s = self._process_cpu_s()
        tids = self._tids()
        if self.source is None:
            self.source = SCHEDSTAT if any(
                os.path.exists(self._path(t, SCHEDSTAT)) for t in tids
            ) else STAT
        names = {t.native_id: t.name for t in self._threads()}
        added = dict.fromkeys(mn.THREAD_ROLES, 0.0)
        for tid in tids:
            known = self._known.get(tid)
            if (not every and known is not None
                    and known.still >= IDLE_AFTER
                    and known.cpu_s < IDLE_CPU_S
                    and (self.samples + tid) % IDLE_STRIDE):
                if not merge:
                    known.burnt = 0.0
                continue
            name = names.get(tid)
            if name is not None and name.startswith(
                    mn.SELF_ACCOUNTING_PREFIXES):
                continue  # it books itself as it ends
            cpu_s = self._cpu_s(tid)
            if cpu_s is None:
                continue
            if known is None or cpu_s < known.cpu_s:
                # New to the account (a reading that fell is another
                # thread on an old tid): all it has burnt is an increase.
                known = self._known[tid] = _Thread(
                    *self._who(tid, name), 0.0)
            elif name is not None and name != known.name:  # renamed
                known.name, known.role = self._who(tid, name)
            burnt = cpu_s - known.cpu_s
            known.burnt = burnt + known.burnt if merge else burnt
            added[known.role] += burnt
            known.cpu_s = cpu_s
            known.still = 0 if burnt else known.still + 1
        for tid in self._known.keys() - set(tids):
            self._forget(tid)
        m = get_metrics()
        for role, s in added.items():
            m.thread_cpu_seconds.labels(role=role).inc(s)
        m.process_cpu_seconds.inc(process_s - self._process_s)
        self._process_s = process_s
        self._added = {r: s + self._added.get(r, 0.0)
                       for r, s in added.items()} if merge else added
        self._sampled_at = time.perf_counter()
        self.samples += 1

    def close(self) -> None:
        with self._lock:
            for tid in list(self._fds):
                self._forget(tid)

    def run(self, stop: threading.Event) -> None:
        """(the ``cpu-account`` thread) A sample a period until stop."""
        try:
            while not stop.wait(PERIOD_S):
                self.sample()
        finally:
            self.close()

    # -- /debug/vars -----------------------------------------------------
    def stats(self) -> dict[str, Any]:
        """Every thread of the last sample by name (the runtime's by
        ``comm``), tid, role and CPU seconds, and the counters' totals:
        the process, each role, and what no role holds."""
        m = get_metrics()
        roles = {k[0]: v for k, v in _totals(m.thread_cpu_seconds).items()}
        process = _totals(m.process_cpu_seconds).get((), 0.0)
        return {
            "source": self.source,
            "period_s": PERIOD_S,
            "samples": self.samples,
            "process_cpu_s": round(process, 4),
            "roles": {r: round(v, 4) for r, v in sorted(roles.items())},
            "unnamed_s": round(process - sum(roles.values()), 4),
            "threads": sorted(
                ({"name": t.name, "tid": tid, "role": t.role,
                  "cpu_s": round(t.cpu_s, 4)}
                 for tid, t in list(self._known.items())),
                key=lambda r: -r["cpu_s"]),
        }
