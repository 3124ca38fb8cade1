#!/usr/bin/env python3
"""Run one cell of the benchmark with a stall planted in its measured
window, for the controls of the program's stall record
(``retina_tpu/runtime/supervisor.py``): a detector is worth what it
finds when the answer is known.

    python3 benchmarks/tests/drive_stall.py <control> [--at S] [--for S] \
        -- --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything after ``--`` is ``run.py``'s own command line, and the run is
``run.py``'s, in this process (which owns the chip). ``--at`` seconds
into the measured window (default 12, past the traced seconds) the
control is planted, for ``--for`` seconds (default 2):

``pause``  a helper process sends this one ``SIGSTOP`` and, later,
           ``SIGCONT``: the whole process is off the CPU. Must read
           ``stall cause=paused`` with hardly any CPU.
``hold``   a thread of this process (``stall-control``) runs one
           ``sum(range(n))``, which never lets go of the interpreter
           lock. Must read ``held`` with that thread as ``top_thread``.
``proxy``  the fault layer hangs the next dispatch's transfer on the
           device proxy (``transfer:hang<S>@1``). Must read ``thread``,
           ``device-proxy``, ``kind=step``, and no process stall.
``none``   nothing is planted: the wrapper only reads.

Whatever the control, the wrapper logs at the window's close what an
untraced run's line leaves out (``phase: "window_account"``): the
program's CPU account by thread role in ms a second of the window
(``tpu_thread_cpu_seconds_counter{role}``, read in-process at the
window's two ends, so exact to the account's 2 s period), the scan's
mean lateness, the ``stalls`` line the per-layer readers log on a
traced run, and the poller's side of a hole (``outside_clock``). Where the program has no such counter or stage (a parent
commit) that part is left out.

The controls need not end ``correct``: a held transfer may well flip
the overload controller. What is read is the ``stall`` line the program
logs and, on a traced run, the ``stalls`` line and the three metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

_HELPER = ("import os, signal, sys, time\n"
           "pid, seconds = int(sys.argv[1]), float(sys.argv[2])\n"
           "os.kill(pid, signal.SIGSTOP)\n"
           "time.sleep(seconds)\n"
           "os.kill(pid, signal.SIGCONT)\n")


def log(**obj) -> None:
    print(json.dumps(obj), file=sys.stderr, flush=True)


def spin_for(seconds: float) -> int:
    """An ``n`` whose ``sum(range(n))`` takes about ``seconds`` here."""
    n = 2_000_000
    t = time.perf_counter()
    sum(range(n))
    return int(n * seconds / max(time.perf_counter() - t, 1e-6))


def plant(control: str, seconds: float) -> None:
    t = time.monotonic()
    if control == "pause":
        # The helper outlives the pause: nobody waits for it.
        subprocess.Popen([sys.executable, "-c", _HELPER, str(os.getpid()),
                          str(seconds)], stdin=subprocess.DEVNULL)
        time.sleep(seconds + 1.0)  # stopped inside this sleep
    elif control == "hold":
        n = spin_for(seconds)
        t = time.monotonic()
        sum(range(n))
    elif control == "proxy":
        from retina_tpu.runtime import faults

        faults.configure(f"transfer:hang{seconds:g}@1")
        time.sleep(seconds + 1.0)
    log(phase="stall_control", control=control, planted_at=round(t, 3),
        for_s=seconds, took_s=round(time.monotonic() - t, 3))
    # A thread that ends with its hold is gone from /proc before anyone
    # can ask who ran: the holder of a real hold lives on, so this does.
    time.sleep(5.0)


def _reading() -> dict:
    """The program's counters this wrapper reports, as they stand."""
    from retina_tpu.metrics import get_metrics

    m = get_metrics()
    out = {"t": time.monotonic()}
    roles = getattr(m, "thread_cpu_seconds", None)
    if roles is not None:
        out["roles"] = {s.labels["role"]: s.value
                        for mf in roles.collect() for s in mf.samples
                        if s.name.endswith("_total")}
    for key in ("wake_late_seconds", "watchdog_scans"):
        c = getattr(m, key, None)
        if c is not None:
            out[key] = c._value.get()
    return out


def window_account(first: dict, last: dict) -> None:
    seconds = last["t"] - first["t"]
    out: dict = {"seconds": round(seconds, 2)}
    if "roles" in last:
        out["role_ms_per_s"] = {
            r: round(1e3 * (v - first["roles"].get(r, 0.0)) / seconds, 2)
            for r, v in sorted(last["roles"].items())}
    scans = last.get("watchdog_scans", 0) - first.get("watchdog_scans", 0)
    if scans > 0:
        out["scans"] = scans
        out["wake_late_mean_ms"] = round(1e3 * (
            last["wake_late_seconds"] - first["wake_late_seconds"]) / scans,
            4)
    log(phase="window_account", **out)


def outside_clock(load) -> None:
    """What the poller, a process of its own, saw of a hole: the longest
    wait between two scrapes it sent in the window and the longest round
    trip. A long round trip says the agent alone stood still (the poller
    ran, and waited for it); a long wait between sends with no long round
    trip says the poller stood still too: the whole sandbox."""
    inside = [s for s in load.scrapes
              if load.t_open <= s["sent"] < load.t_close]
    if len(inside) < 2:
        return
    gap, gap_at = max((b["sent"] - a["sent"], a["sent"])
                      for a, b in zip(inside, inside[1:]))
    trip, trip_at = max((s["done"] - s["sent"], s["sent"]) for s in inside)
    log(phase="outside_clock", scrapes=len(inside),
        longest_send_gap_s=round(gap, 3),
        gap_at_s=round(gap_at - load.t_open, 3),
        longest_round_trip_s=round(trip, 3),
        trip_at_s=round(trip_at - load.t_open, 3))


def main() -> int:
    argv = sys.argv[1:]
    rest = argv[argv.index("--") + 1:] if "--" in argv else []
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("control", choices=("pause", "hold", "proxy", "none"))
    ap.add_argument("--at", type=float, default=12.0)
    ap.add_argument("--for", dest="seconds", type=float, default=2.0)
    args = ap.parse_args(argv[:argv.index("--")] if "--" in argv else argv)

    import harness
    import run

    offer = harness.Bench.offer

    def offer_and_plant(self, mix, seconds, trace=False):
        import traffic

        sched = traffic.Schedule.of(mix, self.cfg.window_seconds, seconds)
        delay = 0.25 + sched.warm_ticks * sched.tick_s + args.at
        log(phase="stall_control", control=args.control,
            window_opens_in_s=round(delay - args.at, 2), plant_at_s=args.at)
        readings: list = []
        timers = [threading.Timer(d, lambda: readings.append(_reading()))
                  for d in (delay - args.at, delay - args.at + seconds)]
        if args.control != "none":
            timers.append(threading.Timer(delay, plant,
                                          (args.control, args.seconds)))
        for timer in timers:
            timer.name = "stall-control"
            timer.daemon = True
            timer.start()
        load = offer(self, mix, seconds, trace)
        if len(readings) == 2:
            window_account(*readings)
        outside_clock(load)
        import stalls

        stalls.window_stalls(load)  # logs the run's `stalls` line
        return load

    harness.Bench.offer = offer_and_plant
    return run.main(rest)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
