"""Device (the host under it), by the program's own counters: how late
the watchdog's scan woke on average over the measured window, in
milliseconds (``tpu_wake_late_seconds_counter`` over
``tpu_watchdog_scans_counter``, between the window's first and last
scrape). The scan sleeps half a second at a time and books, each time
it wakes, how long after it was due: what a Python thread of the
agent's process pays to get the CPU and the interpreter lock back
after a wait. A hole of seconds is in here too, spread over a hundred
scans. A program without the counters reads 0 at every scrape and the
reader says nothing."""

UNIT = "ms"
LATE = "tpu_wake_late_seconds_counter"
SCANS = "tpu_watchdog_scans_counter"
COUNTERS = (LATE, SCANS)


def read(run):
    inside = [s["c"] for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    scans = inside[-1].get(SCANS, 0.0) - inside[0].get(SCANS, 0.0)
    if scans <= 0:
        return None
    return 1e3 * (inside[-1].get(LATE, 0.0) - inside[0].get(LATE, 0.0)) \
        / scans
