"""Snapshot + publish, by the program's own counter: CPU milliseconds
the publisher's cycle costs the agent's process per second of the
measured window (``tpu_publish_cpu_seconds_counter`` summed over its
``part`` label, between the window's first and last scrape). The
counter is ``time.thread_time()`` of the publisher's thread inside a
cycle's snapshot and ``series_publish`` and of the gathering thread
inside a render of the pod-level bytes: what ≈ 35,000 pod-level series
cost at whatever cadence the publisher keeps. As one child per series,
set and rendered whole every cycle, it was most of the agent's host
CPU."""

UNIT = "ms/s"
CPU = "tpu_publish_cpu_seconds_counter"
COUNTERS = (CPU,)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    cpu_s = inside[-1]["c"].get(CPU, 0.0) - inside[0]["c"].get(CPU, 0.0)
    seconds = inside[-1]["sent"] - inside[0]["sent"]
    # A program without the counter reads 0 at every scrape.
    if cpu_s <= 0 or seconds <= 0:
        return None
    return 1e3 * cpu_s / seconds
