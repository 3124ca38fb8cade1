"""Snapshot + publish, by the program's own counters: of the rows of
the pod-level tables the publish cycles of the measured window looked
at (``tpu_publish_rows_counter``), the share they appended or rewrote
because the value had changed (``tpu_publish_rows_changed_counter``),
between the window's first and last scrape. What a cycle costs in
Python follows the changed rows, so this says how much of the delta's
saving the traffic leaves: 100 means every series moved between two
cycles."""

UNIT = "%"
ROWS = "tpu_publish_rows_counter"
CHANGED = "tpu_publish_rows_changed_counter"
COUNTERS = (ROWS, CHANGED)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    first, last = inside[0]["c"], inside[-1]["c"]
    rows = last.get(ROWS, 0.0) - first.get(ROWS, 0.0)
    # A program without the counters reads 0 at every scrape.
    if rows <= 0:
        return None
    return 100.0 * (last.get(CHANGED, 0.0) - first.get(CHANGED, 0.0)) / rows
