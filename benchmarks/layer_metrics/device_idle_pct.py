"""Device: share of the traced span in which no operation ran on the
chip (1 - union of device-operation intervals over the span)."""

UNIT = "%"


def read(run):
    t = run.trace
    if t is None or not t.window_s or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
