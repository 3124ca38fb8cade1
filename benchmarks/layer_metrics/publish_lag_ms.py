"""Snapshot + publish, by the program's own watermark: mean of
``tpu_publish_lag_seconds`` over the publish cycles that ended in the
window. At the end of a cycle the program observes now minus the accept
time of the oldest event its sink accepted that the cycle's snapshot
does not hold (0 when it holds all). Ten to thirty cycles a window: too
few for a tail, so the mean."""

UNIT = "ms"
SUM = "tpu_publish_lag_seconds_sum"
COUNT = "tpu_publish_lag_seconds_count"
COUNTERS = (SUM, COUNT)


def read(run):
    inside = [s["c"] for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    n = inside[-1].get(COUNT, 0.0) - inside[0].get(COUNT, 0.0)
    if n <= 0:
        return None
    return 1e3 * (inside[-1].get(SUM, 0.0) - inside[0].get(SUM, 0.0)) / n
