"""Fused step, by the program's own counter: fused steps dispatched a
second over the measured window (``tpu_steps_counter`` between the
window's first and last scrape). A step costs the device the same
whatever it holds, so this has to follow the rows offered, not the
hand-overs: a flush-per-hand-over feed at 16 hand-overs a second asks
for 32 (two wire sides a flush) where the device has about 13.7."""

UNIT = "steps/s"
STEPS = "tpu_steps_counter"
COUNTERS = (STEPS,)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    steps = inside[-1]["c"].get(STEPS, 0.0) - inside[0]["c"].get(STEPS, 0.0)
    seconds = inside[-1]["sent"] - inside[0]["sent"]
    # A program without the counter reads 0 at every scrape.
    if steps <= 0 or seconds <= 0:
        return None
    return steps / seconds
