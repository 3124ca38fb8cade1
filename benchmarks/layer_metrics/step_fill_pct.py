"""Fused step, by the program's own counters: valid rows folded into
steps over the capacity of the steps dispatched (``tpu_step_rows_counter``
over ``tpu_steps_counter`` x ``batch_capacity``), from the start of the
load to the settled scrape. One chip a cell, so one device's capacity a
step. A feed that pays a step per hand-over and wire side reads 1-10 %."""

UNIT = "%"
ROWS = "tpu_step_rows_counter"
STEPS = "tpu_steps_counter"
COUNTERS = (ROWS, STEPS)


def read(run):
    steps = run.counter_delta(STEPS)
    rows = run.counter_delta(ROWS)
    if steps <= 0 or rows <= 0:
        return None
    capacity = run.config["agent"]["batch_capacity"]
    return 100.0 * rows / (steps * capacity)
