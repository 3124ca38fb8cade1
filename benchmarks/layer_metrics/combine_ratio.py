"""Feed + combine: events in over wire rows out, from the start of the
load to the settled scrape (counter deltas, so exact and the same on
every run of one seed)."""

UNIT = "events/row"
COUNTERS = ("tpu_wire_rows_counter",)


def read(run):
    rows = run.counter_delta("tpu_wire_rows_counter")
    return run.total_rows / rows if rows else None
