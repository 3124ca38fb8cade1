"""Window close + harvest: window ticks deferred per window closed, in
percent, from the start of the load to the settled scrape."""

UNIT = "%"
COUNTERS = ("tpu_windows_closed", "tpu_windows_deferred")


def read(run):
    closed = run.counter_delta("tpu_windows_closed")
    if not closed:
        return None
    return 100.0 * run.counter_delta("tpu_windows_deferred") / closed
