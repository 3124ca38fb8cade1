"""Snapshot + publish: bytes read back from the device per window
closed, from the start of the load to the settled scrape."""

UNIT = "bytes/window"
COUNTERS = ("tpu_windows_closed", "tpu_readback_bytes")


def read(run):
    closed = run.counter_delta("tpu_windows_closed")
    back = run.counter_delta("tpu_readback_bytes")
    return back / closed if closed and back else None
