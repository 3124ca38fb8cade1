"""Flow dictionary + wire: bytes sent to the device per offered event,
from the start of the load to the settled scrape."""

UNIT = "bytes/event"
COUNTERS = ("tpu_transfer_bytes",)


def read(run):
    sent = run.counter_delta("tpu_transfer_bytes")
    return sent / run.total_rows if sent and run.total_rows else None
