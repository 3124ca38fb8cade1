"""Device proxy: 95th percentile of the wait in its FIFO (enqueue to
start) over the window's proxied calls of every kind but ``poll``
(the ``proxy_run`` spans; polls write none)."""

import host_spans
from measure import percentile

UNIT = "ms"


def read(run):
    waits = [s["args"]["wait_s"]
             for s in host_spans.window_spans(run, "proxy_run")
             if "wait_s" in s.get("args", {})]
    return percentile(waits, 95) * 1e3 if waits else None
