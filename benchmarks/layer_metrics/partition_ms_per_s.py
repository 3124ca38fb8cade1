"""Feed + combine, on a mesh: milliseconds inside the host partition
(the ``partition`` spans that began in the window, one a flush of a
feed worker) per second of the window. On one device the partition is
a zero-copy view; over four it is the canonical connection hash in
numpy and a masked copy per device, on the feed workers' threads. A
program without the span says nothing."""

import host_spans

UNIT = "ms/s"


def read(run):
    spans = host_spans.window_spans(run, "partition")
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) \
        / (run.t_close - run.t_open)
