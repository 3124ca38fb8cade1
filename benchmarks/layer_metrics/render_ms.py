"""Snapshot + publish: mean ``render`` span in the window, one render of
the whole exposition by the HTTP server."""

import host_spans

UNIT = "ms"


def read(run):
    spans = host_spans.window_spans(run, "render")
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
