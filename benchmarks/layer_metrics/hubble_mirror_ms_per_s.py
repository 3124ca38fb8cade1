"""Hubble mirror: milliseconds inside the flow observer's write (the
``hubble_consume`` spans that began in the window) per second of the
window. The monitor agent hands every plugin block to the observer; a
write that walks the block record by record costs the GIL hundreds of
milliseconds a second at the cells' rate, one that costs per block a
few."""

import host_spans

UNIT = "ms/s"


def read(run):
    spans = host_spans.window_spans(run, "hubble_consume")
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) \
        / (run.t_close - run.t_open)
