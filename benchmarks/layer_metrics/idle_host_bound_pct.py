"""Device: share of the traced span in which the chip was idle *and* the
program had work in hand for it: a proxy call running (others queued
behind it), or a feed-side span open while the proxy waited
(``host_spans.attribute``). The rest of the idle share is ``no_work``:
nothing was there to give the chip."""

import host_spans

UNIT = "%"


def read(run):
    a = host_spans.analysis(run)
    if not a.get("idle") or not a.get("span_s"):
        return None
    return 100.0 * host_spans.host_bound_s(a["idle"]) / a["span_s"]
