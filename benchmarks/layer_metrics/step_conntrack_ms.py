"""Fused step: device milliseconds per step inside the conntrack update
and the apiserver latency matcher (scopes ``conntrack``,
``latency_match``). ``BENCHMARK.json`` lists it for the cell with
conntrack metrics alone: without them the conntrack update all but
disappears from the step (0.002 ms) while the matcher still runs
(5.03 ms), so the sum there would read the matcher under conntrack's
name."""

import host_spans

UNIT = "ms"
SCOPES = ("conntrack", "latency_match")


def read(run):
    return host_spans.scope_ms(run, SCOPES)
