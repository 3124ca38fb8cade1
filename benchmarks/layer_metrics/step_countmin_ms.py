"""Fused step: device milliseconds per step inside the three Count-Min
and top-k updates (scopes ``cms_flow_hh``, ``cms_svc_hh``,
``cms_dns_hh``), from the profiler's trace joined with the program's
scope map (``host_spans.scope_seconds``)."""

import host_spans

UNIT = "ms"
SCOPES = ("cms_flow_hh", "cms_svc_hh", "cms_dns_hh")


def read(run):
    return host_spans.scope_ms(run, SCOPES)
