"""Snapshot + publish, as a scraper sees it: for every scrape sent in the
window, from the due time of the oldest block that was due when it was
sent and that it does not show in full, to the scrape's end; 95th
percentile over all of them (``measure.staleness_s``). A stall anywhere
between the sink and the HTTP server grows it scrape by scrape. Read in
every run; it is not an end-to-end metric only because runs of one code
spread by 6-31 % on it (PERF.md, section 2)."""

from measure import percentile

UNIT = "ms"


def read(run):
    stale = run.staleness_s()
    return percentile(stale, 95) * 1e3 if stale else None
