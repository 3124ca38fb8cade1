"""Overload control: the pressure the controller compares with its
thresholds (the largest of its signals at its last tick,
``tpu_overload_pressure``), 95th percentile over the scrapes sent in the
measured window. Entering SAMPLING takes 0.75; a run counts only while
the controller makes no transition."""

from measure import percentile

UNIT = "pressure"
GAUGE = "tpu_overload_pressure"
COUNTERS = (GAUGE,)


def read(run):
    seen = [s["c"].get(GAUGE, 0.0) for s in run.scrapes
            if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    # A program without the gauge reads 0 at every scrape; one with it
    # under load never does (a dispatch's enqueue takes time).
    if not seen or max(seen) <= 0:
        return None
    return percentile(seen, 95)
