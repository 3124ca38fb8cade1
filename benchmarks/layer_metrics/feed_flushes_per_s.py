"""Feed + combine, by the program's own counter: flushes of the feed
workers a second over the measured window (``tpu_feed_flushes_counter``
summed over its ``cause`` label, between the window's first and last
scrape). Each flush is one combine and one partition of what a worker
held, and one hand-off to the dispatch thread. A worker that holds the
blocks it is dealt until something would release them (a full quantum,
the age bound, a reader, the stop) flushes a few times a second at a
ring's cadence; one that flushed each hand-over alone read 16."""

UNIT = "flushes/s"
FLUSHES = "tpu_feed_flushes_counter"
COUNTERS = (FLUSHES,)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    flushes = (inside[-1]["c"].get(FLUSHES, 0.0)
               - inside[0]["c"].get(FLUSHES, 0.0))
    seconds = inside[-1]["sent"] - inside[0]["sent"]
    # A program without the counter reads 0 at every scrape.
    if flushes <= 0 or seconds <= 0:
        return None
    return flushes / seconds
