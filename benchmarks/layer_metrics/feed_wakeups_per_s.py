"""Feed + combine, by the program's own counter: returns from a wait of
the feed path a second over the measured window
(``tpu_feed_wakeups_counter`` summed over its ``thread`` and ``cause``
labels, between the window's first and last scrape). The feed loop, the
feed workers and the dispatch thread sleep until data or a real
deadline, so this follows the hand-overs, flushes, dispatches and ticks
of a second: tens to a few hundred. Six threads polling every 2 ms
would read 3,000, and on the chip host's sandboxed kernel each wake-up
costs about a third of a millisecond of CPU."""

UNIT = "wakeups/s"
WAKEUPS = "tpu_feed_wakeups_counter"
COUNTERS = (WAKEUPS,)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    wakeups = (inside[-1]["c"].get(WAKEUPS, 0.0)
               - inside[0]["c"].get(WAKEUPS, 0.0))
    seconds = inside[-1]["sent"] - inside[0]["sent"]
    # A program without the counter reads 0 at every scrape.
    if wakeups <= 0 or seconds <= 0:
        return None
    return wakeups / seconds
