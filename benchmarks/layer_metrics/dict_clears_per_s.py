"""Flow dictionary + wire, by the program's own counter: capacity
clears of the host flow dictionary a second over the measured window
(``tpu_flow_dict_clears_counter`` between the window's first and last
scrape). A clear is the dictionary's answer to a flush that would
overflow it: every resident descriptor is forgotten, the generation
moves on, and each flow's next row crosses the link as a full
descriptor row again. A dictionary sized to its working set never
clears in a window; one a twelfth of it clears about once a second.
Failure resyncs are not clears and are not counted. A program without
the counter reads 0 at every scrape and the metric says nothing."""

UNIT = "clears/s"
CLEARS = "tpu_flow_dict_clears_counter"
COUNTERS = (CLEARS,)


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    clears = inside[-1]["c"].get(CLEARS, 0.0) - inside[0]["c"].get(CLEARS, 0.0)
    seconds = inside[-1]["sent"] - inside[0]["sent"]
    if clears <= 0 or seconds <= 0:
        return None
    return clears / seconds
