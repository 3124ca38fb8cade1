"""Feed + combine, on a mesh, by the program's own counter: how far the
fullest device's share of the dispatched rows stands over the mean of
the devices' (``tpu_shard_rows_counter{device}``, between the window's
first and last scrape): 100 x (fullest / mean - 1). Events are
partitioned by the connection hash, so Zipf traffic is skewed by
design; the fullest device sizes the wire of all of them and is the one
that overflows first. 0 is an even split, 300 one device of four taking
everything.

The poller sums a counter over its labels, so each device's series is
asked for by its full sample name, label and all. The devices are those
the configuration's ``sizing.mesh_devices`` states (where it states
none, those that were given rows); a program without the counter reads
0 on every one and the metric says nothing."""

UNIT = "%"
SERIES = 'tpu_shard_rows_counter_total{device="%d"}'
MAX_DEVICES = 8  # a TPU host holds at most eight chips
COUNTERS = tuple(SERIES % d for d in range(MAX_DEVICES))


def read(run):
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    if len(inside) < 2:
        return None
    first, last = inside[0]["c"], inside[-1]["c"]
    rows = [last.get(name, 0.0) - first.get(name, 0.0) for name in COUNTERS]
    n = run.config.get("sizing", {}).get("mesh_devices")
    rows = rows[:n] if n else [r for r in rows if r > 0]
    if sum(rows) <= 0:
        return None
    return 100.0 * (max(rows) * len(rows) / sum(rows) - 1.0)
