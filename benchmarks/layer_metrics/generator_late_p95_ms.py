"""How late the benchmark's own generator ran: 95th percentile, over
every block handed over in the window, of the time from the tick's due
time to the hand-over. A starved generator must not read as a fast
agent."""

from measure import percentile

UNIT = "ms"


def read(run):
    late = run.window_block_late_s
    return percentile(late, 95) * 1e3 if late else None
