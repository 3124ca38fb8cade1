"""Device, on a mesh: device milliseconds inside collective operations
per second of the traced span, mean over the chips. The step's ``psum``
of its summary runs every step, ``end_window``'s once a window and the
snapshot's ``psum`` / ``pmax`` / ``all_gather`` once a publish cycle;
they are ordinary operations of each chip's ``XLA Ops`` line
(``trace_reduce.Chip.ops``), told from the rest by name, with the
``-start`` / ``-done`` halves of an asynchronous one counted both. A
one-chip program has none, and the metric says nothing."""

import re

UNIT = "ms/s"
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|collective-permute|reduce-scatter"
    r"|all-to-all|collective-broadcast)\b")


def read(run):
    t = run.trace
    if t is None or not t.window_s or not t.chips:
        return None
    ns = [sum(d for name, _, d in c.ops if COLLECTIVE.match(name))
          for c in t.chips]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / 1e6 / t.window_s
