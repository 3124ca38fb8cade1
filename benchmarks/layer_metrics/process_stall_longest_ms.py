"""Device (the host under it), by the program's own stall record: the
longest ``stall`` span of cause ``paused`` or ``held`` that overlaps
the measured window, in milliseconds. The watchdog's scan woke that
long after it was due: for that long no Python thread of the agent's
process could wake, because the process was off the CPU (``paused``:
it burnt hardly any) or because somebody kept the interpreter or the
machine (``held``). Resolution is the scan's interval: a hole of L
seconds reads between L - 0.5 and L. 0.0 on a run without one; nothing
on a program without the stage (``stalls``)."""

import stalls

UNIT = "ms"


def read(run):
    return stalls.longest_ms(run, stalls.PROCESS)
