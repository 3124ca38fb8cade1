"""Device proxy, by the program's own stall record: the longest
``stall`` span of cause ``thread`` that overlaps the measured window,
in milliseconds: one thread was mid-work (its liveness cell beaten, not
parked) and silent for over a second while the watchdog's scan woke on
time. Its ends are the thread's own clock readings. In a run whose
host is sound this is the device proxy inside one call (``kind`` says
which: a first dispatch of a bucket that stalls the chip's runtime is
``step``) or the completion thread waiting for the device. 0.0 on a
run without one; nothing on a program without the stage (``stalls``)."""

import stalls

UNIT = "ms"


def read(run):
    return stalls.longest_ms(run, stalls.THREAD)
