"""Device, by the program's own CPU account, and the account's own
check: of the CPU seconds of the agent's process over the measured
window (``tpu_process_cpu_seconds_counter``: ``getrusage``, what
``host_cpu_us_per_event`` charges), the share that the counters by role
(``tpu_thread_cpu_seconds_counter{role}``, every role of the registry)
do not hold. Both are read at one instant once a period, so the share
is 0 when the account is exhaustive; what it holds is threads that
were born and died between two samples without booking themselves
(the runtime's own short-lived threads), and the ticks a clock of
10 ms rounds away. Read as ``cpu_account`` says. A program without the
account reads nothing."""

import cpu_account

UNIT = "%"
COUNTERS = cpu_account.counters(*cpu_account.ROLES)


def read(run):
    return cpu_account.unnamed_pct(run)
