"""Flow dict + wire, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `dispatch`
(``tpu_thread_cpu_seconds_counter{role="dispatch"}``):
the dispatch thread (`engine-dispatch`): the fold of the held
flushes, the flow dictionary's probe, the wire's build, the enqueue
onto the proxy. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "dispatch"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
