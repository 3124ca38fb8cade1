"""Snapshot + publish, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `serve`
(``tpu_thread_cpu_seconds_counter{role="serve"}``):
the HTTP side: the accepting thread, the render thread (the default
registry walked child by child, the pod-level bytes joined) and one
handler thread a request, which books itself as it ends (the socket
write of a 6.9 MB body). Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "serve"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
