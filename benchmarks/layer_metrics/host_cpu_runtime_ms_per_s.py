"""Device, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `runtime`
(``tpu_thread_cpu_seconds_counter{role="runtime"}``):
every thread of the process that is no Python thread: XLA's,
libtpu's and PJRT's pools, what the device's runtime costs the host
behind the calls the proxy makes. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "runtime"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
