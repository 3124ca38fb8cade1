"""Device proxy, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `proxy`
(``tpu_thread_cpu_seconds_counter{role="proxy"}``):
the one thread every JAX call rides (`device-proxy`) and the
completion thread beside it: `device_put`, the ingest programs' and
the steps' dispatch, readiness polls, readbacks. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "proxy"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
