"""Snapshot + publish, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `publish`
(``tpu_thread_cpu_seconds_counter{role="publish"}``):
the publisher's thread (`metricsmodule`): a cycle's snapshot, the
compare of the active entries with the rows' last values, the lines
it rewrites. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "publish"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
