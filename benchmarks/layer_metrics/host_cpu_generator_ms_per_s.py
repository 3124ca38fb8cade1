"""Benchmark generator, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `foreign`
(``tpu_thread_cpu_seconds_counter{role="foreign"}``):
the Python threads the program did not spawn (role `foreign`): in
the window the benchmark's pacer, which copies and restamps every
block it hands over, and the harness's sleeping main thread. It is
the generator's share of the bill `host_cpu_us_per_event` charges the
agent. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "foreign"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
