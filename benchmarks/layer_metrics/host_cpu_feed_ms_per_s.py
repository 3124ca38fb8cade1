"""Feed + combine, by the program's own CPU account: CPU milliseconds a
second of the measured window burnt by the threads of role `feed`
(``tpu_thread_cpu_seconds_counter{role="feed"}``):
the distributor loop (`engine`), the feed workers, the plugins' and
the plugin manager's threads, and the combine's stripe threads, which
book themselves: deal, combine, partition, the hand-off to the
dispatch thread. Read as ``cpu_account`` says: between the
first and the last sample of the account that landed in the window. A
program without the account reads nothing."""

import cpu_account

UNIT = "ms/s"
ROLE = "feed"
COUNTERS = cpu_account.counters(ROLE)


def read(run):
    return cpu_account.role_ms_per_s(run, ROLE)
