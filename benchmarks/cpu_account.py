"""What the ``host_cpu_*`` readers share: the program's own account of
its process's CPU (``retina_tpu/obs/cpuaccount.py``), as the poller
scraped it.

Once a period a sampler in the agent's process reads
``getrusage(RUSAGE_SELF)`` into ``tpu_process_cpu_seconds_counter``
and, at the same instant, every thread's CPU seconds into
``tpu_thread_cpu_seconds_counter{role}`` by the role of the thread:
two readings of what ``host_cpu_us_per_event`` charges. The poller
gives a line of a counter to the first name whose prefix matches, so
nobody asks for the by-role counter's sum: each role's sample is asked
for by its full name, label and all.

The counters move once a period, not once a scrape, so a rate is taken
between the first and the last scrape of the window at which the
process's counter had moved (a sample had just landed), not between
the window's first and last scrape: those would cut a period (2 s)
short at either end, up to 4 % of a 50 s window each. A program without the account
reads 0 at every scrape: nothing moves and the readers say nothing.
"""

PROCESS = "tpu_process_cpu_seconds_counter"
SERIES = 'tpu_thread_cpu_seconds_counter_total{role="%s"}'
# Every role of the program's registry (metric_names.THREAD_ROLES; a
# tier-1 test holds the two together): what no role holds is unnamed.
ROLES = ("feed", "dispatch", "proxy", "harvest", "publish", "serve",
         "control", "hubble", "account", "runtime", "foreign")


def counters(*roles):
    """What a reader of these roles asks the poller for."""
    return (PROCESS, *(SERIES % r for r in roles))


def between(run):
    """(the counters at the first sample that landed in the window, at
    the last, the seconds between their scrapes), or None."""
    inside = [s for s in run.scrapes
              if s["ok"] and run.t_open <= s["sent"] < run.t_close]
    moved = [b for a, b in zip(inside, inside[1:])
             if b["c"].get(PROCESS, 0.0) != a["c"].get(PROCESS, 0.0)]
    if len(moved) < 2:
        return None
    return moved[0]["c"], moved[-1]["c"], moved[-1]["sent"] - moved[0]["sent"]


def role_ms_per_s(run, role):
    """CPU milliseconds a second of the window burnt by the threads of
    one role (0.0 where the role burnt nothing: the account is there)."""
    found = between(run)
    if found is None:
        return None
    first, last, seconds = found
    name = SERIES % role
    return 1e3 * (last.get(name, 0.0) - first.get(name, 0.0)) / seconds


def unnamed_pct(run):
    """Of the process's CPU seconds, the share no role holds."""
    found = between(run)
    if found is None:
        return None
    first, last, _ = found
    process = last[PROCESS] - first[PROCESS]
    named = sum(last.get(SERIES % r, 0.0) - first.get(SERIES % r, 0.0)
                for r in ROLES)
    return 100.0 * (process - named) / process
