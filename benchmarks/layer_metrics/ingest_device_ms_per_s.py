"""Flow dictionary + wire, on the device: milliseconds inside the ingest
programs per second of the traced span, mean over the chips. They are
what turns a wire back into step windows: the descriptor insert into
the device's table (new rows), the gather of resident descriptors
(known rows) and the unpack of both, compiled one a wire bucket and all
under the name ``jit_ingest``; found among the trace's programs as
``step_device_ms`` finds the step. It is what the dictionary costs the
device beside the step: the new side's program hands the whole table
on, so the time follows the table's slots more than the rows inserted
(6.3 ms a second with 2^18 slots and 64,000 inserts a dispatch, 8.7 with
2^21 slots and a few thousand: PERF.md, PR 35)."""

UNIT = "ms/s"
PROGRAM = r"^jit_ingest"


def read(run):
    t = run.trace
    if t is None or not t.window_s or not t.chips:
        return None
    ms = t.program_ms(PROGRAM)
    return sum(ms) / len(t.chips) / t.window_s if ms else None
