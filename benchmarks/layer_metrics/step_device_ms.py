"""Fused step: mean device time of one execution of the step program
(the configuration's ``step_program``), over its executions in the
traced part of the window. From the profiler's trace, so it is device
time and not an enqueue."""

UNIT = "ms"


def read(run):
    if run.trace is None:
        return None
    ms = run.trace.program_ms(run.config["step_program"])
    return sum(ms) / len(ms) if ms else None
