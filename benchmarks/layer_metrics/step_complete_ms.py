"""Fused step, by the program's own timer: seconds of the ``device_step``
spans that began in the window over the steps they ran. The span opens
at a group's first dispatch and is closed by the completion thread when
its last step's output is ready, so this stands beside the profiler's
``step_device_ms``; a timer that reads less is timing an enqueue."""

import host_spans

UNIT = "ms"


def read(run):
    spans = host_spans.window_spans(run, "device_step")
    steps = sum(s.get("args", {}).get("n_steps", 0) for s in spans)
    if not steps:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / steps
