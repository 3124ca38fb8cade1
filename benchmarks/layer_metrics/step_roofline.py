"""Kernels of the fused step: the least time the chip could take for the
step's work (``roofline.step_bytes`` over the chip's HBM bandwidth; the
step is bandwidth-bound, it has next to no arithmetic) over the device
time the step took, in percent."""

import roofline
import step_device_ms

UNIT = "%"


def read(run):
    took = step_device_ms.read(run)
    if not took:
        return None
    return 100.0 * roofline.least_step_ms(run.config, run.device_kind) / took
