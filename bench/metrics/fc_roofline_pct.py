"""Roofline share of the FC kernel (kernels/qgemm.py) from the device
trace: over every executor run in the traced window, the least time of
its FC layers (bench/counts.py) over their kernels' device time."""
from bench.trace import kernel_share

#: op name of the FC kernel in the device trace
KERNEL = r"qgemm"


def read(rec):
    return kernel_share(rec, KERNEL, "fc")
