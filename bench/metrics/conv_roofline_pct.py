"""Roofline share of the conv band kernels (kernels/qconv.py) from the
device trace: over every executor run in the traced window, the least
time of its conv layers (bench/counts.py) over their kernels' device
time."""
from bench.trace import kernel_share

#: op name of the conv band kernel in the device trace
KERNEL = r"qconv"


def read(rec):
    return kernel_share(rec, KERNEL, "conv")
