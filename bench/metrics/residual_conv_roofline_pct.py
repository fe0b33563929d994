"""Roofline share of the conv band kernels (kernels/qconv.py) in a
network whose residual adds the program folds into conv epilogues: the
calls ``conv_roofline_pct`` reads, over a least time that also charges
each folded add's skip operand.

A folded add runs inside its host conv's kernel, which reads the skip
operand from HBM: one of the add row's three int8 tensors
(bench/counts.py), read at the memory bandwidth.  Its other two are not
charged: the host conv's own result is merged in VMEM and never leaves
it, and the merged output is the conv's output, whose write the conv
row already counts.  Left out, with a line, where the window does not
hold exactly one conv call per conv layer and request."""
from bench.counts import roofline_s
from bench.trace import kernel_share

#: op name of the conv band kernel in the device trace
KERNEL = r"qconv"


def read(rec):
    share = kernel_share(rec, KERNEL, "conv")
    if share is None:
        return None
    rows, peak = rec["counts"], rec["peak"]
    conv_s = roofline_s([r for r in rows if r["kind"] == "conv"], peak)
    skip_s = sum(r["bytes"] for r in rows if r["kind"] == "add") / 3 / peak["hbm_bytes_per_s"]
    # the share is conv_s per request over the kernels' time per request
    return share * (conv_s + skip_s) / conv_s
