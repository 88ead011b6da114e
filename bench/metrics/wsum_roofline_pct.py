"""wsum_roofline_pct (%): the weighted-sum kernel's share of the HBM
roofline in the traced sub-window: the bytes its calls must move
(bench/roofline.py, from the compiled fold step's operand shapes) over
their device time from the trace, over 819 GB/s; over a mesh, the
mean chip's share (``roofline.kernel_share``)."""

from bench import roofline

KERNEL = "weighted_sum_pallas"


def read(run):
    return roofline.kernel_share(run, "wsum", KERNEL)
