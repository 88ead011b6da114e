"""wsum_dequant_roofline_pct (%): the int8 dequant weighted-sum kernel's
share of the HBM roofline in the traced sub-window: the bytes its calls
must move (bench/roofline.py, from the compiled fold step's operand
shapes) over their device time from the trace, over 819 GB/s; over a
mesh, the mean chip's share (``roofline.kernel_share``)."""

from bench import roofline

KERNEL = "weighted_sum_dequant_pallas"


def read(run):
    return roofline.kernel_share(run, "dequant", KERNEL)
