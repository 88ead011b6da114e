"""wsum_dequant_roofline_pct (%): the int8 dequant weighted-sum kernel's
share of the HBM roofline in the traced sub-window: the bytes its calls
must move (bench/roofline.py, from the compiled fold step's operand
shapes) over their device time from the trace, over 819 GB/s."""

from bench import roofline

KERNEL = "weighted_sum_dequant_pallas"


def read(run):
    if run.trace is None:
        return None
    calls, secs = run.trace.op_time(
        lambda name: name.split(".")[0] == KERNEL)
    per_call = roofline.step_bytes("dequant", run.fold_steps)
    if not calls or per_call is None:
        return None
    return roofline.share_pct(calls * per_call, secs, run.peaks)
