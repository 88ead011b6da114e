"""The bytes a fold kernel must move for one call, from its operands'
shapes, and the share of the chip's HBM roofline that a measured time
gives. Both kernels do about one multiply-add per byte read, far below
the v5e's ridge point, so HBM bandwidth bounds them."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

Shape = Tuple[Tuple[int, ...], str]   # (shape, dtype name) of one operand


def wsum_bytes(rows: int, params: int, itemsize: int) -> int:
    """``weighted_sum_pallas``: read the (rows, params) block and the
    (rows,) fp32 weights once, write the (params,) fp32 sum."""
    return rows * params * itemsize + 4 * rows + 4 * params


def wsum_dequant_bytes(rows: int, padded: int, n_blocks: int) -> int:
    """``weighted_sum_dequant_pallas``: read (rows, padded) int8 codes,
    (rows, n_blocks) fp32 scales and (rows,) fp32 weights once, write
    the (padded,) fp32 sum."""
    return rows * padded + 4 * rows * n_blocks + 4 * rows + 4 * padded


def step_bytes(kind: str, steps: Sequence[Sequence[Shape]]) -> Optional[int]:
    """Bytes per kernel call of the one compiled fold step of ``kind``
    (``"wsum"``: a float block; ``"dequant"``: int8 codes and scales)
    among ``steps``, each given by its operands; None unless exactly
    one step of that kind was compiled."""
    found = set()
    for args in steps:
        (shape, dtype), rest = args[0], args[1:]
        if len(shape) != 2:
            continue
        rows, width = shape
        if kind == "wsum" and dtype.startswith(("float", "bfloat")):
            itemsize = 2 if "16" in dtype else 4 if "32" in dtype else 8
            found.add(wsum_bytes(rows, width, itemsize))
        elif kind == "dequant" and dtype == "int8" and rest:
            found.add(wsum_dequant_bytes(rows, width, rest[0][0][1]))
    return found.pop() if len(found) == 1 else None


def share_pct(total_bytes: float, seconds: float,
              peaks: Optional[dict]) -> Optional[float]:
    """Percent of the HBM roofline: least time at peak bandwidth over the
    measured time. None without a measured time or a peak."""
    if not peaks or seconds <= 0 or total_bytes <= 0:
        return None
    return 100.0 * total_bytes / peaks["hbm_bytes_per_s"] / seconds


def kernel_share(run, kind: str, kernel: str) -> Optional[float]:
    """The share of the HBM roofline that ``kernel``'s calls in the
    traced sub-window reach: fold steps times the bytes of one step of
    ``kind`` (``step_bytes``), over the kernel's device seconds. None
    without a trace, a call, or one compiled step of that kind.

    Over a mesh of d chips every fold step calls the kernel once on each
    chip, on that chip's shard: the trace counts d calls a step, and the
    compiled step's operand is the global one. So the steps are the
    calls over the devices in the trace, and the bytes they move are the
    steps times the global operand's bytes; the seconds are summed over
    the chips. Each chip moves about 1/d of the bytes in its own time
    ``t_i``, and ``(bytes / d) / peak / mean(t_i)`` is ``bytes / peak /
    sum(t_i)``: the mean chip's share of one chip's peak. On one device
    the steps are the calls."""
    if run.trace is None:
        return None
    calls, secs = run.trace.op_time(
        lambda name: name.split(".")[0] == kernel)
    per_step = step_bytes(kind, run.fold_steps)
    if not calls or per_step is None:
        return None
    steps = calls / len(run.trace.per_device)
    return share_pct(steps * per_step, secs, run.peaks)
