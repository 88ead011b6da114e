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
