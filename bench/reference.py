"""The plain reference that decides ``correct``, and its control.

The reference is FedAvg over the payloads a round included, in float64:
``sum_i w_i x_i / sum_i w_i``, with int8 payloads dequantized from the
codes and scales that were sent. A round is judged by one number: the
widest gap ``max |fused - reference|`` over the vector, against the
vector's scale ``max |reference|``.

The control is the same fold one precision step below what the
configurations state (fp32 products at ``Precision.HIGHEST``): products
as the TPU takes them at ``Precision.HIGH``, where each fp32 operand is
split into a high and a low bfloat16 part and the product of the two low
parts is dropped; sums in fp32. For int8 payloads the split operand is
the fp32 product of weight and scale, as the dequant kernel forms it;
the codes are exact in bfloat16.

numpy and the seed's payloads alone: nothing here comes from the
program under test.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

from bench import payloads

# float64 values of one chunk of the pool stay under this many bytes
_CHUNK_BYTES = 256 << 20


class Pool:
    """The payloads a cell's rounds drew from, made anew from the
    seed."""

    def __init__(self, seed: int, config: dict, keys: Sequence[int]):
        self.config = config
        self.keys = sorted(set(int(k) for k in keys))
        self.col = {k: i for i, k in enumerate(self.keys)}
        self.rows = [payloads.make(seed, config, k) for k in self.keys]
        self.int8 = config["payload"]["kind"] == "int8"
        self.block = config["payload"].get("block", 1)

    def chunks(self) -> Iterator[Tuple[int, int]]:
        params = self.config["params"]
        step = max(_CHUNK_BYTES // (8 * max(len(self.keys), 1)), 1)
        step = max(step // self.block, 1) * self.block
        for lo in range(0, params, step):
            yield lo, min(lo + step, params)

    def codes_scales(self, lo: int, hi: int):
        """int8 pools: codes (K, hi-lo) and each value's scale
        (K, hi-lo), fp32; ``lo`` is a multiple of the block."""
        b0, b1 = lo // self.block, -(-hi // self.block)
        codes = np.stack([c[lo:hi] for c, _ in self.rows])
        scales = np.stack([s[b0:b1] for _, s in self.rows])
        return codes, np.repeat(scales, self.block, axis=1)[:, :hi - lo]

    def values64(self, lo: int, hi: int) -> np.ndarray:
        """(K, hi-lo) float64 payload values."""
        if self.int8:
            codes, scales = self.codes_scales(lo, hi)
            return codes.astype(np.float64) * scales.astype(np.float64)
        return np.stack([r[lo:hi] for r in self.rows]).astype(np.float64)


def round_weights(pool: Pool,
                  rounds: Sequence[Sequence[Tuple[int, int]]]) -> np.ndarray:
    """(R, K) float64: round r's summed sample counts per pool payload,
    from each round's ``(key, weight)`` pairs."""
    w = np.zeros((len(rounds), len(pool.keys)), np.float64)
    for r, included in enumerate(rounds):
        for key, weight in included:
            w[r, pool.col[int(key)]] += float(weight)
    return w


def _bf16_parts(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """fp32 ``x`` as high + low bfloat16 parts, held in fp32."""
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def control_chunk(pool: Pool, w: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """(R, hi-lo) fp32: the FedAvg fold with ``Precision.HIGH``
    products."""
    w32 = w.astype(np.float32)
    tot = np.maximum(w32.sum(axis=1, keepdims=True), 1.0)
    if not pool.int8:
        x = np.stack([r[lo:hi] for r in pool.rows])
        wh, wl = _bf16_parts(w32)
        xh, xl = _bf16_parts(x)
        return (wh @ xh + wh @ xl + wl @ xh) / tot
    codes, _ = pool.codes_scales(lo, hi)
    out = np.empty((w.shape[0], hi - lo), np.float32)
    b0 = lo // pool.block
    for j in range(0, hi - lo, pool.block):
        b = b0 + j // pool.block
        s = np.asarray([row[1][b] for row in pool.rows], np.float32)
        ah, al = _bf16_parts(w32 * s[None, :])   # the kernel's w * s
        q = codes[:, j:j + pool.block].astype(np.float32)
        out[:, j:j + pool.block] = ah @ q + al @ q
    return out / tot


def rel_errors(pool: Pool, w: np.ndarray,
               fused: Optional[Sequence[Optional[np.ndarray]]] = None,
               control: bool = False) -> List[float]:
    """Per round, ``max |candidate - reference| / max |reference|``.
    The candidate is round r's ``fused[r]``, or with ``control`` the
    control fold. A missing, misshapen or non-finite candidate reads
    inf."""
    n_rounds = w.shape[0]
    params = pool.config["params"]
    tot = w.sum(axis=1, keepdims=True)
    ok = [tot[r, 0] > 0 and (control or (
              fused[r] is not None and np.shape(fused[r]) == (params,)))
          for r in range(n_rounds)]
    tot[tot == 0] = 1.0
    gap = np.zeros(n_rounds)
    scale = np.zeros(n_rounds)
    for lo, hi in pool.chunks():
        ref = (w @ pool.values64(lo, hi)) / tot
        if control:
            cand = control_chunk(pool, w, lo, hi).astype(np.float64)
        else:
            cand = np.zeros_like(ref)
            for r, f in enumerate(fused):
                if ok[r]:
                    cand[r] = f[lo:hi]
        diff = np.abs(cand - ref)
        diff[~np.isfinite(diff)] = np.inf
        gap = np.maximum(gap, diff.max(axis=1))
        scale = np.maximum(scale, np.abs(ref).max(axis=1))
    return [float(g / s) if ok[r] and s > 0 else float("inf")
            for r, (g, s) in enumerate(zip(gap, scale))]


def check_rounds(seed: int, config: dict,
                 rounds: Sequence[Tuple[Sequence[Tuple[int, int]],
                                        Optional[np.ndarray]]],
                 control: bool = False) -> Dict[str, object]:
    """Judge rounds given as ``(included (key, weight) pairs, fused)``:
    ``{"rel_errors": [...], "max_rel_err": float}``."""
    if not rounds:
        return {"rel_errors": [], "max_rel_err": float("inf")}
    pool = Pool(seed, config, [k for inc, _ in rounds for k, _ in inc])
    w = round_weights(pool, [inc for inc, _ in rounds])
    errs = rel_errors(pool, w, [f for _, f in rounds], control=control)
    return {"rel_errors": errs, "max_rel_err": max(errs)}
