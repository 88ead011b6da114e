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
the codes are exact in bfloat16. bf16 payloads step down their values
instead, to fp8 (e4m3), with fp32 products and sums: at
``Precision.HIGH`` their products would be exact, since the values are
bf16 and a sample count under 2**17 splits exactly into two bfloat16
parts, so that control could not fail a sound round. The slip a fold of
bf16 rows is likelier to make, products at ``Precision.DEFAULT`` (one
bfloat16 pass, the sample counts rounded to bfloat16), is
``default_chunk``; the limit fails it too.

The arithmetic runs chunk by chunk over the parameters, in a thread
pool, one check at a time in a process. Ranged payloads
(``payloads.make_range``) are made per chunk and never whole, so the
reference holds no row of them.

numpy and the seed's payloads alone: nothing here comes from the
program under test.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np
from threadpoolctl import threadpool_limits

from bench import payloads

# float64 values of one chunk of the pool stay under this many bytes
_CHUNK_BYTES = 256 << 20
# the chunks in flight at once stay under this many bytes of values
# (a chunk also holds about twice its values in per-round arrays)
_CHECK_BYTES = 4 << 30
# parameters in one chunk of a ranged pool: whole tiles
_RANGE_CHUNK = 16 * payloads.TILE
# one check at a time: its chunks take every core, and its BLAS limit
# is the process's, so two at once would undo each other's
_ONE_CHECK = threading.Lock()


class Pool:
    """The payloads a cell's rounds drew from, made anew from the
    seed: whole rows, or for ranged payloads each chunk's slice of
    each row when it is read."""

    def __init__(self, seed: int, config: dict, keys: Sequence[int]):
        self.seed = seed
        self.config = config
        self.keys = sorted(set(int(k) for k in keys))
        self.col = {k: i for i, k in enumerate(self.keys)}
        self.ranged = payloads.ranged(config)
        self.rows = None if self.ranged else [
            payloads.make(seed, config, k) for k in self.keys]
        self.int8 = config["payload"]["kind"] == "int8"
        self.block = config["payload"].get("block", 1)

    def chunks(self) -> Iterator[Tuple[int, int]]:
        params = self.config["params"]
        if self.ranged:
            step = _RANGE_CHUNK
        else:
            step = max(_CHUNK_BYTES // (8 * max(len(self.keys), 1)), 1)
            step = max(step // self.block, 1) * self.block
        for lo in range(0, params, step):
            yield lo, min(lo + step, params)

    def workers(self) -> int:
        """Threads for the chunks: one per core, as far as the chunks in
        flight fit ``_CHECK_BYTES``."""
        lo, hi = next(self.chunks(), (0, 1))
        chunk = 8 * max(len(self.keys), 1) * (hi - lo)
        return max(1, min(payloads.workers(), _CHECK_BYTES // (3 * chunk)))

    def codes_scales(self, lo: int, hi: int):
        """int8 pools: codes (K, hi-lo) and each value's scale
        (K, hi-lo), fp32; ``lo`` is a multiple of the block."""
        b0, b1 = lo // self.block, -(-hi // self.block)
        codes = np.stack([c[lo:hi] for c, _ in self.rows])
        scales = np.stack([s[b0:b1] for _, s in self.rows])
        return codes, np.repeat(scales, self.block, axis=1)[:, :hi - lo]

    def dense(self, lo: int, hi: int) -> np.ndarray:
        """Dense pools: (K, hi-lo) payload values in their own type."""
        if self.ranged:
            return np.stack([
                payloads.make_range(self.seed, self.config, k, lo, hi)
                for k in self.keys])
        return np.stack([r[lo:hi] for r in self.rows])

    def values64(self, lo: int, hi: int) -> np.ndarray:
        """(K, hi-lo) float64 payload values."""
        if self.int8:
            codes, scales = self.codes_scales(lo, hi)
            return codes.astype(np.float64) * scales.astype(np.float64)
        return self.dense(lo, hi).astype(np.float64)


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
    products, or of bf16 payloads with fp8 values."""
    w32 = w.astype(np.float32)
    tot = np.maximum(w32.sum(axis=1, keepdims=True), 1.0)
    if pool.ranged:
        x = pool.dense(lo, hi).astype(ml_dtypes.float8_e4m3fn)
        return (w32 @ x.astype(np.float32)) / tot
    if not pool.int8:
        x = pool.dense(lo, hi)
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


def default_chunk(pool: Pool, w: np.ndarray, lo: int,
                  hi: int) -> np.ndarray:
    """(R, hi-lo) fp32: the FedAvg fold of bf16 payloads with products
    at ``Precision.DEFAULT``, one bfloat16 pass: the sample counts
    rounded to bfloat16, the values exact in it, sums in fp32 over the
    counts as they are."""
    w32 = w.astype(np.float32)
    wb = w32.astype(ml_dtypes.bfloat16).astype(np.float32)
    tot = np.maximum(w32.sum(axis=1, keepdims=True), 1.0)
    return (wb @ pool.dense(lo, hi).astype(np.float32)) / tot


def round_gaps(pool: Pool, w: np.ndarray,
               fused: Optional[Sequence[Optional[np.ndarray]]] = None,
               control: bool = False) -> List[Tuple[float, float]]:
    """Per round, ``(max |candidate - reference|, max |reference|)``.
    The candidate is round r's ``fused[r]``, or with ``control`` the
    control fold. A missing, misshapen or non-finite candidate, or a
    round of no weight, gives an infinite gap."""
    n_rounds = w.shape[0]
    params = pool.config["params"]
    tot = w.sum(axis=1, keepdims=True)
    ok = [tot[r, 0] > 0 and (control or (
              fused[r] is not None and np.shape(fused[r]) == (params,)))
          for r in range(n_rounds)]
    tot[tot == 0] = 1.0

    def chunk(bounds: Tuple[int, int]) -> Tuple[np.ndarray, np.ndarray]:
        lo, hi = bounds
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
        return diff.max(axis=1), np.abs(ref).max(axis=1)

    gap = np.zeros(n_rounds)
    scale = np.zeros(n_rounds)
    # one BLAS thread to a chunk: the chunks are the parallelism, and
    # BLAS's own threads, shared by every caller, would serialize them
    with _ONE_CHECK, threadpool_limits(1, user_api="blas"), \
            ThreadPoolExecutor(pool.workers()) as ex:
        for g, s in ex.map(chunk, pool.chunks()):
            gap = np.maximum(gap, g)
            scale = np.maximum(scale, s)
    return [(float(g), float(s)) if ok[r] else (float("inf"), float(s))
            for r, (g, s) in enumerate(zip(gap, scale))]


def rel_error(gap: float, scale: float) -> float:
    """One round's number: the gap over the scale, inf where either
    says the round cannot be judged."""
    return float(gap / scale) if scale > 0 and gap < float("inf") \
        else float("inf")


def rel_errors(pool: Pool, w: np.ndarray,
               fused: Optional[Sequence[Optional[np.ndarray]]] = None,
               control: bool = False) -> List[float]:
    """Per round, ``max |candidate - reference| / max |reference|``
    (``round_gaps``); a round that cannot be judged reads inf."""
    return [rel_error(g, s) for g, s in round_gaps(pool, w, fused, control)]


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


def check_round(seed: int, config: dict,
                included: Sequence[Tuple[int, int]],
                fused: Optional[np.ndarray]) -> Tuple[float, float]:
    """One round's ``(gap, scale)`` as ``check_rounds`` reads it, for
    a round judged as soon as it ends."""
    pool = Pool(seed, config, [k for k, _ in included])
    return round_gaps(pool, round_weights(pool, [included]), [fused])[0]
