"""Update payloads made from a run's seed, with numpy alone.

The load generator's process, the in-process writer and the reference
all call these functions, so one seed gives the same bytes everywhere,
and nothing the reference reads comes from the program under test.

A configuration's ``payload`` says what a client uploads:

  * ``{"kind": "float32"}``: a dense fp32 vector of ``params`` values;
  * ``{"kind": "int8", "block": B}``: int8 codes, zero past ``params``
    up to a whole number of B-blocks, and one positive fp32 scale per
    block. The codes are drawn directly, so the reference needs no copy
    of the program's quantizer;
  * ``{"kind": "bfloat16"}``: a dense bf16 vector of ``params`` values,
    made by parameter range: tile ``t`` holds parameters ``[t * TILE,
    (t + 1) * TILE)`` and comes from a stream of its own, so any range
    can be made alone (``make_range``) and a whole row tile by tile in
    a thread pool, with no fp32 copy of the row.

Payloads are addressed by a pool index ``key``: a cell draws which key
each upload carries.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple, Union

import ml_dtypes
import numpy as np

_STREAM_PAYLOAD = 1
_STREAM_TILE = 4

# parameters per tile of a ranged payload: its values depend on it
TILE = 1 << 16
RANGED = ("bfloat16",)

Payload = Union[np.ndarray, Tuple[np.ndarray, np.ndarray]]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def workers() -> int:
    """Threads for host-side arithmetic: one per core this process may
    run on."""
    return len(os.sched_getaffinity(0))


def ranged(config: dict) -> bool:
    """Whether the configuration's payloads are made by parameter
    range."""
    return config["payload"]["kind"] in RANGED


def _tile(seed: int, key: int, t: int) -> np.ndarray:
    """Tile ``t`` of ranged payload ``key``: TILE bf16 values, past the
    row's end too."""
    r = rng(seed, _STREAM_TILE, key, t)
    return r.standard_normal(TILE, dtype=np.float32).astype(
        ml_dtypes.bfloat16)


def make_range(seed: int, config: dict, key: int, lo: int,
               hi: int) -> np.ndarray:
    """Parameters ``[lo, hi)`` of ranged payload ``key``, as bf16."""
    if not ranged(config):
        raise ValueError(f"{config['payload']['kind']!r} payloads are "
                         "made whole")
    if not 0 <= lo <= hi <= config["params"]:
        raise ValueError(f"range [{lo}, {hi}) outside the "
                         f"{config['params']} parameters")
    out = np.empty(hi - lo, ml_dtypes.bfloat16)
    for t in range(lo // TILE, -(-hi // TILE)):
        a, b = max(lo, t * TILE), min(hi, (t + 1) * TILE)
        out[a - lo:b - lo] = _tile(seed, key, t)[a - t * TILE:b - t * TILE]
    return out


def make(seed: int, config: dict, key: int) -> Payload:
    """Payload ``key`` of the pool: an fp32 or bf16 vector, or
    ``(codes, scales)`` for int8 payloads."""
    params = config["params"]
    payload = config["payload"]
    if ranged(config):
        row = np.empty(params, ml_dtypes.bfloat16)

        def fill(t: int) -> None:
            lo, hi = t * TILE, min(params, (t + 1) * TILE)
            row[lo:hi] = _tile(seed, key, t)[:hi - lo]

        with ThreadPoolExecutor(workers()) as pool:
            list(pool.map(fill, range(-(-params // TILE))))
        return row
    r = rng(seed, _STREAM_PAYLOAD, key)
    if payload["kind"] == "float32":
        return r.standard_normal(params, dtype=np.float32)
    if payload["kind"] != "int8":
        raise ValueError(f"unknown payload kind {payload['kind']!r}")
    block = payload["block"]
    n_blocks = -(-params // block)
    codes = np.frombuffer(r.bytes(n_blocks * block), np.int8).copy()
    np.maximum(codes, -127, out=codes)   # the symmetric int8 range
    codes[params:] = 0
    scales = np.exp(r.normal(-7.0, 0.5, n_blocks)).astype(np.float32)
    return codes, scales
