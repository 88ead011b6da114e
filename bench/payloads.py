"""Update payloads made from a run's seed, with numpy alone.

The load generator's process, the in-process writer and the reference
all call these functions, so one seed gives the same bytes everywhere,
and nothing the reference reads comes from the program under test.

A configuration's ``payload`` says what a client uploads:

  * ``{"kind": "float32"}``: a dense fp32 vector of ``params`` values;
  * ``{"kind": "int8", "block": B}``: int8 codes, zero past ``params``
    up to a whole number of B-blocks, and one positive fp32 scale per
    block. The codes are drawn directly, so the reference needs no copy
    of the program's quantizer.

Payloads are addressed by a pool index ``key``: a cell draws which key
each upload carries.
"""
from __future__ import annotations

from typing import Tuple, Union

import numpy as np

_STREAM_PAYLOAD = 1

Payload = Union[np.ndarray, Tuple[np.ndarray, np.ndarray]]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def make(seed: int, config: dict, key: int) -> Payload:
    """Payload ``key`` of the pool: an fp32 vector, or ``(codes,
    scales)`` for int8 payloads."""
    r = rng(seed, _STREAM_PAYLOAD, key)
    params = config["params"]
    payload = config["payload"]
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

