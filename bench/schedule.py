"""Which payload each upload carries, with what sample count, and when
it is due: all drawn from the run's seed, with numpy alone.

Two shapes of traffic:

  * open loop (``open_loop_epoch``): Poisson arrivals at a fixed total
    rate. Each epoch of ``epoch_s`` seconds holds exactly ``rate *
    epoch_s`` arrivals, so every seed offers the same work in a window
    of whole epochs; within an epoch the arrivals are those of a Poisson
    process conditioned on that count. Tenants get equal shares.
  * rounds (``round_uploads``): one tenant's round of
    ``clients_per_round`` uploads, with distinct payload keys in a drawn
    order; from a pool smaller than the round, keys drawn with
    repetition.

Every upload has its own client id, and its sample count (the FedAvg
weight) is an integer drawn from the configuration's ``sample_counts``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.payloads import rng

_STREAM_OPEN = 2
_STREAM_ROUND = 3


@dataclasses.dataclass(frozen=True)
class Upload:
    cid: str
    tenant: str
    key: int        # payload pool index (payloads.make)
    weight: int     # sample count
    due: float = 0.0  # open loop: seconds after the schedule's origin


def tenant_names(config: dict) -> List[str]:
    return [f"t{i}" for i in range(config["tenants"])]


def _weights(r: np.random.Generator, config: dict, n: int) -> np.ndarray:
    lo, hi = config["sample_counts"]
    return r.integers(lo, hi + 1, size=n)


def per_epoch(traffic: dict) -> int:
    return int(round(traffic["rate_per_s"] * traffic["epoch_s"]))


def open_loop_epoch(seed: int, config: dict, traffic: dict,
                    epoch: int) -> List[Upload]:
    """The uploads due in epoch ``epoch`` (0, 1, ...), in due order."""
    n = per_epoch(traffic)
    span = traffic["epoch_s"]
    r = rng(seed, _STREAM_OPEN, epoch)
    # exponential gaps at the rate (the arithmetic of
    # repro.workload.arrivals.PoissonArrivals), scaled so that n + 1
    # gaps fill the epoch: n arrivals conditioned to fall inside it
    gaps = r.exponential(1.0 / traffic["rate_per_s"], size=n + 1)
    due = epoch * span + np.cumsum(gaps)[:n] * (span / gaps.sum())
    names = tenant_names(config)
    tenants = r.permutation(np.arange(n) % len(names))
    keys = r.integers(0, traffic["payload_pool"], size=n)
    weights = _weights(r, config, n)
    return [
        Upload(cid=f"u{epoch * n + i:09d}", tenant=names[tenants[i]],
               key=int(keys[i]), weight=int(weights[i]),
               due=float(due[i]))
        for i in range(n)
    ]


def round_uploads(seed: int, config: dict, traffic: dict,
                  tenant: int, round_index: int) -> List[Upload]:
    """One round of tenant ``tenant``: ``clients_per_round`` uploads, in
    the order they are sent, each with a client id of its own. Their
    payload keys are distinct where the pool holds a round; a smaller
    pool is drawn with repetition, so a round of large rows needs few of
    them on the host."""
    n = config["clients_per_round"]
    pool = traffic.get("payload_pool", n)
    r = rng(seed, _STREAM_ROUND, tenant, round_index)
    keys = r.permutation(pool)[:n] if pool >= n else \
        r.integers(0, pool, size=n)
    weights = _weights(r, config, n)
    name = tenant_names(config)[tenant]
    return [
        Upload(cid=f"{name}-r{round_index:06d}-c{i:04d}", tenant=name,
               key=int(keys[i]), weight=int(weights[i]))
        for i in range(n)
    ]
