"""A cell run end to end on the CPU at a tiny size: the real server,
drivers, load generator, readers and correctness check, with the Pallas
kernels in interpret mode. Only the tests take this path; the command
itself refuses to run without a TPU.

Faults planted underneath the timed path (``FAULTS``) must turn
``correct`` false."""
from __future__ import annotations

import json
import time

import jax.numpy as jnp
import numpy as np

from bench import harness

# tiny sizes: one int8 block of 2048 and a ragged one; 8 clients a round
TINY = {
    "int8": {"params": 3000, "tenants": 2, "clients_per_round": 8},
    "float32": {"params": 7000, "clients_per_round": 3},
}
TINY_TRAFFIC = {"payload_pool": 16, "rate_per_s": 40, "senders": 4}


def tiny_cell(name: str, payload: dict = None) -> harness.Cell:
    """The cell at a tiny size; with ``payload``, its configuration's
    payload replaced (a bf16 one: rounds checked as each ends)."""
    with open(harness.ROOT / "BENCHMARK.json") as f:
        cell = harness.resolve(json.load(f), name)
    kind = cell.config["payload"]["kind"]
    cell.config = dict(cell.config, **TINY[kind])
    if payload is not None:
        cell.config["payload"] = payload
    # three int8 rows per streamed block, so rounds fold several blocks
    cell.config["service"] = dict(cell.config["service"],
                                  stream_chunk_bytes=3 * 4200)
    cell.traffic = dict(cell.traffic, **{
        k: v for k, v in TINY_TRAFFIC.items() if k in cell.traffic})
    return cell


def rehearse(name: str, tmp_path, seconds: float = 1.0,
             trace: bool = False, payload: dict = None) -> dict:
    started = time.monotonic()
    device, peaks = harness.device_info(1, require_tpu=False)
    ctx = harness.Context(cell=tiny_cell(name, payload), seed=2**31 + 77,
                          seconds=seconds, trace=trace, started=started,
                          trace_dir=str(tmp_path))
    return harness.run_cell(ctx, device, peaks)


# a payload whose rounds are checked as each ends
BF16 = {"kind": "bfloat16"}


def rehearse_keeping_run(name: str, tmp_path, monkeypatch, **kw):
    """``rehearse``, and the ``Run`` its result line was read from."""
    runs = []
    result = harness.result
    monkeypatch.setattr(harness, "result", lambda run, device: (
        runs.append(run) or result(run, device)))
    return rehearse(name, tmp_path, **kw), runs[0]


def checked_as_each_ended(run) -> bool:
    """Every window round was checked as it ended and kept no fused
    vector, and each check's time is left out of the window."""
    window = run.window_rounds()
    return bool(window) and len(run.paused) == len(window) and all(
        r.checked is not None and r.fused is None for r in window)


def _state_unchanged(orig):
    def fold_block(self, state, payload, weights, scale=None, **kw):
        return tuple(state)
    return fold_block


def _half_batch(orig):
    def fuse_stream(self, fusion, blocks, *args, **kw):
        def every_other_row():
            seen = 0
            for block, w, *rest in blocks:
                w = np.array(w, np.float32)
                w[(seen + np.arange(len(w))) % 2 == 1] = 0.0
                seen += len(w)
                yield (block, w, *rest)
        return orig(self, fusion, every_other_row(), *args, **kw)
    return fuse_stream


def _answer_altered(orig):
    def finalize(self, state):
        out = jnp.asarray(orig(self, state))   # numpy on the mesh engine
        return out.at[0].add(1e-4 * jnp.max(jnp.abs(out)))
    return finalize


# name -> [(class, method, wrapper)]: a fold step that returns its state
# unchanged; every other row of a round left out, the mean taken over
# the rest, on either engine; one value of the answer altered where it
# is produced
FAULTS = {
    "state_unchanged": [("FedAvg", "fold_block", _state_unchanged)],
    "half_batch": [("LocalEngine", "fuse_stream", _half_batch),
                   ("DistributedEngine", "fuse_stream", _half_batch)],
    "answer_altered": [("FedAvg", "finalize", _answer_altered)],
}


def plant(monkeypatch, fault: str) -> None:
    from repro.core.distributed import DistributedEngine
    from repro.core.fusion.averaging import FedAvg
    from repro.core.local import LocalEngine

    classes = {"FedAvg": FedAvg, "LocalEngine": LocalEngine,
               "DistributedEngine": DistributedEngine}
    for owner, method, wrap in FAULTS[fault]:
        cls = classes[owner]
        monkeypatch.setattr(cls, method, wrap(getattr(cls, method)))


def force_mesh_plan(monkeypatch) -> None:
    """Make the planner pick the mesh engine wherever the service has a
    mesh: at a rehearsal's size it would fold on one chip."""
    from repro.core.planner import Planner

    plan = Planner.plan

    def on_the_mesh(self, load, fusion, warm_engines=()):
        plans = [p for p in self.candidate_plans(load, fusion, warm_engines)
                 if p.feasible and p.engine != "local"]
        if self.n_devices > 1 and plans:
            return min(plans, key=lambda p: p.est_seconds)
        return plan(self, load, fusion, warm_engines)

    monkeypatch.setattr(Planner, "plan", on_the_mesh)
