"""A four-chip cell of bf16 updates rehearsed through the harness, with
a configuration that no cell of ``BENCHMARK.json`` uses: bf16 rows made
by parameter range (so each round is checked as it ends), 3 silos of
one tenant, a pool of 2 rows (smaller than a round), ``"mesh":
{"model": 4}``.
``harness.run_cell`` drives it on ``DistributedEngine``; the planner is
made to pick the mesh (``rehearsal.force_mesh_plan``), since at these
sizes it would fold on one chip.

    python3 bench/tests/mesh_rehearsal.py [--params N] [--seconds S]
        [--seeds A,B] [--faults] [--trace]

On the CPU, as the tests run it: ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. On a host with
four TPU chips it runs as it stands. One JSON line per run: the result
line's ``correct``, ``checks``, ``metrics`` and ``device``, and what the
rehearsal reads besides (``mesh``): whether a mesh step folded, each
device's peak bytes, the control's reading of the same rounds, and the
time each round's check took, with its threads. ``--faults`` adds a
run under each of ``rehearsal.FAULTS``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]

CHIPS = 4
STORE_CELL = "xdev_cnn46_int8.store_rounds"   # the metrics it reads,
WSUM = "wsum_roofline_pct"                     # and the bf16 kernel's share


def config(params: int) -> dict:
    return {
        "name": "mesh4_bf16_rehearsal",
        "params": params,
        "payload": {"kind": "bfloat16"},
        "precision": "bf16 updates; fp32 weighted fold, products at "
                     "Precision.HIGHEST",
        "fusion": "fedavg",
        "tenants": 1,
        "clients_per_round": 3,
        "sample_counts": [1000, 100000],
        "mesh": {"model": CHIPS},
        "service": {
            "threshold_frac": 1.0,
            "monitor_timeout": 300.0,
            # two rows a block: a round folds a full block and a ragged one
            "stream_chunk_bytes": 2 * 2 * params,
            "device_concurrency": 1,
        },
        "server": {"max_running": 2},
        "frontend": {},
        "round": {"async_round": True},
    }


TRAFFIC = {
    "driver": "store_writer",
    "payload_pool": 2,
    "warmup_rounds": 1,
    "trace_rounds": 2,
    "trace_min_s": 0,
}


def cell(params: int):
    from bench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    store = harness.resolve(manifest, STORE_CELL)
    wsum = [m for m in manifest["per_layer"] if m["name"] == WSUM]
    return harness.Cell(
        name="mesh4_bf16_rehearsal.store_rounds", chips=CHIPS,
        config=config(params), traffic=dict(TRAFFIC),
        driver_path=harness.BENCH / "drivers" / "store_writer.py",
        end_to_end=store.end_to_end, per_layer=store.per_layer + wsum)


def rehearse(params: int, seed: int, seconds: float, trace: bool,
             fault: str = "") -> dict:
    """One run through ``harness.run_cell``, with ``fault`` planted."""
    import jax
    import pytest

    from bench import harness, payloads, reference, sut
    from bench.tests import rehearsal

    seen: dict = {"systems": [], "check_s": []}
    with pytest.MonkeyPatch.context() as mp:
        rehearsal.force_mesh_plan(mp)
        if fault:
            rehearsal.plant(mp, fault)
        init, result, check = sut.System.__init__, harness.result, \
            reference.check_round

        def system_init(self, *a, **kw):
            init(self, *a, **kw)
            seen["systems"].append(self)

        def keep_run(run, device):
            seen["run"] = run
            return result(run, device)

        def timed_check(*a, **kw):
            t0 = time.perf_counter()
            out = check(*a, **kw)
            seen["check_s"].append(time.perf_counter() - t0)
            return out

        mp.setattr(sut.System, "__init__", system_init)
        mp.setattr(harness, "result", keep_run)
        mp.setattr(reference, "check_round", timed_check)
        device, peaks = harness.device_info(
            CHIPS, require_tpu=jax.default_backend() == "tpu")
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
            ctx = harness.Context(cell=cell(params), seed=seed,
                                  seconds=seconds, trace=trace,
                                  started=time.monotonic(), trace_dir=d)
            line = harness.run_cell(ctx, device, peaks)
    run, system = seen["run"], seen["systems"][0]
    pdim = params + (-params) % CHIPS
    rounds, _ = harness.judged(run)
    control = reference.check_rounds(
        seed, run.ctx.cell.config, [(inc, None) for inc, _, _ in rounds],
        control=True) if rounds else {"max_rel_err": None}
    line["mesh"] = {
        "fault": fault,
        "local_compiles": system.service.local.cache.misses,
        "mesh_compiles": system.service.distributed.cache.misses,
        "mesh_step_folded": any(
            args and args[0] == ((2, pdim), "bfloat16")
            for args in run.fold_steps),
        "fold_steps": run.fold_steps,
        "rounds_checked": sum(r.checked is not None for r in run.rounds),
        "vectors_kept": sum(r.fused is not None for r in run.rounds),
        "checks_paused": len(run.paused),
        "memory_peak_bytes_per_device": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:CHIPS]],
        "control_max_rel_err": control["max_rel_err"],
        "check_s": seen["check_s"],
        "check_threads": payloads.workers(),
    }
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--params", type=int, default=10_007)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", default=str(2**31 + 91))
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args(argv)

    from bench.tests import rehearsal

    seeds = [int(s) for s in args.seeds.split(",")]
    runs = [(seed, False, "") for seed in seeds]
    if args.trace:
        runs.append((seeds[0], True, ""))
    if args.faults:
        runs += [(seeds[0], False, f) for f in sorted(rehearsal.FAULTS)]
    for seed, trace, fault in runs:
        line = rehearse(args.params, seed, args.seconds, trace, fault)
        line["mesh"]["seed"] = seed
        line["mesh"]["trace"] = trace
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
