"""Readings for the limit on ``max_rel_err``: the program's and the
control's, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it drives the cell as ``bench/run.py`` does (no trace),
then judges the rounds started in the window twice: the program's fused
vectors, and the control put in their place, which is the same FedAvg
with products one precision step lower (``reference.control_chunk``).
It prints one JSON line per seed. The benchmark's own runs never run
this; PERF.md gives the readings each limit was set from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, reference

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(manifest, args.workload)
    try:
        device, peaks = harness.device_info(cell.chips)
    except harness.NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    from repro.utils.jitcache import enable_persistent_cache

    enable_persistent_cache()
    driver = harness.load_module(cell.driver_path)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, started=time.monotonic())
        run = driver.run(ctx)
        run.peaks = peaks
        program = harness.checks(run)
        rounds, _ = harness.judged(run)
        control = reference.check_rounds(seed, cell.config, rounds,
                                         control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "device": device,
            "rounds": len(rounds), "program": program,
            "control_max_rel_err": control["max_rel_err"],
            "control_rel_errors": control["rel_errors"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
