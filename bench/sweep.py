"""Find an open-loop cell's knee: the highest offered rate at which the
backlog of unacknowledged uploads does not grow and no upload fails.

    python3 bench/sweep.py --workload <cell> --rates 120,240,360 --seconds 20 --seed 7

Runs the cell once per rate in one process (the rate in its traffic file
replaced) and prints one JSON line per rate: uploads due and failed,
latency percentiles from the due time, the generator's lag, and the
backlog (uploads due but not yet acknowledged) over the first and the
last fifth of the window, and the uploads left in the store unfolded.
Give one rate a process where the higher rates may exhaust the host's
memory. The cell's traffic file then fixes its rate at
about four fifths of the knee; PERF.md records the sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]


def backlog(run, at: float) -> int:
    """Uploads due by ``at`` and not acknowledged by then."""
    return sum(1 for u in run.uploads if u.due is not None and u.due <= at
               and (u.acked is None or u.acked > at))


def summary(run, rate: float) -> dict:
    w0, w1 = run.window
    ups = run.window_uploads()
    lat = np.array([u.acked - u.due for u in ups if u.acked is not None])
    lag = np.array([u.sent - u.due for u in ups])
    span = w1 - w0
    first = [backlog(run, w0 + f * span) for f in np.linspace(0, 0.2, 9)]
    last = [backlog(run, w0 + f * span) for f in np.linspace(0.8, 1.0, 9)]
    return {
        "rate_per_s": rate, "due": len(ups),
        "failed": sum(1 for u in ups if u.acked is None),
        "latency_ms": {q: 1e3 * float(np.percentile(lat, q))
                       for q in (50, 90, 99)} if lat.size else None,
        "gen_lag_p99_ms": 1e3 * float(np.percentile(lag, 99))
        if lag.size else None,
        "backlog_first_fifth": float(np.mean(first)),
        "backlog_last_fifth": float(np.mean(last)),
        "rounds": len(run.window_rounds()),
        # acknowledged but never folded: the rounds fell behind
        "left_in_store": sum(len(ids) for ids in run.leftover.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from bench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(manifest, args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    from repro.utils.jitcache import enable_persistent_cache

    enable_persistent_cache()
    driver = harness.load_module(cell.driver_path)
    traffic = cell.traffic
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(traffic, rate_per_s=rate)
        ctx = harness.Context(cell=cell, seed=args.seed,
                              seconds=args.seconds, trace=False,
                              started=time.monotonic())
        print(json.dumps(summary(driver.run(ctx), rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
