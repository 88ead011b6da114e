"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a workload of ``BENCHMARK.json``. The run makes its
payloads and schedule from ``--seed``, warms up every shape the cell
uses, measures for ``--seconds`` seconds, checks every round started in
the window against the float64 reference, and prints one JSON result
line last on standard output (with ``--trace 1``: the per-layer metrics
of a profiled sub-window instead of the end-to-end metrics). It exits
with 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or a device kind missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout and the program, in place of this directory
sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(manifest, args.workload)
    try:
        device, peaks = harness.device_info(cell.chips)
    except harness.NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    from repro.utils.jitcache import enable_persistent_cache

    enable_persistent_cache()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        ctx = harness.Context(cell=cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              started=STARTED, trace_dir=trace_dir)
        line = harness.run_cell(ctx, device, peaks)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
