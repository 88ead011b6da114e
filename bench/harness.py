"""The benchmark harness: finds a cell's files by name, runs the cell's
driver, checks what the timed rounds produced, reads the metrics and
prints the result line.

Everything specific lives in a file of its own, found by name:

  BENCHMARK.json            cells and metrics
  bench/configs/<c>.json    a deployment
  bench/traffic/<t>.json    a traffic mix; its ``driver`` key names...
  bench/drivers/<d>.py      ...the code that offers it: ``run(ctx) -> Run``
  bench/metrics/<m>.py      one reader per metric: ``read(run) -> value``

A reader returns ``None`` where it finds nothing to read, and the metric
is then left out of the line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import resource
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# the comparisons that decide ``correct``: name -> limit (PERF.md gives
# the readings each limit was set from)
LIMITS = {
    "max_rel_err": 1e-6,
    "lost_uploads": 0,
    "rounds_failed": 0,
}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver_path: Path
    end_to_end: List[dict]    # this cell's end-to-end metric entries
    per_layer: List[dict]     # this cell's per-layer metric entries


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(manifest: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    bench = root / "bench"
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        driver_path=bench / "drivers" / f"{traffic['driver']}.py",
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name)],
    )


# -- what a run records --------------------------------------------------------


@dataclasses.dataclass
class UploadRec:
    """One upload or store write, on the shared monotonic clock."""

    cid: str
    tenant: str
    key: int
    weight: int
    due: Optional[float]     # open loop: when it was scheduled
    sent: float
    acked: Optional[float]   # None: never acknowledged
    error: Optional[str] = None


@dataclasses.dataclass
class RoundRec:
    tenant: str
    started: float                    # submitted to the edge server
    on_host: Optional[float] = None   # fused vector copied to the host
    closed: Optional[float] = None    # its last included upload acked
    included: List[str] = dataclasses.field(default_factory=list)
    phase: Dict[str, float] = dataclasses.field(default_factory=dict)
    n_clients: int = 0
    fused: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    started: float          # process start, monotonic
    trace_dir: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What a driver hands back once the program's state is freed."""

    ctx: Context
    setup_s: float
    window: Tuple[float, float]
    uploads: List[UploadRec]
    rounds: List[RoundRec]
    counters: Dict[str, Tuple[float, float]]   # name -> (at start, at end)
    leftover: Dict[str, List[str]]   # tenant -> ids left in the store
    memory_peak_bytes: int = 0
    fold_steps: List[list] = dataclasses.field(default_factory=list)
    trace: Optional[object] = None   # tracing.Reduced of the traced run
    peaks: Optional[dict] = None     # this device's row of peaks.json

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.window[0] <= t < self.window[1]

    def window_rounds(self) -> List[RoundRec]:
        """Rounds started in the window. Each runs to its end, so how
        many there are does not hang on where the window cuts the
        last."""
        return [r for r in self.rounds if self.in_window(r.started)]

    def window_uploads(self) -> List[UploadRec]:
        """Uploads due (or, without a schedule, sent) in the window."""
        return [u for u in self.uploads
                if self.in_window(u.due if u.due is not None else u.sent)]

    def counter(self, name: str) -> Optional[float]:
        if name not in self.counters:
            return None
        a, b = self.counters[name]
        return b - a


def close_times(rounds: Sequence[RoundRec], uploads: Sequence[UploadRec]):
    """Set each round's close condition: the ack of its last included
    upload."""
    acked = {u.cid: u.acked for u in uploads}
    for r in rounds:
        times = [acked.get(cid) for cid in r.included]
        if times and all(t is not None for t in times):
            r.closed = max(times)


# -- correctness ---------------------------------------------------------------


def judged(run: Run) -> Tuple[list, int]:
    """The rounds started in the window as ``(included (key, weight)
    pairs, fused)``, and the count of rounds that failed."""
    by_cid = {u.cid: u for u in run.uploads}
    failed = sum(1 for r in run.rounds if r.error is not None)
    out = []
    for r in run.window_rounds():
        if r.error is not None:
            continue
        if r.n_clients != len(r.included) \
                or any(c not in by_cid for c in r.included):
            failed += 1
            continue
        out.append(([(by_cid[c].key, by_cid[c].weight)
                     for c in r.included], r.fused))
    return out, failed


def checks(run: Run) -> Dict[str, float]:
    """The compared numbers: each is judged against ``LIMITS``."""
    from bench import reference

    rounds, failed = judged(run)
    errs = reference.check_rounds(run.ctx.seed, run.ctx.cell.config, rounds)
    return {
        "max_rel_err": errs["max_rel_err"],
        "lost_uploads": lost_uploads(run),
        "rounds_failed": failed,
    }


def lost_uploads(run: Run) -> int:
    """Acknowledged uploads that no round folded and the store no longer
    holds, plus uploads folded twice or never sent."""
    folded: Dict[str, int] = {}
    for r in run.rounds:
        for cid in r.included:
            folded[cid] = folded.get(cid, 0) + 1
    sent = {u.cid for u in run.uploads}
    left = {cid for ids in run.leftover.values() for cid in ids}
    lost = sum(1 for u in run.uploads if u.acked is not None
               and u.cid not in folded and u.cid not in left)
    twice = sum(n - 1 for n in folded.values())
    unknown = sum(1 for cid in folded if cid not in sent)
    return lost + twice + unknown


def correct(values: Dict[str, float]) -> bool:
    return all(values[k] <= LIMITS[k] for k in LIMITS)


# -- metrics and the result line -------------------------------------------------


def read_metrics(run: Run, entries: Sequence[dict]) -> Dict[str, dict]:
    """Each metric's reader, ``bench/metrics/<name>.py``; a metric whose
    reader finds nothing is left out."""
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(run: Run, device: dict) -> dict:
    """The result line, with the compared numbers last."""
    values = checks(run)
    cell = run.ctx.cell
    entries = cell.per_layer if run.ctx.trace else cell.end_to_end
    window = run.window_uploads()
    line = {
        "correct": correct(values),
        "attempted": len(window),
        "failed": sum(1 for u in window if u.acked is None),
        "metrics": read_metrics(run, entries),
        "device": dict(device, memory_peak_bytes=run.memory_peak_bytes),
    }
    if run.ctx.trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace.busy_s
        line["device"]["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {k: {"value": values[k], "limit": LIMITS[k]}
                      for k in LIMITS}
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


# -- the device ---------------------------------------------------------------


class NoDevice(RuntimeError):
    """No accelerator fit for the cell: exit without a result."""


def device_info(chips: int, peaks_file: Path = BENCH / "peaks.json",
                require_tpu: bool = True) -> Tuple[dict, Optional[dict]]:
    """``(device, peaks)`` for the devices JAX finds; with
    ``require_tpu``, raises ``NoDevice`` unless there are ``chips`` TPUs
    of a kind that ``peaks.json`` lists."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    table = load_json(peaks_file)["devices"]
    if require_tpu:
        check_device(info, chips, table)
    return info, table.get(info["kind"])


def check_device(info: dict, chips: int, table: dict) -> None:
    """Raise ``NoDevice`` unless ``info`` names ``chips`` or more TPUs
    of a kind in the peaks ``table``."""
    if info["platform"] != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{info['count']}")
    if info["kind"] not in table:
        raise NoDevice(f"device kind {info['kind']!r} is not in the "
                       "peaks table")


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where JAX reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def log_host_memory(stage: str) -> None:
    """The peak resident memory so far of this process and of its ended
    children (the load generator), on standard error."""
    self_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    child_kb = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    print(f"host peak rss {stage}: harness {self_kb / 2**20:.2f} GiB, "
          f"load generator {child_kb / 2**20:.2f} GiB", file=sys.stderr)


def run_cell(ctx: Context, device: dict, peaks: Optional[dict]) -> dict:
    """Drive the cell, free the program, then check and read it."""
    driver = load_module(ctx.cell.driver_path)
    run = driver.run(ctx)
    run.peaks = peaks
    gc.collect()
    log_host_memory("after the window")
    line = result(run, device)
    log_host_memory("after the check")
    return line
