"""Profiler sessions and the reduction of a trace to device numbers.

A traced run profiles one sub-window with JAX's profiler. The benchmark
marks the profiler's clock against ``time.monotonic`` with two
``TraceAnnotation`` marks, one as the session starts and one as it
stops, so that the intervals the harness times on the host (round
closes) and the device's busy intervals land on one clock. Its own host
spans (``span``) wrap its calls into the program and label the device's
idle gaps.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane. A host without a device plane (the CPU, in the
tests) has its XLA operations on host threads, marked by an ``hlo_op``
statistic; those stand in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import time
from typing import Dict, List, Sequence, Tuple

import jax

Interval = Tuple[float, float]

START, STOP = "bench.clock.start", "bench.clock.stop"
SPAN_PREFIX = "bench."


def span(name: str):
    """A host span of the benchmark's own, on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def _mark(name: str) -> float:
    """Emit an empty annotation; returns the monotonic second it sat
    at."""
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(name):
        pass
    return 0.5 * (t0 + time.monotonic())


class Profile:
    """One profiler session writing under ``directory``."""

    def __init__(self, directory: str):
        self.directory = directory
        self.marks: Dict[str, float] = {}
        self.running = False

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # Python calls would swamp the host
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.running = True
        self.marks[START] = _mark(START)

    def stop(self) -> None:
        if not self.running:
            return
        self.marks[STOP] = _mark(STOP)
        jax.profiler.stop_trace()
        self.running = False

    def path(self) -> str:
        found = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one xplane file, found {found}")
        return found[0]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


@dataclasses.dataclass
class Reduced:
    """A trace reduced to monotonic-clock intervals and op times."""

    window: Interval
    per_device: List[List[Interval]]     # merged busy intervals, by device
    ops: Dict[str, Tuple[int, float]]    # op name -> (count, seconds)
    spans: List[Tuple[str, float, float]]  # the benchmark's host spans
    drift_s: float                       # clock offset change, stop - start

    @property
    def busy(self) -> List[Interval]:
        """Intervals in which some device ran an operation."""
        return merge([iv for dev in self.per_device for iv in dev])

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        return sum(covered(dev, *self.window)
                   for dev in self.per_device) / len(self.per_device)

    def busy_within(self, intervals: Sequence[Interval]) -> float:
        busy = self.busy
        return sum(covered(busy, a, b) for a, b in intervals)

    def op_time(self, match) -> Tuple[int, float]:
        """(calls, seconds) over the ops whose name ``match`` accepts."""
        calls = secs = 0
        for name, (n, s) in self.ops.items():
            if match(name):
                calls += n
                secs += s
        return calls, secs

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """The device's idle gaps in the window, longest first, each
        labelled with the innermost benchmark span around its middle."""
        lo, hi = self.window
        busy = [iv for iv in self.busy if iv[1] > lo and iv[0] < hi]
        edges = [lo] + [x for a, b in busy for x in (a, b)] + [hi]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                mid = 0.5 * (a + b)
                around = [s for s in self.spans if s[1] <= mid <= s[2]]
                label = (min(around, key=lambda s: s[2] - s[1])[0]
                         if around else "outside benchmark spans")
                gaps.append((label, b - a))
        return sorted(gaps, key=lambda g: -g[1])

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        return {
            "device_ops": [[name, secs] for name, (_, secs) in ops],
            "idle_gaps": [[label, secs]
                          for label, secs in self.idle_gaps()[:top]],
        }


def op_name(name: str) -> str:
    """An XLA op's instruction name: the TPU's trace names each device
    op by its whole HLO line, ``%weighted_sum_pallas.1 = f32[...] ...``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def reduce(path: str, marks: Dict[str, float]) -> Reduced:
    """Read one ``.xplane.pb`` written by a ``Profile`` session."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    found: Dict[str, float] = {}
    spans: List[Tuple[str, float, float]] = []
    host_ops: List[Tuple[str, float, float]] = []
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if on_device:
                    if line.name == "XLA Ops":
                        device_ops.setdefault(plane.name, []).append(
                            (op_name(ev.name), a, b))
                    continue
                if ev.name in marks:
                    found[ev.name] = a
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):], a, b))
                elif "hlo_op" in _stats(ev):
                    host_ops.append((ev.name, a, b))
    missing = set(marks) - set(found)
    if missing:
        raise RuntimeError(f"clock marks missing from the trace: {missing}")
    offset = found[START] - marks[START]
    drift = (found[STOP] - marks[STOP]) - offset
    per_device = list(device_ops.values()) or [host_ops]
    ops: Dict[str, Tuple[int, float]] = {}
    for events in per_device:
        for name, a, b in events:
            n, s = ops.get(name, (0, 0.0))
            ops[name] = (n + 1, s + (b - a))
    return Reduced(
        window=(marks[START], marks[STOP]),
        per_device=[merge([(a - offset, b - offset) for _, a, b in events])
                    for events in per_device],
        ops=ops,
        spans=[(n, a - offset, b - offset) for n, a, b in spans],
        drift_s=drift,
    )
