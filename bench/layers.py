"""The program's own spans in a traced run, and what they say of its
layers, for the per-layer readers in ``bench/metrics/``.

The program marks its layers with ``repro.*`` spans
(``src/repro/utils/spans.py``), which land in the profile of the traced
sub-window beside the device's operations. ``spans(run)`` reads them
back from that profile, each with the thread it ran on and its stats
(``tenant``, ``round``, ``client``, ``n``, ...), on the monotonic clock
that ``tracing.reduce`` puts the device's intervals on. A span's self
time is its time less what its child spans on the same thread cover.

A close runs from a round's close condition to its fused vector on the
host (``round_close_ms``). Only closes wholly inside the traced
sub-window count, as in ``device_idle_in_close_pct``. A round's spans
are those whose ``tenant`` stat is the round's tenant and which overlap
its close: a tenant has at most one round running.

Every reader returns None where the trace holds no program span, as the
trace of a program from before them does not.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from bench import tracing

PREFIX = "repro."
ROUND = "repro.round"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Span:
    """One program span, on the monotonic clock."""

    name: str                  # with its prefix: ``repro.engine.step``
    start: float
    end: float
    thread: Tuple[str, int]    # (host plane, line): the thread it ran on
    stats: Dict[str, object]
    own: List[Interval] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def read_spans(path: str, start_mark: float) -> List[Span]:
    """The ``repro.*`` spans of one profile, with their self intervals
    (``own``). ``start_mark`` is the monotonic second of the session's
    start mark (``tracing.START``), which places the profile's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Span] = []
    at_mark = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                a = ev.start_ns * 1e-9
                if ev.name == tracing.START:
                    at_mark = a
                elif ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, a, a + ev.duration_ns * 1e-9,
                                    (plane.name, index), _stats(ev)))
    if at_mark is None:
        return []
    offset = at_mark - start_mark
    for s in out:
        s.start -= offset
        s.end -= offset
    _own(out)
    return out


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def _own(spans: List[Span]) -> None:
    """Set each span's self intervals: the parts of it that no child
    span on its thread covers. Spans on one thread nest."""
    by_thread: Dict[Tuple[str, int], List[Span]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for line in by_thread.values():
        line.sort(key=lambda s: (s.start, -s.end))
        children: Dict[int, List[Span]] = {id(s): [] for s in line}
        stack: List[Span] = []
        for s in line:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack and s.end <= stack[-1].end:
                children[id(stack[-1])].append(s)
            stack.append(s)
        for s in line:
            at = s.start
            for c in children[id(s)]:
                if c.start > at:
                    s.own.append((at, c.start))
                at = max(at, c.end)
            if s.end > at:
                s.own.append((at, s.end))


def spans(run) -> List[Span]:
    """The program spans of a traced run, or [] where the run was not
    traced or the program emitted none."""
    if run.trace is None or not run.ctx.trace_dir:
        return []
    return _spans_of(tracing.Profile(run.ctx.trace_dir).path(),
                     run.trace.window[0])


# every reader of a run asks for the same profile: read it once
_spans_of = functools.lru_cache(maxsize=1)(read_spans)


# -- a round's close -------------------------------------------------------


def traced_closes(run) -> List:
    """The rounds whose close lies wholly inside the traced
    sub-window."""
    lo, hi = run.trace.window
    return [r for r in run.rounds
            if r.closed is not None and r.on_host is not None
            and lo <= r.closed < r.on_host <= hi]


def self_time(found: Iterable[Span], names: Iterable[str], lo: float,
              hi: float, tenant: str) -> float:
    """Seconds of ``[lo, hi]`` that the ``tenant``'s spans named
    ``names`` cover in their self time."""
    names = set(names)
    return sum(tracing.covered(s.own, lo, hi) for s in found
               if s.name in names and s.stats.get("tenant") == tenant
               and s.start < hi and s.end > lo)


def close_self_ms(run, names: Iterable[str]) -> Optional[float]:
    """Mean over the traced closes of the self time, inside the close,
    of the round's spans named ``names``."""
    found = spans(run)
    if not found:
        return None
    names = list(names)
    vals = [self_time(found, names, r.closed, r.on_host, r.tenant)
            for r in traced_closes(run)]
    return 1e3 * float(np.mean(vals)) if vals else None


def close_slot_wait_ms(run) -> Optional[float]:
    """Mean over the traced closes of the part of the close before the
    round's ``repro.round`` span opened: the round waiting for a running
    slot after its last upload had landed."""
    found = [s for s in spans(run) if s.name == ROUND]
    if not found:
        return None
    vals = []
    for r in traced_closes(run):
        opened = [s.start for s in found
                  if s.stats.get("tenant") == r.tenant
                  and s.start < r.on_host and s.end > r.closed]
        if opened:
            vals.append(min(max(max(opened) - r.closed, 0.0),
                            r.on_host - r.closed))
    return 1e3 * float(np.mean(vals)) if vals else None


# -- an upload ---------------------------------------------------------------


def _in_window(run, name: str) -> List[Span]:
    found = spans(run)
    if not found:
        return []
    lo, hi = run.trace.window
    return [s for s in found
            if s.name == name and lo <= s.start and s.end <= hi]


def upload_span_ms(run, name: str) -> Optional[float]:
    """Mean seconds, in ms, of the front-end's spans ``name`` (one per
    upload) wholly inside the traced sub-window."""
    found = _in_window(run, name)
    if not found:
        return None
    return 1e3 * float(np.mean([s.seconds for s in found]))


def commit_ms(run, queued: bool) -> Optional[float]:
    """Over the uploads of the ``repro.ingest.commit`` spans wholly
    inside the traced sub-window, in ms: the mean wait for their
    batch's store commit (the span's time), or with ``queued`` the mean
    wait from enqueue to the committer's drain (the span's
    ``queue_wait_s`` stat, summed over its batch)."""
    found = _in_window(run, "repro.ingest.commit")
    n = sum(int(s.stats.get("n", 0)) for s in found)
    if not n:
        return None
    if queued:
        total = sum(float(s.stats.get("queue_wait_s", 0.0)) for s in found)
    else:
        total = sum(s.seconds * int(s.stats.get("n", 0)) for s in found)
    return 1e3 * total / n
