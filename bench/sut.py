"""The system under test, built from a configuration: one
``EdgeAggregatorServer`` (HTTP ingest and the fair round scheduler) over
one ``AggregationService`` and its ``UpdateStore``, which is the served
path ``EdgeAggregatorServer -> FairRoundScheduler -> AggregationService
-> LocalEngine -> Pallas fold kernels``.

The benchmark calls into it only here and in the drivers' uploads, and
wraps each call in a span of its own (``tracing.span``).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from bench import harness, schedule, tracing
from bench.harness import RoundRec, UploadRec

from repro.core import DEFAULT_TENANT, AggregationService, UpdateStore
from repro.fl import EdgeAggregatorServer


class RecordingStore(UpdateStore):
    """An ``UpdateStore`` that keeps, per tenant, the ids each
    arrival-driven round consumed: the served path's own account of
    which uploads a round folded (``AggregationService`` removes exactly
    those once the fold is done)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._consumed_lock = threading.Lock()
        self._consumed: Dict[str, List[List[str]]] = {}

    def remove(self, client_ids, versions=None, tenant=DEFAULT_TENANT):
        ids = list(client_ids)
        with self._consumed_lock:
            self._consumed.setdefault(tenant, []).append(ids)
        super().remove(ids, versions=versions, tenant=tenant)

    def consumed(self, tenant: str) -> List[List[str]]:
        with self._consumed_lock:
            return list(self._consumed.get(tenant, []))


class System:
    """The served path for one configuration."""

    def __init__(self, config: dict):
        self.config = config
        self.tenants = schedule.tenant_names(config)
        self.tokens = {t: f"tok-{t}" for t in self.tenants}
        self.store = RecordingStore()
        self.service = AggregationService(
            fusion=config["fusion"], store=self.store, **config["service"])
        self.edge = EdgeAggregatorServer(
            self.service, {tok: t for t, tok in self.tokens.items()},
            **config["server"], **config["frontend"])

    @property
    def port(self) -> int:
        return self.edge.port

    def submit(self, tenant: str) -> Tuple[RoundRec, object, int]:
        """Queue one round of ``tenant``; ``finish`` waits for it. A
        tenant's rounds must not overlap."""
        consumed = len(self.store.consumed(tenant))
        rec = RoundRec(tenant=tenant, started=time.monotonic())
        fut = self.edge.submit_round(
            tenant, expected_clients=self.config["clients_per_round"],
            **self.config["round"])
        return rec, fut, consumed

    def finish(self, pending: Tuple[RoundRec, object, int]) -> RoundRec:
        """Wait for the round and copy its fused vector to the host."""
        rec, fut, consumed = pending
        try:
            with tracing.span("round_wait"):
                fused, report = fut.result()
            with tracing.span("fetch"):
                rec.fused = np.asarray(fused)
            rec.on_host = time.monotonic()
            rec.phase = dict(report.phase_seconds)
            rec.n_clients = int(report.n_clients)
            after = self.store.consumed(rec.tenant)
            if len(after) != consumed + 1:
                raise RuntimeError(
                    f"round of {rec.tenant} consumed {len(after) - consumed}"
                    " batches of uploads, expected 1")
            rec.included = after[consumed]
        except Exception as exc:   # recorded: the check counts it failed
            rec.error = repr(exc)
        return rec

    def counters(self) -> Dict[str, float]:
        q = self.edge.frontend.queue.stats()
        return {
            "compiles": self.service.local.cache.misses,
            "committed": q["committed"],
            "batches": q["batches"],
        }

    def fold_steps(self) -> List[List[Tuple[tuple, str]]]:
        """The operands, ``(shape, dtype)``, of each fold step the
        engine compiled: the shapes its kernels run at."""
        out = []
        for fn in self.service.local.cache.executables().values():
            args = jax.tree_util.tree_leaves(getattr(fn, "args_info", ()))
            out.append([(tuple(a.shape), str(a.dtype)) for a in args])
        return out

    def leftover(self) -> Dict[str, List[str]]:
        return {t: self.store.client_ids(t) for t in self.tenants}

    def close(self) -> None:
        self.edge.close()


class Session:
    """What every driver shares: the system under test, the rounds and
    uploads recorded, the traced sub-window, the measured window, and
    the ``Run`` built from them. A driver brings only its traffic."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.system = System(ctx.cell.config)
        self.traced = TracedWindow(ctx, ctx.cell.traffic)
        self.rounds: List[RoundRec] = []
        self.uploads: List[UploadRec] = []
        self.stop = threading.Event()   # set at the window's end
        self.window: Optional[Tuple[float, float]] = None
        self._lock = threading.Lock()
        self._done = {t: 0 for t in self.system.tenants}
        self._state: dict = {}

    def record(self, rounds: Sequence[RoundRec],
               uploads: Sequence[UploadRec] = ()) -> None:
        """Keep finished rounds and their uploads. Only rounds started
        in the window are judged, so the others' fused vectors go at
        once."""
        with self._lock:
            for rec in rounds:
                if self.window is None or not (
                        self.window[0] <= rec.started < self.window[1]):
                    rec.fused = None
                self.rounds.append(rec)
                self._done[rec.tenant] += 1
            self.uploads.extend(uploads)
        for _ in rounds:
            self.traced.round_done()

    def tenant_rounds(self, tenant: str,
                      land: Callable[[int], Sequence[UploadRec]] = None
                      ) -> None:
        """One tenant's rounds back to back until the window's end:
        submit, ``land(r)`` its uploads where the driver writes them
        itself, wait for the fused vector."""
        r = 0
        while not self.stop.is_set():
            pending = self.system.submit(tenant)
            ups = land(r) if land is not None else ()
            rec = self.system.finish(pending)
            self.record([rec], ups)
            if rec.error is not None:
                return
            r += 1

    def wait_warm(self, rounds: int) -> None:
        """Until every tenant has finished ``rounds`` rounds, or one
        failed."""
        while True:
            with self._lock:
                if min(self._done.values()) >= rounds or \
                        any(r.error for r in self.rounds):
                    return
            time.sleep(0.01)

    def measure(self, w0: float,
                drive: Callable[[float], None] = None) -> None:
        """Measure the window ``[w0, w0 + seconds)``: the system's
        counters at both ends, the traced sub-window from its start.
        ``drive(w1)`` runs the traffic in this thread until ``w1``;
        without it the driver's own threads do, and this one waits."""
        w1 = w0 + self.ctx.seconds
        self.window = (w0, w1)
        time.sleep(max(0.0, w0 - time.monotonic()))
        before = self.system.counters()
        self.traced.start()
        if drive is not None:
            drive(w1)
        while time.monotonic() < w1:
            time.sleep(min(0.05, max(0.0, w1 - time.monotonic())))
            self.traced.stop_if_due()
        after = self.system.counters()
        self.traced.stop()
        self.stop.set()
        self._state["counters"] = {k: (before[k], after[k]) for k in before}

    def read_state(self) -> None:
        """What the Run needs from the system while it still holds its
        state: call once the traffic has stopped."""
        self._state.update(
            memory_peak_bytes=harness.memory_peak_bytes(),
            leftover=self.system.leftover(),
            fold_steps=self.system.fold_steps())

    def close(self) -> None:
        self.stop.set()
        self.system.close()

    def run(self) -> "harness.Run":
        harness.close_times(self.rounds, self.uploads)
        return harness.Run(
            ctx=self.ctx, setup_s=self.window[0] - self.ctx.started,
            window=self.window, uploads=self.uploads, rounds=self.rounds,
            trace=self.traced.reduced(), **self._state)


class TracedWindow:
    """The traced sub-window of a ``--trace 1`` run: from the window's
    start until ``trace_rounds`` rounds have reached the host and
    ``trace_min_s`` seconds have passed, or the window's end."""

    def __init__(self, ctx, traffic: dict):
        self.profile = tracing.Profile(ctx.trace_dir) if ctx.trace else None
        self.rounds_needed = int(traffic.get("trace_rounds", 1))
        self.min_s = float(traffic.get("trace_min_s", 0.0))
        self._lock = threading.Lock()
        self._rounds = 0
        self._t0: Optional[float] = None

    def start(self) -> None:
        if self.profile is not None:
            with self._lock:
                self._rounds = 0
            self.profile.start()
            self._t0 = time.monotonic()

    def round_done(self) -> None:
        with self._lock:
            self._rounds += 1

    def due(self) -> bool:
        if self.profile is None or not self.profile.running:
            return False
        with self._lock:
            rounds = self._rounds
        return rounds >= self.rounds_needed and \
            time.monotonic() - self._t0 >= self.min_s

    def stop(self) -> None:
        if self.profile is not None:
            self.profile.stop()

    def stop_if_due(self) -> None:
        if self.due():
            self.stop()

    def reduced(self):
        if self.profile is None or tracing.STOP not in self.profile.marks:
            return None
        return tracing.reduce(self.profile.path(), self.profile.marks)


def update_of(config: dict, payload):
    """A payload (``bench.payloads.make``) as the program's upload
    type: an fp32 vector, or a ``CompressedUpdate``."""
    if config["payload"]["kind"] != "int8":
        return payload
    from repro.core.compress import CompressedUpdate

    codes, scales = payload
    return CompressedUpdate(codes=codes, scales=scales,
                            dim=int(config["params"]))
