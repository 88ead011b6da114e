"""FL servers: orchestrate rounds through the AggregationService.

``FederatedServer`` is deliberately thin — client selection, broadcast,
collect, aggregate, apply — because the aggregation SERVICE is the
paper's object of study. The server consumes RoundReports (which
engine ran, monitor state, seamless-transition routing) and exposes
them to benchmarks.

``EdgeAggregatorServer`` is the Edge deployment composition: one
``repro.serving.IngestServer`` (HTTP uploads with admission control)
feeding one ``UpdateStore``, with rounds admitted through a
``FairRoundScheduler`` on one shared ``AggregationService`` — the
object ``repro.launch.serve`` runs and ``benchmarks/ingest_service.py``
measures.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.service import (
    AggregationService,
    FairRoundScheduler,
    RoundReport,
)
from repro.data.loader import FederatedLoader
from repro.fl.client import Client
from repro.models.base import Model

PyTree = Any


@dataclasses.dataclass
class RoundResult:
    round_idx: int
    mean_client_loss: float
    report: RoundReport
    n_selected: int


class FederatedServer:
    def __init__(
        self,
        model: Model,
        clients: Sequence[Client],
        loader: FederatedLoader,
        service: AggregationService,
        rng_seed: int = 0,
        clients_per_round: Optional[int] = None,
    ):
        self.model = model
        self.clients = list(clients)
        self.loader = loader
        self.service = service
        self.rng = np.random.default_rng(rng_seed)
        self.clients_per_round = clients_per_round or len(self.clients)
        self.params = model.init(jax.random.PRNGKey(rng_seed))
        self.results: List[RoundResult] = []

    def run_round(self, round_idx: int) -> RoundResult:
        sel = self.rng.choice(
            len(self.clients), size=self.clients_per_round, replace=False
        )
        updates, weights, losses = [], [], []
        send_delta = any(self.clients[i].send_delta for i in sel)
        for i in sel:
            c = self.clients[i]
            batch_fn = lambda s, i=i: self.loader.client_batch(
                c.client_id, round_idx * 1000 + s
            )
            upd, loss = c.train_round(self.params, batch_fn, round_idx)
            updates.append(upd)
            weights.append(self.loader.client_weight(c.client_id))
            losses.append(loss)

        fused, report = self.service.aggregate(
            updates=updates, weights=weights, template=self.params,
        )
        if send_delta:
            # pseudo-gradient: apply fused delta to the global weights
            self.params = jax.tree_util.tree_map(
                lambda p, d: (
                    p.astype(jnp.float32) + d.astype(jnp.float32)
                ).astype(p.dtype),
                self.params, fused,
            )
        else:
            self.params = jax.tree_util.tree_map(
                lambda p, f: f.astype(p.dtype), self.params, fused
            )
        res = RoundResult(
            round_idx=round_idx,
            mean_client_loss=float(np.mean(losses)),
            report=report,
            n_selected=len(sel),
        )
        self.results.append(res)
        return res

    def run(self, n_rounds: int) -> List[RoundResult]:
        return [self.run_round(r) for r in range(n_rounds)]


class EdgeAggregatorServer:
    """The network-facing aggregator: HTTP ingest + fair round
    admission over ONE AggregationService.

    Composition, not new machinery: an ``IngestServer`` (token auth,
    rate limits, quota pre-checks, batched ``IngestQueue`` commits)
    lands uploads in ``service.store``; a ``FairRoundScheduler``
    admits rounds with weighted-fair tenant selection under a
    concurrency cap. ``tokens`` maps bearer token -> tenant.

        svc = AggregationService(fusion="fedavg", store=UpdateStore(),
                                 threshold_frac=1.0, monitor_timeout=5)
        with EdgeAggregatorServer(svc, {"tok-a": "appA"}) as edge:
            ...clients POST to edge.url...
            fused, report = edge.run_round("appA", expected_clients=48)

    ``frontend_kwargs`` pass through to ``IngestServer`` (rate, burst,
    queue_size, batch_max, read_timeout, max_body_bytes, ...);
    scheduler knobs are explicit."""

    def __init__(
        self,
        service: AggregationService,
        tokens: Dict[str, str],
        host: str = "127.0.0.1",
        port: int = 0,
        max_running: int = 2,
        weights: Optional[Dict[str, float]] = None,
        capacity_bytes: Optional[int] = None,
        **frontend_kwargs,
    ):
        # imported here: repro.fl must stay importable without the
        # serving layer's http machinery loaded for in-process use
        from repro.serving.frontend import IngestServer

        if service.store is None:
            raise ValueError(
                "EdgeAggregatorServer needs a store-backed service "
                "(AggregationService(store=UpdateStore(...)))"
            )
        self.service = service
        self.frontend = IngestServer(
            service.store, tokens, host=host, port=port,
            **frontend_kwargs,
        )
        self.scheduler = FairRoundScheduler(
            service, max_running=max_running, weights=weights,
            capacity_bytes=capacity_bytes,
        )

    @property
    def port(self) -> int:
        return self.frontend.port

    @property
    def url(self) -> str:
        return self.frontend.url

    def submit_round(self, tenant: str, **aggregate_kwargs):
        """Queue one round through the fair scheduler (Future of
        ``(fused, RoundReport)``)."""
        return self.scheduler.submit(
            tenant, from_store=True, **aggregate_kwargs
        )

    def run_round(self, tenant: str, **aggregate_kwargs):
        """One tenant's round, synchronously."""
        return self.submit_round(tenant, **aggregate_kwargs).result()

    def run_rounds(
        self, tenants: Sequence[str], **aggregate_kwargs
    ) -> Dict[str, Tuple[PyTree, RoundReport]]:
        """A fair fan-out across tenants; waits for all."""
        futs = {t: self.submit_round(t, **aggregate_kwargs)
                for t in tenants}
        return {t: f.result() for t, f in futs.items()}

    def metrics(self) -> dict:
        """The front-end's counters (``/v1/healthz``), the rounds
        admitted and running, and ``slot_wait_s``: the summed wait of
        admitted rounds for a running slot."""
        out = self.frontend.metrics()
        sched = self.scheduler.stats()
        out["rounds_admitted"] = sched["admitted"]
        out["rounds_running"] = sched["running"]
        out["slot_wait_s"] = sched["slot_wait_s"]
        return out

    def close(self) -> None:
        self.scheduler.shutdown()
        self.frontend.close()

    def __enter__(self) -> "EdgeAggregatorServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
