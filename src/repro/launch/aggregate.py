"""Standalone aggregation driver over the paper's Table-I CNN workloads.

Simulates n clients writing updates of a chosen model size to the
UpdateStore, runs the monitor, and fuses with the adaptive service —
the paper's end-to-end flow (Fig. 12/13) in one command.

  PYTHONPATH=src python -m repro.launch.aggregate --model CNN4.6 \
      --clients 64 --fusion fedavg

``--async-rounds`` overlaps fusion with the straggler wait: a writer
thread spreads client arrivals over ``--spread`` seconds while the
service folds partial sums off the arrival stream (Algorithm 1 with the
monitor inside the ingest loop).

``--adaptive`` enables the learned gate: the controller records each
round's arrival curve and replaces the static ``--threshold-frac`` /
``--timeout`` gate with a learned threshold/deadline that optimizes the
``--cost-bias`` knob (0 = fastest rounds, 1 = maximum update inclusion).
Run several ``--rounds`` to watch the policy move from ``static`` to
``learned`` as the curve accumulates — the report line prints the gate
each round used, labeled with its tenant.

``--compress`` turns on quantized transport: every client write is
int8 block-quantized with per-tenant error feedback
(``repro.core.compress``) before it hits the store, and the round
streams codes + scales through the engines' dequant-folding step —
~4x fewer ingest bytes at one quantization step of error. The report
line's ``ingest=`` field shows the actual payload bytes fused.

``--tenant`` tags every write and round with a tenant label (store
partition + service continuity key). ``--concurrent-tenants K`` runs K
tenants' rounds GENUINELY CONCURRENTLY on ONE store and ONE service:
a ``RoundScheduler`` worker per tenant executes all K rounds at once
(device execution bounded by ``--device-concurrency``, default 1),
their writers land interleaved while every round is open, and each
round folds only its own tenant's partition — watch the per-tenant
report lines show full inclusion and ``compile=0.000s`` for every
tenant after the first (single-flight compile cache: K racing tenants
pay ONE cold compile). ``--quota-updates`` / ``--quota-bytes`` /
``--quota-policy`` install a per-tenant capacity quota on the shared
store (the noisy-neighbor bound; see docs/MULTITENANCY.md).
"""
from __future__ import annotations

import argparse
import threading
import time
import zlib

import numpy as np

from repro.configs import CNN_SUITE
from repro.core import (
    AggregationService,
    QuotaExceededError,
    RoundScheduler,
    UpdateStore,
    Workload,
    classify,
)
from repro.utils.jitcache import enable_persistent_cache
from repro.utils.mem import bytes_to_human


def _report_line(report, gate: str) -> str:
    """One round's outcome, labeled with its tenant so interleaved
    multi-tenant logs stay unambiguous."""
    st = report.store_stats
    stats = (f" writes={st.writes} wbytes={st.bytes_written}"
             f" evictions={st.evictions}") if st is not None else ""
    for note in report.notes:
        stats += f" note={note!r}"
    return (f"[aggregate] tenant={report.tenant} "
            f"engine={report.plan.engine} "
            f"class={report.plan.workload_class.value} "
            f"streamed={report.streamed} "
            f"monitor_ready={report.monitor.ready} "
            f"gate={gate} "
            f"ingest={bytes_to_human(report.bytes_ingested)} "
            f"fuse={report.fuse_seconds:.3f}s "
            f"overlap={report.overlap_seconds:.3f}s "
            f"compile={report.phase_seconds.get('compile', 0.0):.3f}s "
            f"est={report.plan.est_seconds:.4f}s(model) "
            f"route_next_to_store={report.route_next_to_store}"
            + stats)


def _gate_str(report) -> str:
    pol = report.close_policy
    if not pol:
        return "static"
    return (f"{pol.source}(frac={pol.threshold_frac:.2f} "
            f"deadline={pol.deadline:.2f}s)")


def main():
    ap = argparse.ArgumentParser(
        description="End-to-end aggregation rounds over the UpdateStore "
                    "(paper Fig. 12/13)."
    )
    ap.add_argument("--model", default="CNN4.6", choices=sorted(CNN_SUITE),
                    help="Table-I CNN workload (sets the update size)")
    ap.add_argument("--clients", type=int, default=32,
                    help="simulated clients writing one update each "
                         "(per tenant)")
    ap.add_argument("--fusion", default="fedavg",
                    help="fusion algorithm (repro.core.fusion.REGISTRY)")
    ap.add_argument("--local-strategy", default=None,
                    choices=["jnp", "pallas"],
                    help="single-chip engine (default: the service's, "
                         "the fused Pallas kernels)")
    ap.add_argument("--compress", action="store_true",
                    help="quantize client writes to int8 codes + fp32 "
                         "per-block scales (error feedback per tenant); "
                         "rounds stream them through the dequant-folding "
                         "step — ~4x fewer ingest bytes")
    ap.add_argument("--threshold-frac", type=float, default=0.8,
                    help="static gate: close at this fraction of clients")
    ap.add_argument("--timeout", type=float, default=5.0,
                    help="static gate deadline (and learned-deadline cap)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--async-rounds", action="store_true",
                    help="fold arrivals while stragglers write "
                         "(monitor-overlapped round)")
    ap.add_argument("--spread", type=float, default=1.0,
                    help="seconds over which async-round client arrivals "
                         "are spread")
    ap.add_argument("--adaptive", action="store_true",
                    help="learn the arrival curve and close rounds with "
                         "the adaptive controller's policy")
    ap.add_argument("--cost-bias", type=float, default=0.5,
                    help="adaptive knob in [0,1]: 0 optimizes round "
                         "wall-clock, 1 optimizes update inclusion")
    ap.add_argument("--rounds", type=int, default=1,
                    help="rounds to run (adaptive gates need >1 to learn)")
    ap.add_argument("--tenant", default="default",
                    help="tenant label for writes and rounds (store "
                         "partition + service continuity key)")
    ap.add_argument("--concurrent-tenants", type=int, default=0,
                    help="run this many tenants' rounds CONCURRENTLY on "
                         "ONE shared store/service via the RoundScheduler "
                         "(overrides --tenant; writers for all tenants "
                         "run while every round is open)")
    ap.add_argument("--device-concurrency", type=int, default=1,
                    help="bound on concurrent device execution across "
                         "tenants' rounds (the scheduler's hardware "
                         "semaphore; 1 serializes folds, waits overlap)")
    ap.add_argument("--quota-updates", type=int, default=None,
                    help="per-tenant resident-update budget on the "
                         "shared store (None: unbounded)")
    ap.add_argument("--quota-bytes", type=int, default=None,
                    help="per-tenant resident-byte budget on the shared "
                         "store (None: unbounded)")
    ap.add_argument("--quota-policy", default="reject",
                    choices=["reject", "evict"],
                    help="over-budget writes: reject (raise) or evict "
                         "the tenant's oldest resident updates")
    args = ap.parse_args()
    enable_persistent_cache()

    spec = CNN_SUITE[args.model]
    n_params = spec.num_params
    store = UpdateStore()
    strategy = ({"local_strategy": args.local_strategy}
                if args.local_strategy else {})
    svc = AggregationService(
        fusion=args.fusion, store=store, **strategy,
        threshold_frac=args.threshold_frac, monitor_timeout=args.timeout,
        adaptive=args.adaptive, cost_bias=args.cost_bias,
        compress=args.compress,
        device_concurrency=args.device_concurrency,
    )
    tenants = (
        [f"app{i}" for i in range(args.concurrent_tenants)]
        if args.concurrent_tenants else [args.tenant]
    )
    if args.quota_updates is not None or args.quota_bytes is not None:
        for t in tenants:
            store.set_quota(
                t, max_updates=args.quota_updates,
                max_bytes=args.quota_bytes, policy=args.quota_policy,
            )
    scheduler = (
        RoundScheduler(svc) if args.concurrent_tenants else None
    )
    overlapped = args.async_rounds or args.adaptive \
        or args.concurrent_tenants > 0
    # classify on the REAL wire size: --compress rounds move int8
    # codes + scales, ~4x smaller than fp32 — at fp32 bytes the banner
    # could report DISTRIBUTED for work that fits one chip's HBM
    load = Workload.for_params(n_params, args.clients,
                               compressed=args.compress)
    print(f"[aggregate] model={args.model} w_s={bytes_to_human(load.update_bytes)} "
          f"n={args.clients} S={bytes_to_human(load.total_bytes)} "
          f"class={classify(load).value}"
          + (f" adaptive(cost_bias={args.cost_bias})" if args.adaptive
             else "")
          + (f" tenants={tenants}" if len(tenants) > 1 else ""))

    for rnd in range(args.rounds):
        t0 = time.time()
        write_lat = []
        rejected = []

        def write_all(tenant):
            pause = args.spread / max(args.clients, 1) if overlapped else 0.0
            # crc32, not hash(): per-tenant streams must stay
            # reproducible across processes under one --seed — and
            # unreduced, so distinct tenant labels get distinct streams
            trng = np.random.default_rng(
                args.seed + rnd * 1009 + zlib.crc32(tenant.encode())
            )
            for i in range(args.clients):
                if pause:
                    time.sleep(pause)
                u = trng.normal(size=(n_params,)).astype(np.float32)
                if args.compress:
                    # client-side quantization: spool int8 codes + fp32
                    # scales; the residual stays with the client (EF)
                    u = svc.compress_update(f"client{i:05d}", u,
                                            tenant=tenant)
                try:
                    write_lat.append(
                        store.write(f"client{i:05d}", u,
                                    weight=float(trng.integers(1, 100)),
                                    tenant=tenant)
                    )
                except QuotaExceededError:
                    # reject policy: the write is refused, the writer
                    # keeps going — the round closes on whatever the
                    # quota admitted (reported below)
                    rejected.append(tenant)

        if overlapped:
            # arrivals land WHILE rounds are open (the overlapped round,
            # or a serialized monitor wait the controller can actually
            # observe an arrival curve from) — with several tenants,
            # every tenant's writer runs under every tenant's round
            writers = [
                threading.Thread(target=write_all, args=(t,), daemon=True)
                for t in tenants
            ]
            for w in writers:
                w.start()
            if scheduler is not None:
                # truly concurrent execution: every tenant's round runs
                # NOW on its scheduler worker — monitor waits overlap,
                # device folds share the execution semaphore
                results = scheduler.run_round(
                    tenants, from_store=True,
                    expected_clients=args.clients,
                    async_round=args.async_rounds,
                )
                reports = [results[t] for t in tenants]
            else:
                reports = [
                    svc.aggregate(from_store=True,
                                  expected_clients=args.clients,
                                  async_round=args.async_rounds,
                                  tenant=t)
                    for t in tenants
                ]
            for w in writers:
                w.join()
        else:
            for t in tenants:
                write_all(t)
            reports = [
                svc.aggregate(from_store=True,
                              expected_clients=args.clients, tenant=t)
                for t in tenants
            ]
        if not args.async_rounds:
            for t in tenants:
                store.clear(tenant=t)   # serialized rounds don't consume
        avg_write = np.mean(write_lat) * 1e3 if write_lat else 0.0
        print(f"[aggregate] round={rnd} {len(write_lat)} updates written "
              f"(modeled avg write {avg_write:.1f} ms, "
              f"wall {time.time()-t0:.2f}s)"
              + (f" [{len(rejected)} writes rejected by quota]"
                 if rejected else ""))
        for fused, report in reports:
            if report.empty:
                print(f"[aggregate] tenant={report.tenant} empty round "
                      "(monitor timed out with no arrivals)")
                continue
            print(_report_line(report, _gate_str(report)))
            print(f"[aggregate] tenant={report.tenant} "
                  f"fused[:5]={np.asarray(fused[:5])}")
    if scheduler is not None:
        scheduler.shutdown()


if __name__ == "__main__":
    main()
