"""Smoke test: the served aggregator's main path on a TPU, at the paper's
Table-I update widths, checked against float64 numpy references.

  python chip_smoke.py              # phases A-D on one chip
  python chip_smoke.py --chips 4    # the mesh engine on four chips only

One chip (EdgeAggregatorServer -> AggregationService -> LocalEngine, the
default fused Pallas strategy; data from --seed):

  A  2 tenants x 64 clients upload fp32 CNN4.6 updates (P = 1,150,000)
     over HTTP; FedAvg rounds fold through the weighted-sum kernel.
  B  2 tenants x 32 clients upload int8 Resnet50 frames (P = 22,750,000,
     ~23 MB each) over HTTP; rounds fold through the dequant kernel.
  C  a store round of streamed TrimmedMean at CNN4.6, n = 48: the top-k
     carve kernel.
  D  a store round of FedAvg at VGG16 width (P = 132,000,000, 528 MB per
     update), n = 8, streamed one row per block: the cross-silo shape.

``--chips 4`` runs only FedAvg through ``DistributedEngine`` on a (4, 1)
mesh at VGG16 width, against the numpy reference and against the
one-chip ``LocalEngine`` result.

Each phase prints one JSON line (engine, whether every compiled fold
holds a ``tpu_custom_call``, compile and fuse seconds, the largest error
relative to max|reference|). Any failure exits non-zero; the last line,
printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase. Times here are
a smoke test's, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FP32_TOL = 1e-5    # max |fused - ref| / max |ref| for fp32 folds
TENANTS = ("app0", "app1")


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, *stream])


def _rel_err(fused, ref: np.ndarray) -> float:
    fused = np.asarray(fused, np.float64)
    if fused.shape != ref.shape or not np.isfinite(fused).all():
        return float("inf")
    return float(np.max(np.abs(fused - ref)) / max(np.max(np.abs(ref)),
                                                    1e-30))


def _kernel_in_folds(engine) -> bool:
    """Whether the single-chip engine compiled a fold this phase, and
    every fold it compiled holds a compiled Pallas kernel."""
    folds = engine.cache.executables().values()
    return bool(folds) and all(
        "tpu_custom_call" in fn.as_text() for fn in folds)


def _upload_all(port: int, writes) -> None:
    """One HTTP uploader thread per tenant; ``writes(tenant)`` yields
    that tenant's (client_id, update, weight) triples."""
    from repro.serving import HttpStoreClient

    errors = []

    def run(tenant):
        try:
            with HttpStoreClient("127.0.0.1", port, token=f"tok-{tenant}",
                                 timeout=120.0) as cli:
                for cid, update, weight in writes(tenant):
                    cli.write(cid, update, weight=weight, tenant=tenant)
        except BaseException as exc:  # surfaced after join
            errors.append(exc)

    threads = [threading.Thread(target=run, args=(t,)) for t in TENANTS]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def _served_rounds(service, n: int, writes) -> dict:
    """Upload every tenant's round over HTTP, then run the rounds through
    the edge server's fair scheduler; returns {tenant: (fused, report)}."""
    from repro.fl import EdgeAggregatorServer

    tokens = {f"tok-{t}": t for t in TENANTS}
    with EdgeAggregatorServer(service, tokens) as edge:
        _upload_all(edge.port, writes)
        return edge.run_rounds(list(TENANTS), expected_clients=n)


def _summary(name, service, results, errors, **extra) -> dict:
    reports = {t: rep for t, (_, rep) in results.items()}
    for t, rep in reports.items():
        if not rep.streamed or rep.n_clients != extra.get("n"):
            raise RuntimeError(
                f"phase {name}: tenant {t} streamed={rep.streamed} "
                f"n_clients={rep.n_clients}, expected a streamed round "
                f"of {extra.get('n')}")
    return {
        "phase": name,
        "engine": sorted({r.plan.engine for r in reports.values()}),
        "strategy": service.local.strategy,
        "interpret": service.local.interpret,
        "tpu_custom_call": _kernel_in_folds(service.local),
        "compile_s": {t: r.phase_seconds.get("compile", 0.0)
                      for t, r in reports.items()},
        "fuse_s": {t: r.fuse_seconds for t, r in reports.items()},
        "max_rel_err": max(errors.values()),
        **extra,
    }


# -- phases -------------------------------------------------------------------


def phase_a(seed: int, dim: int, n: int = 64) -> dict:
    """HTTP uploads of fp32 updates; FedAvg via the weighted-sum kernel."""
    from repro.core import AggregationService, UpdateStore

    svc = AggregationService(fusion="fedavg", store=UpdateStore(),
                             threshold_frac=1.0, monitor_timeout=120.0)
    refs = {}

    def writes(tenant):
        rng = _rng(seed, 0, TENANTS.index(tenant))
        acc, tot = np.zeros(dim, np.float64), 0.0
        for i in range(n):
            u = rng.standard_normal(dim, dtype=np.float32)
            w = float(rng.integers(1, 100))
            acc += w * u
            tot += w
            yield f"c{i:04d}", u, w
        refs[tenant] = acc / tot

    results = _served_rounds(svc, n, writes)
    errs = {t: _rel_err(results[t][0], refs[t]) for t in TENANTS}
    return _summary("A", svc, results, errs, n=n, P=dim, dtype="float32")


def phase_b(seed: int, dim: int, n: int = 32) -> dict:
    """HTTP uploads of int8 frames; FedAvg via the dequant kernel. The
    fused vector must match the dequantized updates' mean to fp32
    tolerance and the dense updates' mean within one quantization step."""
    from repro.core import AggregationService, UpdateStore

    svc = AggregationService(fusion="fedavg", store=UpdateStore(),
                             threshold_frac=1.0, monitor_timeout=300.0,
                             compress=True)
    refs = {}

    def writes(tenant):
        rng = _rng(seed, 1, TENANTS.index(tenant))
        dense, deq = np.zeros(dim, np.float64), np.zeros(dim, np.float64)
        tot, step = 0.0, 0.0
        for i in range(n):
            u = rng.standard_normal(dim, dtype=np.float32)
            w = float(rng.integers(1, 100))
            cu = svc.compress_update(f"c{i:04d}", u, tenant=tenant)
            dense += w * u
            deq += w * cu.dequantize()
            tot += w
            step = max(step, float(cu.scales.max()))
            yield f"c{i:04d}", cu, w
        refs[tenant] = (dense / tot, deq / tot, step)

    results = _served_rounds(svc, n, writes)
    errs, step_errs = {}, {}
    for t in TENANTS:
        dense, deq, step = refs[t]
        fused = np.asarray(results[t][0], np.float64)
        errs[t] = _rel_err(fused, deq)
        # quantization error of a weighted mean is at most half a step
        step_errs[t] = float(np.max(np.abs(fused - dense)) / step)
    return _summary("B", svc, results, errs, n=n, P=dim, dtype="int8",
                    max_err_in_quant_steps=max(step_errs.values()))


def phase_c(seed: int, dim: int, n: int = 48) -> dict:
    """A store round of streamed TrimmedMean: the top-k carve kernel."""
    from repro.core import AggregationService, UpdateStore

    store = UpdateStore()
    svc = AggregationService(fusion="trimmedmean", store=store,
                             threshold_frac=1.0, monitor_timeout=60.0)
    rng = _rng(seed, 2)
    u = rng.standard_normal((n, dim), dtype=np.float32)
    for i in range(n):
        store.write(f"c{i:04d}", u[i], weight=float(rng.integers(1, 100)))
    fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    k = svc.fusion.trim_count(n)
    ref = np.sort(u.astype(np.float64), axis=0)[k:n - k].mean(axis=0)
    return _summary("C", svc, {"default": (fused, rep)},
                    {"default": _rel_err(fused, ref)}, n=n, P=dim,
                    dtype="float32", trim=k)


def _fedavg_inputs(seed: int, n: int, dim: int):
    """(n, dim) fp32 updates, weights, and their float64 FedAvg."""
    rng = _rng(seed, 3)
    u = rng.standard_normal((n, dim), dtype=np.float32)
    w = rng.integers(1, 100, size=n).astype(np.float32)
    ref = np.zeros(dim, np.float64)
    for i in range(n):
        ref += float(w[i]) * u[i]
    return u, w, ref / float(w.sum())


def phase_d(seed: int, dim: int, n: int = 8) -> dict:
    """A store round of FedAvg at cross-silo width, one row per block."""
    from repro.core import AggregationService, UpdateStore

    store = UpdateStore()
    svc = AggregationService(fusion="fedavg", store=store,
                             threshold_frac=1.0, monitor_timeout=60.0)
    u, w, ref = _fedavg_inputs(seed, n, dim)
    for i in range(n):
        store.write(f"c{i:04d}", u[i], weight=float(w[i]))
    fused, rep = svc.aggregate(from_store=True, expected_clients=n)
    return _summary("D", svc, {"default": (fused, rep)},
                    {"default": _rel_err(fused, ref)}, n=n, P=dim,
                    dtype="float32")


def phase_mesh(seed: int, dim: int, devices, n: int = 8) -> dict:
    """FedAvg through DistributedEngine on a (len(devices), 1) mesh,
    against the numpy reference and the one-chip LocalEngine."""
    import jax

    from repro.core import DistributedEngine, LocalEngine
    from repro.core.fusion import FedAvg
    from repro.launch.mesh import make_mesh

    u, w, ref = _fedavg_inputs(seed, n, dim)
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    dist = DistributedEngine(mesh=mesh)
    t0 = time.perf_counter()
    fused = np.asarray(jax.block_until_ready(dist.fuse(FedAvg(), u, w)))
    mesh_s = time.perf_counter() - t0
    local = LocalEngine(strategy="pallas")
    one = np.asarray(jax.block_until_ready(local.fuse(FedAvg(), u, w)))
    hlo = [fn.as_text() for fn in dist.cache.executables().values()]
    return {
        "phase": "mesh",
        "engine": "distributed",
        "mesh": dict(mesh.shape),
        "all_reduce": any("all-reduce" in h for h in hlo),
        "compile_s": dist.last_compile_seconds,
        "fuse_s": mesh_s,
        "max_rel_err": _rel_err(fused, ref),
        "max_rel_err_vs_local": _rel_err(fused, one.astype(np.float64)),
        "local_rel_err": _rel_err(one, ref),
        "local_tpu_custom_call": _kernel_in_folds(local),
        "n": n,
        "P": dim,
    }


def _check(line: dict) -> None:
    """Fail the run on a missing kernel or an error past tolerance."""
    bad = []
    kernel = line.get("tpu_custom_call", line.get("local_tpu_custom_call"))
    if not kernel:
        bad.append("no compiled Pallas kernel in the fold")
    if line.get("interpret"):
        bad.append("LocalEngine runs in interpret mode")
    for key in ("max_rel_err", "max_rel_err_vs_local", "local_rel_err"):
        if key in line and not line[key] <= FP32_TOL:
            bad.append(f"{key}={line[key]} > {FP32_TOL}")
    if line.get("max_err_in_quant_steps", 0.0) > 1.0:
        bad.append("fused vector is more than one quantization step "
                   "from the dense mean")
    if line.get("engine") == "distributed" and not line.get("all_reduce"):
        bad.append("no all-reduce in the mesh program")
    if bad:
        raise SystemExit(f"phase {line['phase']} failed: {'; '.join(bad)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-D on one chip; 4: only the mesh "
                         "phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.configs import CNN_SUITE
    from repro.utils.jitcache import enable_persistent_cache

    enable_persistent_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    width = {name: spec.num_params for name, spec in CNN_SUITE.items()}
    if args.chips == 4:
        phases = [lambda: phase_mesh(args.seed, width["VGG16"],
                                     devices[:4])]
    else:
        phases = [
            lambda: phase_a(args.seed, width["CNN4.6"]),
            lambda: phase_b(args.seed, width["Resnet50"]),
            lambda: phase_c(args.seed, width["CNN4.6"]),
            lambda: phase_d(args.seed, width["VGG16"]),
        ]
    for run in phases:
        t0 = time.perf_counter()
        line = run()
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        _check(line)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
