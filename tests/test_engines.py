"""Engine equivalence (paper §IV-C): every engine computes the same fusion
formula. Single-device in-process; 8-device via subprocess (the dry-run
alone may force host device counts, never the test process)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DistributedEngine, LocalEngine
from repro.core.fusion import (
    ClippedAvg,
    CoordMedian,
    FedAvg,
    GeometricMedian,
    IterAvg,
    Krum,
    TrimmedMean,
    Zeno,
)

ALL_FUSIONS = [
    FedAvg(), IterAvg(), ClippedAvg(clip_norm=3.0), CoordMedian(),
    TrimmedMean(beta=0.2), Krum(n_byzantine=2), Zeno(n_suspect=2),
    GeometricMedian(),
]


@pytest.fixture(scope="module")
def data(rng=np.random.default_rng(1)):
    u = rng.normal(size=(13, 257)).astype(np.float32)
    w = rng.uniform(1, 5, size=(13,)).astype(np.float32)
    return u, w


@pytest.mark.parametrize("fusion", ALL_FUSIONS, ids=lambda f: f.name)
def test_local_pallas_matches_jnp(fusion, data):
    u, w = data
    a = np.asarray(LocalEngine(strategy="jnp").fuse(fusion, u, w))
    b = np.asarray(LocalEngine(strategy="pallas").fuse(fusion, u, w))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fusion", ALL_FUSIONS, ids=lambda f: f.name)
def test_distributed_1dev_matches_local(fusion, data):
    u, w = data
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    a = np.asarray(LocalEngine(strategy="jnp").fuse(fusion, u, w))
    b = np.asarray(DistributedEngine(mesh=mesh).fuse(fusion, u, w))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_streamed_memory_cap_matches_full(data):
    u, w = data
    full = np.asarray(LocalEngine(strategy="jnp").fuse(FedAvg(), u, w))
    row_bytes = u.shape[1] * 4
    capped = LocalEngine(strategy="jnp", memory_cap_bytes=row_bytes * 3)
    out = np.asarray(capped.fuse(FedAvg(), u, w))
    np.testing.assert_allclose(out, full, rtol=1e-5, atol=1e-6)


def test_memory_cap_rejects_nonstreamable(data):
    u, w = data
    capped = LocalEngine(strategy="jnp", memory_cap_bytes=u.shape[1] * 4 * 2)
    with pytest.raises(MemoryError):
        capped.fuse(Krum(), u, w)


def test_memory_cap_streams_order_statistics(data):
    """CoordMedian under a memory cap streams through the carve fold
    (PR 7) instead of raising MemoryError."""
    u, w = data
    capped = LocalEngine(strategy="jnp", memory_cap_bytes=u.shape[1] * 4 * 2)
    out = np.asarray(capped.fuse(CoordMedian(), u, w))
    np.testing.assert_allclose(out, np.median(u, axis=0),
                               rtol=1e-5, atol=1e-5)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import DistributedEngine, LocalEngine
    from repro.core.fusion import (FedAvg, IterAvg, ClippedAvg, CoordMedian,
                                   TrimmedMean, Krum, Zeno, GeometricMedian)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rng = np.random.default_rng(1)
    u = rng.normal(size=(13, 257)).astype(np.float32)
    w = rng.uniform(1, 5, size=(13,)).astype(np.float32)
    le = LocalEngine(strategy="jnp")
    for hier in (False, True):
        de = DistributedEngine(mesh=mesh, hierarchical=hier)
        for f in (FedAvg(), IterAvg(), ClippedAvg(clip_norm=3.0),
                  CoordMedian(), TrimmedMean(beta=0.2), Krum(n_byzantine=2),
                  Zeno(n_suspect=2), GeometricMedian()):
            if hier and not f.reducible:
                continue
            a = np.asarray(le.fuse(f, u, w))
            b = np.asarray(de.fuse(f, u, w))
            assert np.allclose(a, b, rtol=1e-4, atol=1e-5), (f.name, hier)
    print("MULTI_DEVICE_OK")
""")


def test_multi_device_equivalence_subprocess():
    """2x2x2 pod mesh on 8 forced host devices, all fusions + hierarchical."""
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert "MULTI_DEVICE_OK" in r.stdout, r.stderr[-3000:]


# -- backend-derived settings ---------------------------------------------------


def test_local_engine_interprets_off_tpu(data):
    """The kernels compile only for the TPU: anywhere else the engine
    derives interpret mode, and the fold's HLO holds no TPU kernel."""
    u, w = data
    assert jax.default_backend() != "tpu"
    eng = LocalEngine(strategy="pallas")
    assert eng.interpret is True
    eng.fuse(FedAvg(), u, w)
    (fold,) = eng.cache.executables().values()
    assert "tpu_custom_call" not in fold.as_text()


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", "tpu-v5e"),          # off the TPU: the modeled target
    ("tpu", "TPU v5 lite", "tpu-v5e"),
    ("tpu", "TPU v4", None),            # unknown kind: an error, not v5e
])
def test_service_hardware_from_device_kind(monkeypatch, platform, kind,
                                           want):
    from repro.core import AggregationService
    from repro.utils import mem

    monkeypatch.setattr(mem.jax, "devices",
                        lambda: [_Device(platform, kind)])
    if want is None:
        with pytest.raises(ValueError, match="TPU v4"):
            AggregationService()
    else:
        assert AggregationService().hw.name == want


@pytest.mark.parametrize("env_dir", [True, False])
def test_persistent_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache is
    the checkout's fixed .jax_cache/. Every compile is kept."""
    import os

    from repro.utils.jitcache import enable_persistent_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            # what JAX itself reads from the variable at start-up
            jax.config.update(keys[0], str(tmp_path))
            want = str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
        assert enable_persistent_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
