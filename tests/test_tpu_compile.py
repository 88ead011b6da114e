"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: the TPU compiler ships with libtpu, so what Mosaic refuses
(unaligned blocks, primitives with no TPU lowering, VMEM overruns) fails
here without a chip. Widths are the paper's Table I
(``repro.configs.CNN_SUITE``); row counts are the streamed block sizes
the service picks at those widths (64 MiB chunks) plus a ragged dense
round.

The topology is described inside a module fixture and never at import:
only one process may load libtpu, and every xdist worker imports every
test module. Keep these tests in this one file, so that one worker
loads the library for all of them.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import CNN_SUITE
from repro.core.compress import BLOCK
from repro.core.fusion.robust import TrimmedMean
from repro.kernels.fused_fusion.kernel import (
    weighted_sum_dequant_pallas,
    weighted_sum_pallas,
)
from repro.kernels.robust_fusion.kernel import topk_carve_pallas

CNN46 = CNN_SUITE["CNN4.6"].num_params        # 1,150,000
RESNET50 = CNN_SUITE["Resnet50"].num_params   # 22,750,000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("n,dtype", [
    (14, jnp.float32),      # CNN4.6 streamed block
    (300, jnp.float32),     # ragged dense round (300 % 256 != 0)
    (14, jnp.bfloat16),
])
def test_weighted_sum_compiles_at_cnn46(one_chip, n, dtype):
    spec = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    hlo = _hlo(lambda u, w: weighted_sum_pallas(u, w, interpret=False),
               spec((n, CNN46), dtype), spec((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [2, 300])   # Resnet50 int8 block; ragged
def test_weighted_sum_dequant_compiles_at_resnet50(one_chip, n):
    spec = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pq = -(-RESNET50 // BLOCK) * BLOCK
    hlo = _hlo(
        lambda q, s, w: weighted_sum_dequant_pallas(
            q, s, w, block=BLOCK, interpret=False),
        spec((n, pq), jnp.int8), spec((n, pq // BLOCK), jnp.float32),
        spec((n,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_carve_compiles_at_cnn46(one_chip, dtype):
    """TrimmedMean at n=48 keeps K=4 extremes per side; the service
    streams CNN4.6 in blocks of c=14 rows."""
    c, k_cap = 14, TrimmedMean().trim_count(48)
    spec = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(
        s, d, sharding=one_chip)
    hlo = _hlo(
        lambda u, v, s, t, b: topk_carve_pallas(u, v, s, t, b,
                                                interpret=False),
        spec((c, CNN46), dtype), spec((c,)), spec((CNN46,)),
        spec((k_cap, CNN46)), spec((k_cap, CNN46)),
    )
    assert "tpu_custom_call" in hlo
