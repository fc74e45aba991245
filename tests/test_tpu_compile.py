"""Compile the main path's Pallas kernels for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached: it refuses what Mosaic would refuse there (a
slice off the tiling, too much VMEM), which interpret mode cannot show.
Nothing runs, so these tests say nothing about results or times. All such
tests live in this one file: the worker that is given it loads libtpu, in
a fixture, after collection.
"""

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,K", [
    (162_541, 64), (59_047, 64), (40_000, 16), (33_000, 128)])
def test_resident_cg_compiles_for_v5e(one_chip, n, K):
    """The benchmark cell's two batches (neither a multiple of the
    128-entity tile), the quickstart's rank, and the largest rank the
    selection rule hands the kernel."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.models.als import _cg_solve_resident

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(_cg_solve_resident).lower(
        f32(n, K, K), f32(n, K), f32(K, K)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # A, its transposed copy and the vectors: no third copy of A
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 1.05 * n * K * K * 4
