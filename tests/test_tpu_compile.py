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


def test_stream_finalize_gathers_from_vmem_on_v5e(one_chip, monkeypatch):
    """``finalize`` of the streamed ALS trainer at the benchmark cell's
    shapes (MovieLens-25M, rank 64: seven stream chunks of 17 x 4,096
    user blocks and 3,571,442 edges, 107 x 4,096 item blocks): in both
    half-steps the gather reads its factor table from VMEM (memory space
    1). The plain ``bf16[162541,64]`` table, padded to 128 lanes, stays
    in HBM there, so ``_gather_impl`` packs it; this guards both tables
    against a later change to the scans' carries that would push one
    back unseen."""
    import re

    import jax
    import jax.numpy as jnp

    from pio_tpu.models import als

    U, I, K, W, chunk = 162_541, 59_047, 64, 64, 4096
    S_c, S_item, E_c, n_stream = 17 * chunk, 107 * chunk, 3_571_442, 7

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    span = U // n_stream
    spec = tuple((S_c, min(U - 1, (c + 1) * span), c * span)
                 for c in range(n_stream))
    # the rules read the backend when the trainer is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    als._build_stream_trainer.cache_clear()
    try:
        _, _, finalize = als._build_stream_trainer(
            10, 0.1, False, 1.0, "bfloat16", "auto", K, U, I, W, W, S_item,
            chunk, chunk, spec)
        blocks = tuple((sd((S_c,), i32), sd((S_c, W), i32),
                        sd((S_c, W), f32)) for _ in spec)
        edges = tuple((sd((E_c,), i32), sd((E_c,), f32)) for _ in spec)
        compiled = finalize.lower(
            sd((U, K, K), f32), sd((U, K), f32), sd((I, K), f32),
            sd((U,), i32), sd((I,), i32), blocks, edges).compile()
    finally:
        als._build_stream_trainer.cache_clear()

    text = compiled.as_text()
    tables = {}  # half-step → the table operand of each gather fusion
    for line in text.splitlines():
        m = re.match(r"\s*%\S+ = \S+ fusion\((%[\w.\-]+),", line)
        if not (m and "kind=kCustom" in line
                and "als.normal_eq" in line and "gather/gather" in line):
            continue
        side = re.search(r"/als\.(user|item)/", line).group(1)
        shape = re.search(
            r"^\s*" + re.escape(m.group(1)) + r" = (\S+) ", text, re.M)
        tables.setdefault(side, []).append(shape.group(1))
    assert set(tables) == {"user", "item"}, tables
    assert all(t.startswith("bf16[81271,128]") for t in tables["item"])
    # the item table fits as it is, so the rule leaves it plain
    assert all(t.startswith("bf16[59047,64]") for t in tables["user"])
    assert all("S(1)" in t for ts in tables.values() for t in ts), tables
    # the plain form's temporaries at these shapes: 11,835,239,424 B
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 1.01 * 11_835_239_424
