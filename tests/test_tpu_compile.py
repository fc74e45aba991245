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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

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


@pytest.mark.parametrize("n", [162_541, 59_047])
def test_fused_accum_compiles_for_v5e(one_chip, n):
    """The benchmark cell's two sides: a chunk of 4,096 blocks of 64 slots
    at rank 64 (the one shape ``_accum_impl`` hands the kernel). ``AB``
    is updated in place, and nothing else of its size exists."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.models.als import _accum_fused

    K, W, C = 64, 64, 4096

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    compiled = jax.jit(_accum_fused, donate_argnums=(0,)).lower(
        sd((n, K // 2 + 8, 2 * K), f32), sd((K + 16, 2 * K), f32),
        sd((1,), i32), sd((C,), i32), sd((C, W, K), jnp.bfloat16),
        sd((C, W), f32), sd((C, W), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * (K // 2 + 8) * 2 * K * 4
    assert mem.temp_size_in_bytes <= 4 << 20  # the weights' lane rows


@pytest.fixture(scope="module")
def stream_programs(one_chip):
    """``accum`` (the first of seven) and ``finalize`` of the streamed ALS
    trainer compiled at the benchmark cell's shapes (MovieLens-25M, rank
    64: seven stream chunks of 17 x 4,096 user blocks and 3,571,442
    edges, 107 x 4,096 item blocks), as ``{name: compiled}``."""
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
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    als._build_stream_trainer.cache_clear()
    try:
        _, accums, finalize = als._build_stream_trainer(
            10, 0.1, False, 1.0, "bfloat16", "auto", K, U, I, W, W, S_item,
            chunk, chunk, spec)
        blocks = tuple((sd((S_c,), i32), sd((S_c, W), i32),
                        sd((S_c, W), f32)) for _ in spec)
        edges = tuple((sd((E_c,), i32), sd((E_c,), f32)) for _ in spec)
        A, b, Q0 = sd((U, K, K), f32), sd((U, K), f32), sd((I, K), f32)
        return {
            "accum": accums[0].lower(
                A, b, Q0, sd((spec[0][1] - spec[0][2] + 1,), i32),
                sd((E_c,), i32), sd((E_c,), f32)).compile(),
            "finalize": finalize.lower(
                A, b, Q0, sd((U,), i32), sd((I,), i32), blocks,
                edges).compile(),
        }
    finally:
        jax.default_backend = real_backend
        als._build_stream_trainer.cache_clear()


@pytest.mark.parametrize("program,kernels,temp_bytes", [
    # as compiled with the kernel in; the XLA path's: 10,501,731,840 and
    # 11,834,045,952 B
    # (``accum`` held 7,239,892,992 B until PR 34: the pack's lane rows,
    # ``[69632, 128]`` words each, are live beside the first chunk step)
    ("accum", 1, 7_397_432_832), ("finalize", 3, 8_784_753_152)])
def test_stream_programs_sum_in_the_kernel_on_v5e(stream_programs, program,
                                                  kernels, temp_bytes):
    """Both programs of the streamed trainer hand every half-step to the
    fused kernel (``finalize``: iteration 1's item side, then both sides
    in the loop), no per-block ``f32[4096,64,64]`` product is written to
    HBM, and the temporaries stay what the lane-dense carry needs."""
    compiled = stream_programs[program]
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "als_accum_fused" in ln]
    assert len(calls) == kernels, len(calls)
    assert "f32[4096,64,64]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.01 * temp_bytes


@pytest.mark.parametrize("program,S", [
    ("accum", 17 * 4096), ("finalize", 107 * 4096)])
def test_stream_programs_pack_by_the_row_on_v5e(stream_programs, program, S):
    """``device_pack`` in both programs of the streamed trainer copies a
    block's run of edges as lane rows: under ``als.pack`` stand the four
    native gathers of ``S`` rows of 128 (ids and ratings, a run's two
    rows), no gather of one scalar for each of the ``S x 64`` slots (the
    form that cost 1.57 s of a 5.09 s call: PERF.md section 6, PR 34),
    and no ``while`` (what XLA makes of a batched ``dynamic_slice`` out
    of the flat list, and of ``searchsorted``'s scan)."""
    import math
    import re

    W = 64
    pack = [ln for ln in stream_programs[program].as_text().splitlines()
            if re.search(r'op_name="[^"]*als\.pack', ln)]
    assert not [ln for ln in pack if " while(" in ln]
    gathers = {}  # (elements gathered, slice sizes) -> how many
    for ln in pack:
        m = re.search(r"= \w+\[([\d,]+)\]\S* gather\(.*slice_sizes=\{([\d,]+)\}",
                      ln)
        if m:
            key = (math.prod(map(int, m.group(1).split(","))), m.group(2))
            gathers[key] = gathers.get(key, 0) + 1
    assert gathers.get((S * 128, "1,128")) == 4, gathers
    assert not [k for k in gathers if k[0] >= S * W and k[1] == "1"], gathers


def test_stream_finalize_gathers_from_vmem_on_v5e(stream_programs):
    """``finalize`` of the streamed ALS trainer at the benchmark cell's
    shapes: in both half-steps the gather reads its factor table from
    VMEM (memory space 1). The plain ``bf16[162541,64]`` table, padded to
    128 lanes, stays in HBM there, so ``_gather_impl`` packs it; this
    guards both tables against a later change to the scans' carries (or
    a kernel in their bodies that asks for much VMEM) that would push one
    back unseen."""
    import re

    text = stream_programs["finalize"].as_text()
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


def test_mesh_trainer_sums_in_the_kernel_on_four_v5e(topo, monkeypatch):
    """The mesh route's trainer (``shard_map`` over the block shards,
    then ``psum_scatter``) compiled for the four described chips at rank
    64, width 64: each half-step's sums are the fused kernel there too,
    with its carry updated in place under ``shard_map``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pio_tpu.models import als

    mesh = Mesh(np.array(topo.devices), ("data",))
    U, I, K, W, chunk = 40_000, 20_000, 64, 64, 4096
    # the rules read the backend when the trainer is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    als._build_trainer.cache_clear()
    try:
        run = als._build_trainer(
            mesh, "data", 3, 0.1, False, 1.0, chunk, chunk, "bfloat16",
            "auto", None, K, U, I)

        def side(S):
            rows = NamedSharding(mesh, P("data"))
            slots = NamedSharding(mesh, P("data", None))
            return (jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rows),
                    jax.ShapeDtypeStruct((S, W), jnp.int32, sharding=slots),
                    jax.ShapeDtypeStruct((S, W), jnp.float32,
                                         sharding=slots))

        seed = jax.ShapeDtypeStruct(
            (), jnp.uint32, sharding=NamedSharding(mesh, P()))
        text = run.lower(
            side(4 * 8 * chunk), side(4 * 6 * chunk), seed).compile().as_text()
    finally:
        als._build_trainer.cache_clear()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "als_accum_fused" in ln]
    assert len(calls) == 2, len(calls)
    assert "f32[4096,64,64]" not in text


@pytest.mark.parametrize("rows", [12288, 24576])
@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_grouped_expert_matmul_compiles_for_v5e(one_chip, k, n, rows):
    """The Nemotron cell's two expert matrices (the first passes' 12,288 rows
    and the last's chunks of 24,576, ``seq_layers.pass_widths``, over 8 held
    experts; 1,856 is no multiple of a 128-lane tile) through
    ``seq_layers.grouped_matmul`` with the tiles as committed: the product
    and both gradients are Pallas calls that fit VMEM."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.models.seq_layers import grouped_matmul

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(a, w, sizes, ct):
        y, back = jax.vjp(lambda a, w: grouped_matmul(a, w, sizes), a, w)
        return y, back(ct)

    text = jax.jit(both).lower(
        sd((rows, k), jnp.bfloat16), sd((8, k, n), jnp.bfloat16),
        sd((8,), jnp.int32), sd((rows, n), jnp.float32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3, len(calls)


@pytest.mark.parametrize("cell, b, h_kv, t, d_k, d_v, group, window", [
    ("glm47flash", 2, 20, 8192, 256, 256, 1, 0),
    ("laguna_full", 1, 4, 16384, 128, 128, 6, 0),
    ("laguna_window", 1, 4, 16384, 128, 128, 9, 512),
    ("nemotron", 1, 2, 16384, 128, 128, 16, 0),
])
def test_blocked_attention_compiles_for_v5e(one_chip, monkeypatch, cell, b,
                                            h_kv, t, d_k, d_v, group, window):
    """The three cells' attention (``ring.attention_partial`` at their
    shapes and groups, 512-blocks), forward and backward, with the rule
    asked as a TPU is: two Pallas calls that fit VMEM (``k``, ``v``, ``dk``,
    ``dv`` of a KV head resident), and between them nothing of a score
    tile's or a ``dk``/``dv`` carry's size: XLA's loops hold two float32
    ``[B, H, T, D]`` carries and a float32 ``[B, H, rows, 512]`` tile there.
    The cotangent comes as the step's does, rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel import ring

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    rows = t * group

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(q, k, v, do, dlse):
        def attend(q, k, v):
            return ring.attention_partial(
                q, k, v, jnp.int32(0), jnp.int32(0), True, d_k ** -0.5, 512,
                512, window, group)[:2]

        (o, lse), back = jax.vjp(attend, q, k, v)
        return o, lse, back((do.astype(f32), dlse))

    compiled = jax.jit(both).lower(
        sd((b, h_kv, rows, d_k), bf), sd((b, h_kv, t, d_k), bf),
        sd((b, h_kv, t, d_v), bf), sd((b, h_kv, rows, d_v), bf),
        sd((b, h_kv, rows), f32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)
    score_tile = b * h_kv * group * 512 * 512 * 4
    carry = b * h_kv * t * min(d_k, d_v) * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= min(score_tile, carry) // 4, (temp, score_tile, carry)


@pytest.mark.parametrize("cell, t, g, q, temp_bytes", [
    ("nemotron", 16384, 8, 128, 344_451_584),
    ("granite", 8192, 1, 256, 138_930_688),
])
def test_ssd_kernels_compile_for_v5e(one_chip, cell, t, g, q, temp_bytes):
    """The two mamba cells' chunked scan (one row, 64 heads of 64 channels, a
    state of 128; eight groups with chunks of 128, one group with chunks of
    256) on ``ssd_kernel``, forward and backward from the convolution's
    output: two Pallas calls that fit VMEM, and no ``[Q, Q]`` float32 decay
    weights in HBM, which XLA's scan writes for every chunk. The temporaries are the states that entered each chunk (float32
    ``[T / Q, H P, N]``) and the blocks' ``dB``, ``dC`` before their sum."""
    import math
    import re

    import jax
    import jax.numpy as jnp

    from pio_tpu.models import ssd_kernel

    b, h, p, n = 1, 64, 64, 128

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(xbc, dt, a, d, dy):
        y, back = jax.vjp(lambda *args: ssd_kernel.scan(
            *args, (h, p, g, n), q, jnp.bfloat16)[0], xbc, dt, a, d)
        return y, back(dy)

    compiled = jax.jit(both).lower(
        sd((b, t, h * p + 2 * g * n)), sd((b, t, h)), sd((h,)), sd((h,)),
        sd((b, t, h * p))).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)
    # a head's [Q, Q] weights over the row's chunks: XLA's scan writes R of
    # them a turn; per-position scalars in another layout are fewer
    weights = [dims for dims in re.findall(rf"f32\[([\d,]*),{q},{q}\]", text)
               if math.prod(map(int, dims.split(","))) * q * q >= t * q]
    assert not weights, weights
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.02 * temp_bytes


def _sequence_step(topo, config_name, max_len=0):
    """``chunk_staged`` of a sequence cell (its configuration's file read as
    the benchmark's driver reads it; ``max_len`` another row length), lowered
    for one described chip with the rules asked as a TPU is."""
    import dataclasses
    import json
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run

    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.models import seqrec
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh
    from pio_tpu.templates.sequence import SeqRecParams

    with open(os.path.join(bench, "configs", config_name + ".json")) as f:
        config = json.load(f)
    driver = run.load_module("drivers", "train_seq_cfg")
    p = params_from_dict(SeqRecParams, driver.algorithm_params(
        config, driver.reference_module(config).model(config), 1))
    cfg = seqrec.SeqRecConfig(**{f.name: getattr(p, f.name) for f in
                                 dataclasses.fields(seqrec.SeqRecConfig)})
    cfg = dataclasses.replace(cfg, max_len=max_len or cfg.max_len)
    mesh = build_mesh(MeshSpec(data=-1), devices=topo.devices[:1])
    rows, vocab = int(config["data"]["n_histories"]), int(config["data"]["n_items"]) + 1
    prog = seqrec._programs(dataclasses.replace(cfg, seed=0, steps=0), mesh,
                            vocab, cfg.batch_size, rows // cfg.batch_size)
    params = jax.eval_shape(prog.init, jnp.int32(0))
    whole = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        (jax.ShapeDtypeStruct((), jnp.int32), params,
         jax.eval_shape(prog.opt_init, params)))
    epoch = tuple(jax.ShapeDtypeStruct(
        (rows, cfg.max_len), dtype, sharding=NamedSharding(mesh, P("data", "seq")))
        for dtype in (jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.float32))
    return prog.chunk_staged.lower(state, epoch, cfg.steps), params


@pytest.mark.parametrize("config_name,max_len,parameters,temp_bytes", [
    # the accepted cell of single mixers, whose step runs in 15.04 of the
    # chip's 15.75 GiB (PERF.md section 4): the offline temporaries overstate
    # what the chip reserves, so a step is held to its own number
    # (10,052,246,016 B until the chunked scan became two Pallas kernels)
    ("nemotron3nano-ep16", 0, 666_963_456, 9_545_038_336),
    # no experts: 772 M parameters leave less room, rows of 8,192 fit it
    # (6,396,983,808 B on XLA's scan)
    ("granite4hmicro-vp8", 0, 772_160_448, 6_147_741_184),
    # for the record: one row of 16,384 does not (a Mamba-2 mixer's float32
    # [T, 4096] and [T, 8512] intermediates): the TPU compiler refuses it
    ("granite4hmicro-vp8", 16384, 772_160_448, None),
])
def test_a_sequence_cells_step_fits_a_v5e(topo, monkeypatch, config_name,
                                          max_len, parameters, temp_bytes):
    """The whole training step of the two cells of single mixers, compiled
    for a described v5e: the parameters' count, and the temporaries beside 12
    B a parameter of arguments (weights and Adam's moments, donated)."""
    import jax
    import numpy as np

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, params = _sequence_step(topo, config_name, max_len)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == parameters
    if temp_bytes is None:
        with pytest.raises(Exception, match="(?i)memory|exhausted"):
            lowered.compile()
        return
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes <= 1.02 * temp_bytes
    assert mem.argument_size_in_bytes == pytest.approx(12 * parameters, rel=2e-3)
    assert mem.alias_size_in_bytes >= 12 * parameters  # the state is donated


def test_selected_attention_compiles_for_v5e(one_chip, monkeypatch):
    """The sparse-attention cell's attention (one row of 16,384 events, 4 KV
    heads of 128 with 8 query heads each, 512-blocks) under a selection mask,
    forward and backward, with the rule asked as a TPU is: two Pallas calls
    that fit VMEM, a query block's words of the mask (16 rows of int32)
    beside its rows."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel import ring

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    b, h_kv, t, d, group, blk = 1, 4, 16384, 128, 8, 512
    rows, nq = t * group, t // blk

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(q, k, v, bits, order, count, do, dlse):
        def attend(q, k, v):
            return ring.attention_partial(
                q, k, v, jnp.int32(0), jnp.int32(0), True, d ** -0.5, blk,
                blk, 0, group, (bits, order, count))[:2]

        (o, lse), back = jax.vjp(attend, q, k, v)
        return o, lse, back((do.astype(f32), dlse))

    compiled = jax.jit(both).lower(
        sd((b, h_kv, rows, d), bf), sd((b, h_kv, t, d), bf),
        sd((b, h_kv, t, d), bf), sd((b, nq * ring.select_words(blk), t), i32),
        sd((nq, nq), i32), sd((nq,), i32), sd((b, h_kv, rows, d), bf),
        sd((b, h_kv, rows), f32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)


def test_the_indexers_kernels_compile_for_v5e(one_chip, monkeypatch):
    """One 128-query block of the sparse-attention cell's indexer (16 heads
    of 64 against one row of 16,384 keys) through ``index_scores`` with the
    rule asked as a TPU is, forward and backward: two Pallas calls that fit
    VMEM, and no ``[128, 16, 16,384]`` float32 product in HBM, which the
    einsum writes for every block."""
    import math
    import re

    import jax
    import jax.numpy as jnp

    from pio_tpu.models import seq_layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    b, n, hi, di, t = 1, 128, 16, 64, 16384

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(qi, ki, w, t0, g):
        scores, back = jax.vjp(
            lambda *a: seq_layers.index_scores(*a, t0, bf), qi, ki, w)
        return scores, back(g)

    compiled = jax.jit(both).lower(
        sd((b, n, hi, di), bf), sd((b, t, di), bf), sd((b, n, hi), f32),
        sd((), i32), sd((b, n, t), f32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)
    products = [dims for dims in re.findall(r"f32\[([\d,]+)\]", text)
                if math.prod(map(int, dims.split(","))) >= n * hi * t]
    assert not products, products
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * b * n * t


def test_the_sparse_attention_cells_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the sparse-attention cell (six layers of
    the lightning indexer, the selection, the attention's kernels under it,
    the indexer's loss and 16 held experts on the grouped matmul's kernel)
    compiled for a described v5e: 659,190,016 parameters, and the
    temporaries beside 12 B a parameter of arguments (12,035,358,720 B
    offline with each layer's selection kept for the backward pass,
    11,731,404,800 found again there; the compiler's own report, 14.52 GiB
    in all, stands 0.56 GiB over the Laguna cell's step, which holds 14.29
    GiB on the chip). The indexer's loss walks the queries once, in the
    forward pass, and the layer's recomputation keeps its gradient: a layer
    holds two calls of the index scores' forward kernel (the selection and
    that walk; three while the backward pass walked again) and one of its
    backward kernel."""
    import re

    import jax
    import numpy as np

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, params = _sequence_step(topo, "keyevl2-30b-ep8")
    parameters = 659_190_016
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == parameters
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 1.02 * 12_035_358_720
    assert mem.argument_size_in_bytes == pytest.approx(12 * parameters, rel=2e-3)
    assert mem.alias_size_in_bytes >= 12 * parameters  # the state is donated
    # the attention's two kernels and the grouped matmuls' run, every layer
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    index_calls = sorted(re.findall(
        r"^\s*(?:ROOT )?%\w*?(index_scores_(?:fwd|bwd))_?[.\d]* = "
        r".*tpu_custom_call", text, re.MULTILINE))
    assert index_calls == ["index_scores_bwd"] + ["index_scores_fwd"] * 2


def _accepted_step(topo, config_name):
    """``chunk_staged`` of a sequence cell lowered for one described chip,
    its params read as its own driver reads them (the mla/moe cell's
    ``train_seq.py``, the others' ``train_seq_cfg.py``)."""
    import dataclasses
    import json
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run

    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.models import seqrec
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh
    from pio_tpu.templates.sequence import SeqRecParams

    with open(os.path.join(bench, "configs", config_name + ".json")) as f:
        config = json.load(f)
    if "harness" in config:
        driver = run.load_module("drivers", "train_seq_cfg")
        params = driver.algorithm_params(
            config, driver.reference_module(config).model(config), 1)
    else:
        params = run.load_module("drivers", "train_seq").algorithm_params(
            config, 1)
    p = params_from_dict(SeqRecParams, params)
    cfg = seqrec.SeqRecConfig(**{f.name: getattr(p, f.name) for f in
                                 dataclasses.fields(seqrec.SeqRecConfig)})
    mesh = build_mesh(MeshSpec(data=-1), devices=topo.devices[:1])
    rows = int(config["data"]["n_histories"])
    vocab = int(config["data"]["n_items"]) + 1
    prog = seqrec._programs(dataclasses.replace(cfg, seed=0, steps=0), mesh,
                            vocab, cfg.batch_size, rows // cfg.batch_size)
    params = jax.eval_shape(prog.init, jnp.int32(0))
    whole = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        (jax.ShapeDtypeStruct((), jnp.int32), params,
         jax.eval_shape(prog.opt_init, params)))
    epoch = tuple(jax.ShapeDtypeStruct(
        (rows, cfg.max_len), dtype, sharding=NamedSharding(mesh, P("data", "seq")))
        for dtype in (jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.float32))
    return prog.chunk_staged.lower(state, epoch, cfg.steps)


@pytest.mark.parametrize("config_name,n_ops,digest", [
    ("glm47flash-ep8", 8380, "9fb967fe21dfa794"),
    ("laguna-s21-ep32", 12410, "be44f69f6aed8e1a"),
    ("nemotron3nano-ep16", 12110, "6e897d2d7f514f6e"),
    ("granite4hmicro-vp8", 8838, "ab7e7cd12abe8fff"),
])
def test_the_accepted_cells_steps_lower_to_the_same_operations(
        topo, monkeypatch, config_name, n_ops, digest):
    """The four accepted sequence cells' steps, lowered as a TPU lowers them,
    hold the same StableHLO operations, kind by kind, as before the sparse
    layer kind, the q/k norms and the selection's path through the attention
    tiles were added (counted on the code without them): every new operation
    applies only where set. The text itself differs by the Pallas kernels'
    embedded source locations, so the histogram is compared."""
    import collections
    import hashlib
    import json
    import re

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _accepted_step(topo, config_name).as_text()
    ops = collections.Counter(re.findall(r"= (?:\"?)([a-z_]+\.[a-z_0-9.]+)", text))
    assert sum(ops.values()) == n_ops
    assert hashlib.sha256(json.dumps(sorted(ops.items())).encode()).hexdigest()[
        :16] == digest
