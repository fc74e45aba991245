"""The blocked attention of ``parallel/ring.py`` against the one-block
update it replaced: a dense ``[T, T]`` softmax, forward and gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pio_tpu.parallel import ring
from pio_tpu.parallel.mesh import MeshSpec, build_mesh
from pio_tpu.parallel.ring import (
    attention_partial,
    merge_partials,
    needed_key_blocks,
    pick_block,
    ring_attention,
    ring_attention_sharded,
)

B, T, H, D, DV = 2, 64, 3, 16, 24


def one_block(q, k, v, causal=True):
    """What ``ring_attention`` did without a ``seq`` axis before PR 28: one
    update over the whole row, the ``[B, H, T, T]`` scores standing."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.fixture(scope="module")
def qkvw():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(keys[0], (B, T, H, D)),
            jax.random.normal(keys[1], (B, T, H, D)),
            jax.random.normal(keys[2], (B, T, H, DV)),
            jax.random.normal(keys[3], (B, T, H, DV)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [8, 16, 64])
def test_forward_and_gradients_match_the_one_block_update(qkvw, causal, block):
    q, k, v, w = qkvw

    def blocked(q, k, v):
        return (ring_attention(q, k, v, axis=None, causal=causal,
                               block=block) * w).sum()

    def dense(q, k, v):
        return (one_block(q, k, v, causal) * w).sum()

    got = jax.jit(jax.value_and_grad(blocked, (0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=5e-6)


def test_blocks_above_the_diagonal_are_skipped_not_masked(qkvw):
    """Keys past the first query block are poisoned: a masked tile would
    multiply 0 by NaN; a skipped one never reads them. Forward, and the
    backward pass's own loop over the same tiles."""
    q, k, v, w = qkvw
    blk = 16
    bad_k = k.at[:, blk:].set(jnp.nan)
    bad_v = v.at[:, blk:].set(jnp.nan)

    def first_block(q, k, v):
        out = ring_attention(q, k, v, axis=None, causal=True, block=blk)
        return (out[:, :blk] * w[:, :blk]).sum()

    got = jax.value_and_grad(first_block, (0, 1, 2))(q, bad_k, bad_v)
    want = jax.value_and_grad(first_block, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    # dq of the first query block comes from its own tiles alone
    np.testing.assert_allclose(got[1][0][:, :blk], want[1][0][:, :blk], atol=1e-6)


@pytest.mark.parametrize("i, q_off, k_off, want", [
    (0, 0, 0, 1), (3, 0, 0, 4), (7, 0, 0, 8),      # the diagonal, one row
    (0, 64, 0, 8), (0, 0, 64, 0), (2, 32, 64, 0),  # a ring step past / ahead
    (1, 64, 64, 2), (5, 16, 48, 2),
])
def test_needed_key_blocks(i, q_off, k_off, want):
    assert int(needed_key_blocks(i, q_off, k_off, 8, 8, 8, True)) == want
    assert needed_key_blocks(i, q_off, k_off, 8, 8, 8, False) == 8


def test_the_work_is_the_lower_triangle():
    """Over a row of nq blocks the loops visit nq (nq + 1) / 2 tiles."""
    nq = 16
    visited = sum(int(needed_key_blocks(i, 0, 0, 512, 512, nq, True))
                  for i in range(nq))
    assert visited == nq * (nq + 1) // 2


@pytest.mark.parametrize("t, block, want", [(8192, 512, 512), (64, 512, 64),
                                            (96, 64, 48), (17, 8, 1)])
def test_pick_block(t, block, want):
    assert pick_block(t, block) == want


def test_partials_over_disjoint_keys_merge_exactly(qkvw):
    q, k, v, _w = qkvw
    qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    scale = D ** -0.5
    whole = attention_partial(qh, kh, vh, 0, 0, False, scale, 16, 16)
    half = T // 2
    a = attention_partial(qh, kh[:, :, :half], vh[:, :, :half], 0, 0, False,
                          scale, 16, 16)
    b = attention_partial(qh, kh[:, :, half:], vh[:, :, half:], 0, half,
                          False, scale, 16, 16)
    merged = merge_partials(*a[:2], *b[:2])  # the third is the tile count
    np.testing.assert_allclose(merged[0], whole[0], atol=2e-6)
    np.testing.assert_allclose(merged[1], whole[1], atol=2e-6)


@pytest.mark.parametrize("block", [4, 16])
def test_the_ring_rides_the_same_blocked_update(qkvw, block, monkeypatch):
    """With a ``seq`` axis every ring step is the blocked update: outputs and
    gradients equal the single-device row's."""
    q, k, v, w = qkvw
    qs, ks, vs, ws = (a[..., :D] for a in (q, k, v, w))
    monkeypatch.setattr(ring, "DEFAULT_BLOCK", block)
    mesh = build_mesh(MeshSpec(data=2, seq=4))

    def sharded(q, k, v):
        return (ring_attention_sharded(mesh, q, k, v, causal=True) * ws).sum()

    def dense(q, k, v):
        return (one_block(q, k, v, True) * ws).sum()

    got = jax.jit(jax.value_and_grad(sharded, (0, 1, 2)))(qs, ks, vs)
    want = jax.value_and_grad(dense, (0, 1, 2))(qs, ks, vs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=5e-6)


def test_bfloat16_operands_keep_float32_softmax(qkvw):
    q, k, v, _w = qkvw
    out = ring_attention(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                         axis=None, causal=True, block=16)
    assert out.dtype == jnp.bfloat16
    want = one_block(*(a.astype(jnp.bfloat16).astype(jnp.float32)
                       for a in (q, k, v)))
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=0.03)


# -- the tiles as Pallas kernels (interpret mode) against XLA's loops -------

KB = 128  # the kernels' least block: one lane tile


def _folded(case, dtype, seed=0):
    """Operands as ``attention_partial`` takes them: ``q`` tile-major."""
    group, nq, nk, dk, dv = (case[x] for x in ("group", "nq", "nk", "dk", "dv"))
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, h = 1, 2
    rows = nq * KB * group
    return (jax.random.normal(keys[0], (b, h, rows, dk)).astype(dtype),
            jax.random.normal(keys[1], (b, h, nk * KB, dk)).astype(dtype),
            jax.random.normal(keys[2], (b, h, nk * KB, dv)).astype(dtype),
            jax.random.normal(keys[3], (b, h, rows, dv)),
            jax.random.normal(keys[4], (b, h, rows)))


def _case(group=1, window=0, q_off=0, k_off=0, nq=2, nk=2, dk=128, dv=128,
          dtype="bfloat16"):
    return dict(group=group, window=window, q_off=q_off, k_off=k_off, nq=nq,
                nk=nk, dk=dk, dv=dv, dtype=dtype)


KERNEL_CASES = {
    "one_head_a_kv_head": _case(nq=3, nk=3),
    "six_heads_a_kv_head": _case(group=6),
    "window_off_the_block": _case(window=200, nq=3, nk=3),
    "window_and_group": _case(group=3, window=200, nq=3, nk=3),
    "ring_second_step_all_seen": _case(q_off=2 * KB),
    "ring_step_offsets_cross": _case(group=2, q_off=KB, k_off=2 * KB, nq=3,
                                     nk=2),
    "ring_window_behind": _case(window=200, q_off=3 * KB, k_off=KB, nq=2,
                                nk=3),
    "value_width_differs": _case(dk=128, dv=256),
    "float32_operands": _case(group=2, window=200, nq=3, nk=3,
                              dtype="float32"),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_the_kernels_match_xlas_loops(name):
    """Forward ``o``, ``lse`` and ``tiles`` and the three gradients with a
    nonzero ``dlse``, offsets traced as a ring's are. bfloat16 operands keep
    float32 scores, softmax and accumulators on both sides, so the two differ
    by float32 sums in another order (and, in the gradients, by one rounding
    to the operands' dtype)."""
    case = KERNEL_CASES[name]
    dtype = jnp.dtype(case["dtype"])
    q, k, v, w, wl = _folded(case, dtype)
    scale = case["dk"] ** -0.5

    def run(impl):
        def loss(q, k, v, q_off, k_off):
            o, lse, tiles = ring._attention(
                q, k, v, q_off, k_off, True, scale, KB, KB, case["window"],
                case["group"], impl)
            seen = lse > -1e29  # a row that saw no key has no gradient
            return ((o * w).sum() + (jnp.where(seen, lse, 0.0) * wl).sum(),
                    (o, lse, tiles))

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            q, k, v, jnp.int32(case["q_off"]), jnp.int32(case["k_off"]))

    (_, (o, lse, tiles)), grads = run("pallas_interpret")
    (_, (o_x, lse_x, tiles_x)), grads_x = run("xla")
    assert o.dtype == jnp.float32 and lse.dtype == jnp.float32
    assert [int(t) for t in tiles] == [int(t) for t in tiles_x]
    assert int(tiles[0]) > 0
    # a score that differs in its last float32 place can round ``p`` to the
    # other bfloat16 neighbour before ``p v``: one part in 256 of one term
    np.testing.assert_allclose(
        o, o_x, atol=2e-6 if dtype == jnp.float32 else 2e-4)
    np.testing.assert_allclose(lse, lse_x, atol=5e-6)
    for g, g_x in zip(grads, grads_x):
        assert g.dtype == dtype
        # one unit in the last place of a bfloat16 gradient of this size
        tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
        scale_g = float(jnp.abs(g_x.astype(jnp.float32)).max())
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   g_x.astype(jnp.float32),
                                   atol=tol * max(scale_g, 1.0))


def _through_the_kernels(monkeypatch):
    monkeypatch.setattr(ring, "attention_impl",
                        lambda *a, **kw: "pallas_interpret")


def test_the_kernels_tile_counter_reads_the_loops_own_bounds(monkeypatch):
    """The accepted test's twin on the kernel path: with the window's skip
    turned into a mask (the bounds start at key block 0, the kernel's own
    mask hides what lies before the window) the output stands and the
    counter reads visited == causal: it is the bounds the kernel's loop was
    handed, no second computation."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 6 * KB, 2, 128)).astype(jnp.bfloat16)
               for kk in keys)
    _through_the_kernels(monkeypatch)
    want, tiles = ring_attention(q, k, v, axis=None, block=KB, window=KB,
                                 with_tiles=True)
    assert [int(t) for t in tiles] == [11, 21]
    monkeypatch.setattr(ring, "first_key_block", lambda *a: 0)
    masked, tiles = ring_attention(q, k, v, axis=None, block=KB, window=KB,
                                   with_tiles=True)
    np.testing.assert_allclose(masked.astype(jnp.float32),
                               want.astype(jnp.float32), atol=1e-6)
    assert [int(t) for t in tiles] == [21, 21]


def test_the_kernels_skip_blocks_above_the_diagonal(monkeypatch):
    """Keys past the first query block are poisoned, as in the XLA form's
    test: the kernel holds them in VMEM and never reads them, forward or
    backward, for the first query block."""
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v, w = (jax.random.normal(kk, (1, 3 * KB, 4, 128)) for kk in keys)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    bad_k = k.at[:, KB:].set(jnp.nan)
    bad_v = v.at[:, KB:].set(jnp.nan)
    _through_the_kernels(monkeypatch)

    def first_block(q, k, v):
        out = ring_attention(q, k, v, axis=None, causal=True, block=KB)
        return (out[:, :KB] * w[:, :KB]).sum()

    got = jax.value_and_grad(first_block, (0, 1, 2))(q, bad_k, bad_v)
    want = jax.value_and_grad(first_block, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1][0][:, :KB].astype(jnp.float32),
                               want[1][0][:, :KB].astype(jnp.float32),
                               atol=1e-6)


def test_grouped_queries_ride_the_kernels_like_xlas_loops(monkeypatch):
    """``ring_attention`` end to end (fold, kernels, unfold) with six query
    heads a KV head under a window."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, 3 * KB, 12, 128)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (2, 3 * KB, 2, 128)).astype(jnp.bfloat16)
            for kk in keys[1:])
    want = ring_attention(q, k, v, axis=None, block=KB, window=150)
    _through_the_kernels(monkeypatch)
    got = ring_attention(q, k, v, axis=None, block=KB, window=150)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2.0 ** -8)


@pytest.mark.parametrize("platform, dtype, d_k, d_v, causal, want", [
    ("tpu", "bfloat16", 256, 256, True, "pallas"),   # the GLM cell's heads
    ("tpu", "bfloat16", 128, 128, True, "pallas"),   # Laguna's, Nemotron's
    ("tpu", "bfloat16", 128, 256, True, "pallas"),
    ("tpu", "bfloat16", 64, 64, True, "xla"),        # the toy dense block
    ("tpu", "bfloat16", 128, 64, True, "xla"),
    ("tpu", "bfloat16", 192, 128, True, "xla"),      # no multiple of a tile
    ("tpu", "float32", 128, 128, True, "xla"),
    ("tpu", "bfloat16", 128, 128, False, "xla"),
    ("cpu", "bfloat16", 256, 256, True, "xla"),
    ("cpu", "float32", 64, 64, False, "xla"),
    ("gpu", "bfloat16", 128, 128, True, "xla"),
])
def test_attention_impl_reads_platform_dtype_and_shapes(platform, dtype, d_k,
                                                        d_v, causal, want):
    assert ring.attention_impl(platform, dtype, d_k, d_v, 512, 512, causal,
                               8192) == want


def test_attention_impl_keeps_odd_blocks_and_long_rows_on_xla():
    """Blocks off the lane tile (a short row's ``pick_block``), and keys
    whose ``k``, ``v``, ``dk`` and ``dv`` of one head pass the kernels' VMEM
    share; the three cells' rows fit."""
    rule = ring.attention_impl
    assert rule("tpu", "bfloat16", 128, 128, 64, 64, True, 64) == "xla"
    assert rule("tpu", "bfloat16", 128, 128, 512, 96, True, 8192) == "xla"
    assert rule("tpu", "bfloat16", 256, 256, 512, 512, True, 8192) == "pallas"
    assert rule("tpu", "bfloat16", 128, 128, 512, 512, True, 16384) == "pallas"
    assert rule("tpu", "bfloat16", 256, 256, 512, 512, True, 16384) == "xla"
