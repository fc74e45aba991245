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
