"""Tests for the parallel subsystem: meshes, ring attention, pipelining.

Run on the simulated 8-device CPU mesh (tests/conftest.py) — the analog of
the reference testing Spark code on ``local[*]`` (SURVEY.md §4).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pio_tpu.parallel import (
    MeshSpec,
    build_mesh,
    mesh_axis_size,
    pipeline_apply,
    ring_attention,
    ring_attention_sharded,
    stage_slice,
)


# ---------------------------------------------------------------- mesh spec
def test_mesh_spec_sizes_defaults():
    assert MeshSpec().sizes(8) == {
        "data": 8, "pipe": 1, "seq": 1, "model": 1,
    }


def test_mesh_spec_fixed_axes():
    sizes = MeshSpec(data=-1, seq=2, model=2).sizes(8)
    assert sizes == {"data": 2, "pipe": 1, "seq": 2, "model": 2}


def test_mesh_spec_indivisible_raises():
    with pytest.raises(ValueError):
        MeshSpec(data=-1, model=3).sizes(8)
    with pytest.raises(ValueError):
        MeshSpec(data=4, model=4).sizes(8)


def test_build_mesh_axes():
    mesh = build_mesh(MeshSpec(data=2, seq=2, model=2))
    assert mesh.shape["data"] == 2
    assert mesh.shape["pipe"] == 1
    assert mesh_axis_size(mesh, "seq") == 2
    assert mesh_axis_size(None, "seq") == 1
    assert mesh_axis_size(mesh, "nope") == 1


# ------------------------------------------------------------ ring attention
def _dense_attention(q, k, v, causal):
    """Reference: plain softmax attention in float64-ish numpy."""
    b, t, h, d = q.shape
    scores = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64)
    scores /= np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((t, t), bool))
        scores = np.where(mask[None, None], scores, -np.inf)
    scores -= scores.max(axis=-1, keepdims=True)
    p = np.exp(scores)
    p /= p.sum(axis=-1, keepdims=True)
    out = np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))
    return out.astype(np.float32)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_single_device_matches_dense(causal):
    rng = np.random.default_rng(0)
    q, k, v = (
        rng.normal(size=(2, 16, 2, 8)).astype(np.float32) for _ in range(3)
    )
    out = ring_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        axis=None, causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out), _dense_attention(q, k, v, causal),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_sharded_matches_dense(causal):
    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(1)
    b, t, h, d = 4, 32, 2, 8  # t=32 → 8 positions per seq shard
    q, k, v = (
        rng.normal(size=(b, t, h, d)).astype(np.float32) for _ in range(3)
    )
    out = jax.jit(
        lambda q, k, v: ring_attention_sharded(
            mesh, q, k, v, causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), _dense_attention(q, k, v, causal),
        rtol=1e-4, atol=1e-5,
    )


def test_ring_attention_sharded_grads_flow():
    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 16, 1, 8)), jnp.float32)
        for _ in range(3)
    )

    def loss(q, k, v):
        return ring_attention_sharded(mesh, q, k, v, causal=True).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0


# ----------------------------------------------------- ulysses (all-to-all)
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_single_device_matches_dense(causal):
    from pio_tpu.parallel import ulysses_attention

    rng = np.random.default_rng(3)
    q, k, v = (
        rng.normal(size=(2, 16, 2, 8)).astype(np.float32) for _ in range(3)
    )
    out = ulysses_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        axis=None, causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out), _dense_attention(q, k, v, causal),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_sharded_matches_dense(causal):
    from pio_tpu.parallel import ulysses_attention_sharded

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(4)
    b, t, h, d = 4, 32, 4, 8  # h=4 heads over seq=4 devices
    q, k, v = (
        rng.normal(size=(b, t, h, d)).astype(np.float32) for _ in range(3)
    )
    out = jax.jit(
        lambda q, k, v: ulysses_attention_sharded(
            mesh, q, k, v, causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), _dense_attention(q, k, v, causal),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_ring(causal):
    """Both SP modes are exact attention — identical up to float noise."""
    from pio_tpu.parallel import ulysses_attention_sharded

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(5)
    q, k, v = (
        rng.normal(size=(2, 32, 4, 8)).astype(np.float32) for _ in range(3)
    )
    ring = ring_attention_sharded(mesh, q, k, v, causal=causal)
    uly = ulysses_attention_sharded(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(uly), rtol=1e-4, atol=1e-5
    )


def test_ulysses_rejects_indivisible_heads():
    from pio_tpu.parallel import ulysses_attention_sharded

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(6)
    q, k, v = (
        rng.normal(size=(2, 32, 3, 8)).astype(np.float32)  # 3 heads, n=4
        for _ in range(3)
    )
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(mesh, q, k, v)


def test_ulysses_sharded_grads_flow():
    from pio_tpu.parallel import ulysses_attention_sharded

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
        for _ in range(3)
    )

    def loss(q, k, v):
        return ulysses_attention_sharded(mesh, q, k, v, causal=True).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0


# ------------------------------------------------------------------ pipeline
def test_pipeline_apply_matches_sequential():
    """4-stage pipeline over the pipe axis ≡ applying the stages in order."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_stages, n_micro, mb, f = 4, 6, 4, 8
    mesh = build_mesh(MeshSpec(data=2, pipe=4))
    rng = np.random.default_rng(3)
    w = rng.normal(size=(n_stages, f, f)).astype(np.float32) * 0.3
    b = rng.normal(size=(n_stages, f)).astype(np.float32) * 0.1
    x = rng.normal(size=(n_micro, mb, f)).astype(np.float32)

    def stage(params, h):
        w, b = params
        return jnp.tanh(h @ w + b)

    def run(w, b, x):
        def inner(w_blk, b_blk, x_loc):
            params = stage_slice((w_blk, b_blk))
            return pipeline_apply(params, x_loc, stage)

        return shard_map(
            inner, mesh=mesh,
            in_specs=(P("pipe"), P("pipe"), P(None, "data")),
            out_specs=P(None, "data"),
            check_vma=False,
        )(w, b, x)

    got = np.asarray(jax.jit(run)(w, b, x))

    want = x
    for s in range(n_stages):
        want = np.tanh(want @ w[s] + b[s])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_pipeline_apply_differentiable():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(MeshSpec(data=2, pipe=4))
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32) * 0.3
    x = jnp.asarray(rng.normal(size=(5, 2, 8)), jnp.float32)

    def loss(w, x):
        def inner(w_blk, x_loc):
            return pipeline_apply(
                stage_slice(w_blk), x_loc, lambda p, h: jnp.tanh(h @ p)
            )

        out = shard_map(
            inner, mesh=mesh,
            in_specs=(P("pipe"), P()),
            out_specs=P(),
            check_vma=False,
        )(w, x)
        return (out ** 2).sum()

    g = jax.jit(jax.grad(loss))(w, x)
    g = np.asarray(g)
    assert np.isfinite(g).all()
    # every stage's weights get gradient
    assert (np.abs(g).reshape(4, -1).sum(axis=1) > 0).all()
