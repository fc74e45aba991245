"""Pallas ops tests — run on CPU via interpret mode (conftest pins cpu).

The TPU-compiled path is exercised by chip_smoke.py and the driver's
real-chip runs; here the same kernel body runs under the Pallas interpreter and must
match the XLA fallback bit-for-bit-ish (f32 tolerances).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.ops.embedding import (
    _embedding_bag_pallas,
    _embedding_bag_xla,
    embedding_bag,
    pack_bags,
)


@pytest.fixture()
def bag_case():
    rng = np.random.default_rng(7)
    V, D, B, L = 64, 128, 5, 11
    table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
    ids, w = pack_bags(
        [rng.integers(0, V, size=rng.integers(1, L)) for _ in range(B)],
        [rng.random(L) for _ in range(B)],
    )
    return table, jnp.asarray(ids), jnp.asarray(w)


def test_pack_bags_pads_and_zero_weights():
    ids, w = pack_bags([[3, 4], [5]], [[1.0, 2.0], [0.5]])
    assert ids.shape == w.shape
    assert ids.shape[1] % 8 == 0
    assert ids[0, 0] == 3 and w[0, 1] == 2.0
    assert w[1, 1:].sum() == 0.0  # padding contributes nothing


def test_kernel_matches_xla_interpret(bag_case):
    table, ids, w = bag_case
    ref = _embedding_bag_xla(table, ids, w)
    out = _embedding_bag_pallas(table, ids, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_embedding_bag_dispatch_cpu(bag_case):
    # on CPU the public entry point takes the XLA path
    table, ids, w = bag_case
    out = embedding_bag(table, ids, w)
    ref = _embedding_bag_xla(table, ids, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_embedding_bag_grads_match_explicit(bag_case):
    table, ids, w = bag_case

    def loss_custom(t, ww):
        return jnp.sum(embedding_bag(t, ids, ww) ** 2)

    def loss_explicit(t, ww):
        rows = t[ids]
        out = jnp.einsum("bld,bl->bd", rows, ww)
        return jnp.sum(out**2)

    g1t, g1w = jax.grad(loss_custom, argnums=(0, 1))(table, w)
    g2t, g2w = jax.grad(loss_explicit, argnums=(0, 1))(table, w)
    np.testing.assert_allclose(np.asarray(g1t), np.asarray(g2t), atol=1e-3)
    np.testing.assert_allclose(np.asarray(g1w), np.asarray(g2w), atol=1e-3)


def test_duplicate_ids_accumulate():
    table = jnp.asarray(np.eye(8, 128, dtype=np.float32))
    ids = jnp.asarray([[2, 2, 2, 0, 0, 0, 0, 0]], jnp.int32)
    w = jnp.asarray([[1.0, 2.0, 3.0, 0, 0, 0, 0, 0]], jnp.float32)
    out = embedding_bag(table, ids, w)
    assert float(out[0, 2]) == pytest.approx(6.0)


def test_embedding_bag_dispatch_by_intermediate_size(monkeypatch):
    """Dispatch policy: XLA while the gathered [B, L, D] intermediate is
    small (measured faster at equal accuracy on TPU), the Pallas
    streaming kernel beyond the cutoff (O(1) scratch)."""
    import pio_tpu.ops.embedding as emb

    calls = []
    monkeypatch.delenv("PIO_TPU_EMBED_PALLAS_OVER_MB", raising=False)
    monkeypatch.setattr(emb, "_use_pallas", lambda t: True)
    monkeypatch.setattr(
        emb, "_embedding_bag_pallas",
        lambda t, i, w: calls.append("pallas") or emb._embedding_bag_xla(
            t, i, w
        ),
    )
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (4, 8)).astype(np.int32))
    w = jnp.asarray(rng.random((4, 8)).astype(np.float32))
    # 4*8*128*4 B = 16 KB — far under any sane cutoff → XLA
    emb.embedding_bag.__wrapped__(table, ids, w)
    assert calls == []
    # force a 1-byte cutoff → kernel path
    monkeypatch.setenv("PIO_TPU_EMBED_PALLAS_OVER_MB", "0.000001")
    emb.embedding_bag.__wrapped__(table, ids, w)
    assert calls == ["pallas"]
