"""The lightning indexer's kernels (``pio_tpu.models.index_kernel``) in
interpret mode on the CPU, against the XLA form of
``seq_layers.index_scores``, their oracle, at the sparse cell's head shape
(16 heads of 64) and rows of 2,048-4,096 keys.

Tolerances: the forward computes the XLA form's float32 products, relu,
weights and sums of bfloat16 operands, in another order (1e-5 relative).
The backward stages ``ds`` in bfloat16, as the TPU's MXU takes the XLA
form's float32 ``ds``; against an oracle that rounds ``ds`` so, ``dqi`` and
``dki`` are within one bfloat16 rounding of their own; against the XLA form
as the CPU computes it (float32 ``ds``) within ``2 ** -8`` of their norm.
``dw`` is float32 on both sides.
"""

import numpy as np
import pytest

from pio_tpu.models import index_kernel, seq_layers

HI, DI = 16, 64


def _operands(t, n, t0, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    qi = jnp.asarray(rng.standard_normal((2, n, HI, DI)), jnp.bfloat16)
    ki = jnp.asarray(rng.standard_normal((2, t, DI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((2, n, HI)) * HI ** -0.5, jnp.float32)
    return qi, ki, w, t0


def _scores(impl, qi, ki, w, t0):
    """The kernels (interpreted) or their oracle, the XLA form."""
    import jax.numpy as jnp

    if impl == "xla":
        return seq_layers.index_scores(qi, ki, w, t0, jnp.bfloat16)
    return index_kernel.scores(qi, ki, w, jnp.int32(t0), interpret=True)


def _small_tiles(monkeypatch, tile, chunk):
    monkeypatch.setattr(index_kernel, "KEY_TILE", tile)
    monkeypatch.setattr(index_kernel, "KEY_CHUNK", chunk)


#: (keys, queries of the block, its first position, key tile, chunk): the
#: first block, a block on a tile's diagonal, a later block, two tiles of
#: two chunks, a block of 32 queries inside a chunk
CASES = [(2048, 128, 0, 2048, 512), (2048, 128, 896, 2048, 512),
         (2048, 128, 1920, 2048, 512), (4096, 128, 2432, 1024, 256),
         (2048, 32, 1000, 1024, 256)]


@pytest.mark.parametrize("t,n,t0,tile,chunk", CASES)
def test_the_forward_kernel_gives_the_xla_scores(monkeypatch, t, n, t0, tile,
                                                 chunk):
    _small_tiles(monkeypatch, tile, chunk)
    args = _operands(t, n, t0)
    got = np.asarray(_scores("pallas_interpret", *args))
    want = np.asarray(_scores("xla", *args))
    seen = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), seen)
    assert (got[~seen] == -np.inf).all() and (want[~seen] == -np.inf).all()
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5,
                               atol=1e-6 * np.abs(want[seen]).max())


def _mxu_vjp(qi, ki, w, t0, g):
    """``(dqi, dki, dw)`` of the XLA form as a TPU computes it: ``ds``
    rounded to bfloat16 before its two matmuls, float32 accumulation."""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    s = jnp.einsum("bqhd,bkd->bqhk", qi, ki, preferred_element_type=f32)
    n, t = g.shape[1:]
    seen = jnp.arange(t)[None, :] <= (t0 + jnp.arange(n))[:, None]
    du = jnp.where(seen[None], g, 0.0) * f32(DI ** -0.5)
    dw = (jax.nn.relu(s) * du[:, :, None]).sum(axis=-1)
    ds = jnp.where(s > 0, du[:, :, None] * w[..., None], 0.0).astype(bf16)
    dqi = jnp.einsum("bqhk,bkd->bqhd", ds, ki, preferred_element_type=f32)
    dki = jnp.einsum("bqhk,bqhd->bkd", ds, qi, preferred_element_type=f32)
    return dqi.astype(bf16), dki.astype(bf16), dw


def _selected_cotangent(t, n, t0, share=0.3, seed=1):
    """A cotangent that is zero off a random selection and after each
    query, as the indexer's loss hands it back."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    seen = np.arange(t)[None, :] <= t0 + np.arange(n)[:, None]
    sel = seen[None] & (rng.random((2, n, t)) < share)
    return jnp.asarray(np.where(sel, rng.standard_normal((2, n, t)), 0.0),
                       jnp.float32)


@pytest.mark.parametrize("t,n,t0,tile,chunk", CASES)
def test_the_backward_kernel_gives_the_xla_gradients(monkeypatch, t, n, t0,
                                                     tile, chunk):
    import jax

    _small_tiles(monkeypatch, tile, chunk)
    qi, ki, w, t0 = _operands(t, n, t0)
    g = _selected_cotangent(t, n, t0)

    def vjp(impl):
        _, back = jax.vjp(lambda *a: _scores(impl, *a, t0), qi, ki, w)
        return [np.asarray(x, np.float32) for x in back(g)]

    got, cpu = vjp("pallas_interpret"), vjp("xla")
    mxu = [np.asarray(x, np.float32) for x in _mxu_vjp(qi, ki, w, t0, g)]
    for name, a, b, c in zip(("dqi", "dki", "dw"), got, mxu, cpu):
        assert a.shape == c.shape, name
        if name == "dw":
            np.testing.assert_allclose(a, c, rtol=1e-5,
                                       atol=1e-6 * np.abs(c).max())
            continue
        # within one rounding to bfloat16 of the TPU's own
        np.testing.assert_allclose(a, b, rtol=2.0 ** -7,
                                   atol=1e-3 * np.abs(b).max(), err_msg=name)
        assert np.linalg.norm(a - c) <= 2.0 ** -8 * np.linalg.norm(c), name
    # keys after the block's last query take no gradient
    assert not got[1][:, t0 + n:].any()


@pytest.mark.parametrize("t0,first_bad", [(640, 768), (896, 1024)])
def test_key_chunks_after_the_block_are_never_read(monkeypatch, t0,
                                                   first_bad):
    """Keys poisoned from the chunk after the block's diagonal chunk on (a
    chunk of the diagonal's tile; the next tile): a masked product would
    carry the NaN into ``dw``; a chunk or tile that is skipped never reads
    them, forward or backward."""
    import jax
    import jax.numpy as jnp

    _small_tiles(monkeypatch, 1024, 256)
    t, n = 4096, 128
    qi, ki, w, t0 = _operands(t, n, t0, seed=3)
    bad = ki.at[:, first_bad:].set(jnp.nan)
    g = _selected_cotangent(t, n, t0, share=1.0)

    def run(ki):
        s, back = jax.vjp(lambda *a: _scores("pallas_interpret", *a, t0),
                          qi, ki, w)
        return [np.asarray(x, np.float32) for x in (s,) + back(g)]

    got, want = run(bad), run(ki)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(got[3]).all()


def test_the_kernels_selection_is_the_xla_forms():
    """``top_keys`` of 2,048 keys a query from the kernel's scores takes the
    XLA form's keys, but at rows whose 2,048th and 2,049th scores lie within
    the scores' tolerance of each other."""
    import jax.numpy as jnp

    k, t, n, t0 = 2048, 4096, 128, 3072
    args = _operands(t, n, t0, seed=5)
    got = _scores("pallas_interpret", *args)
    want = _scores("xla", *args)
    sel, _ = seq_layers.top_keys(got, k)
    sel_x, _ = seq_layers.top_keys(want, k)
    ordered = -np.sort(-np.asarray(want), axis=-1)
    kth, after = ordered[..., k - 1], ordered[..., k]
    close = np.abs(kth - after) <= 1e-5 * np.abs(kth) + 1e-6 * np.abs(
        ordered[..., 0])
    same = (np.asarray(sel) == np.asarray(sel_x)).all(axis=-1)
    assert (same | close).all()
    assert same.mean() > 0.9
    assert int(jnp.asarray(sel).sum()) == 2 * n * k


def test_the_chooser_admits_the_cells_blocks_alone():
    """``pallas`` for the sparse cell's blocks (128 queries of 16 heads of 64
    against 16,384 keys, bfloat16) on a TPU; ``xla`` on the CPU, for float32
    operands, for a key count no tile divides and for narrow heads."""
    bf, f32 = "bfloat16", "float32"
    assert seq_layers.index_impl("tpu", bf, HI, DI, 128, 16384) == "pallas"
    assert seq_layers.index_impl("tpu", bf, HI, DI, 128, 8192) == "pallas"
    assert seq_layers.index_impl("cpu", bf, HI, DI, 128, 16384) == "xla"
    assert seq_layers.index_impl("tpu", f32, HI, DI, 128, 16384) == "xla"
    assert seq_layers.index_impl("tpu", bf, HI, DI, 128, 16384 + 512) == "xla"
    assert seq_layers.index_impl("tpu", bf, HI, DI, 128, 1000) == "xla"
    assert seq_layers.index_impl("tpu", bf, HI, 8, 128, 16384) == "xla"
    assert seq_layers.index_impl("tpu", bf, HI, DI, 8, 16384) == "xla"
    # a block of 1,024 queries is 16,384 rows of products a chunk: more
    # VMEM than the kernels' share
    assert seq_layers.index_impl("tpu", bf, HI, DI, 1024, 16384) == "xla"
