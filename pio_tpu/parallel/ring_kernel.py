"""The tiles of :func:`pio_tpu.parallel.ring.attention_partial` as two
Pallas TPU kernels: one forward, one backward.

What :mod:`pio_tpu.parallel.ring` runs as a ``fori_loop`` whose body XLA
cuts into fusions (every score tile written to HBM and read again around
the max, the exp, the mask and the cast; the backward's ``dk``/``dv`` carried
as whole float32 arrays and updated a block a tile) runs here with the tile
in VMEM:

- **forward**: grid ``(batch, KV head, query sub-tile)``; the key blocks of
  a query tile are a loop inside the kernel between the two bounds the
  caller computed (``first_key_block``, ``needed_key_blocks``), which arrive
  by scalar prefetch. ``o``, ``m``, ``l`` are VMEM scratch.
- **backward**: the same grid and the same loop, five matmuls a tile from
  one recomputed score tile (transposed, ``[keys, queries]``, so that
  ``lse`` and ``g`` are lane rows and ``dk``/``dv`` need no transpose).
  ``dq`` of a query sub-tile is VMEM scratch, written once; ``dk`` and
  ``dv`` of one (batch, KV head) are float32 VMEM scratch across all of its
  query tiles and written once, in ``k``'s dtype.

Under a learned selection of keys (``bits``: ``ring.pack_selection``) the
two scalar arrays are each query block's active key blocks and their count
(``ring.selected_blocks``); a query sub-tile's words of the mask stand in
VMEM beside it, and every tile is masked by the selection, unpacked from
its ``W`` word rows: row ``r`` of the tile is bit ``r // W`` of word row
``r mod W``.

``k`` and ``v`` of one (batch, KV head) stay whole in VMEM (:func:`fits`
says whether they can). The arithmetic is the XLA form's: operands in the
dtype given to both matmuls, float32 scores, softmax and accumulators,
``p`` and ``ds`` cast before their matmuls; only the order of float32
additions differs. A key block that a query tile sees whole runs without
its mask (the mask would select every score).
"""

from __future__ import annotations

import functools

_NEG_BIG = -1e30
_LANES = 128
#: most query rows of one grid step: a KV head's ``group`` query heads are
#: taken this many rows at a time (whole heads of ``bq`` positions). On a
#: v5e, 512 / 1,024 / 2,048 rows read within 5% of each other at the three
#: cells' grouped shapes, 2,048 the least in five of six (PERF.md section 6,
#: PR 38)
TILE_ROWS = 2048
#: what a kernel may hold in VMEM (a v5e core has 128 MiB), and what
#: ``k``, ``v`` and the backward's ``dk``, ``dv`` of one (batch, KV head)
#: may take of it
VMEM_LIMIT, RESIDENT_LIMIT = 100 * 2 ** 20, 64 * 2 ** 20


def fits(key_rows: int, d_k: int, d_v: int, itemsize: int) -> bool:
    """Whether one (batch, KV head)'s ``k`` and ``v`` (two buffers each, in
    and, as ``dk`` and ``dv``, out) and the float32 ``dk``/``dv``
    accumulators fit the kernels' VMEM share."""
    return key_rows * (d_k + d_v) * (4 * itemsize + 4) <= RESIDENT_LIMIT


def sub_heads(group: int, bq: int, rows: int) -> int:
    """How many of a KV head's ``group`` query heads one grid step takes:
    the largest divisor of ``group`` whose rows stay within ``rows``."""
    return max(h for h in range(1, group + 1)
               if group % h == 0 and (h == 1 or h * bq <= rows))


def _visible(delta, bq: int, bk: int, window: int, heads: int,
             transposed: bool):
    """Which scores of a tile are seen: query row ``r`` (position ``r mod
    bq``) sees key ``c`` iff ``0 <= delta + r - c`` (``< window``), ``delta``
    being the first query's position less the first key's."""
    import jax
    import jax.numpy as jnp

    shape = (bk, bq) if transposed else (bq, bk)
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transposed else 1)
    d = delta + r - c
    seen = d >= 0
    if window:
        seen = jnp.logical_and(seen, d < window)
    return jnp.tile(seen, (1, heads) if transposed else (heads, 1))


def _chosen(bits_ref, j, bq: int, bk: int, heads: int, transposed: bool):
    """Which scores of tile ``j`` the selection keeps: the block's ``W``
    word rows unpacked, ``[bq, bk]`` (transposed ``[bk, bq]``) tiled over
    the sub-tile's heads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from pio_tpu.parallel.ring import SELECT_BITS

    w = bits_ref.shape[0]
    words = bits_ref[:, pl.ds(pl.multiple_of(j * bk, bk), bk)]  # [W, bk]
    rows = jnp.tile(words, (SELECT_BITS, 1))  # row r: word row r mod W
    shift = jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0),
        jnp.int32(w.bit_length() - 1))  # r // W, W a power of two
    bit = jax.lax.shift_right_logical(rows, shift) & 1
    if transposed:
        bit = bit.T
    seen = bit != 0
    return jnp.tile(seen, (1, heads) if transposed else (heads, 1))


def _chosen_loop(order_ref, count, i, nk: int, tile):
    """Run ``tile(j, None, True)`` for the active key blocks ``order[i * nk
    + idx]``, ``idx < count``."""
    import jax

    def body(idx, carry):
        tile(order_ref[i * nk + idx], None, True)
        return carry

    jax.lax.fori_loop(0, count, body, 0)


def _tile_loop(first, n, base, bq: int, bk: int, window: int, tile):
    """Run ``tile(j, delta, masked)`` for the key blocks ``first <= j < n``
    (``delta``: the first query's position less the block's first key's);
    ``masked`` is static: a block the query tile sees whole runs the body
    compiled without the mask."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def body(j, carry):
        delta = base - j * bk
        whole = delta >= bk - 1
        if window:
            whole = jnp.logical_and(whole, delta + bq - 1 < window)
        pl.when(whole)(lambda: tile(j, delta, False))
        pl.when(jnp.logical_not(whole))(lambda: tile(j, delta, True))
        return carry

    jax.lax.fori_loop(first, n, body, 0)


_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _fwd_kernel(first_ref, n_ref, off_ref, q_ref, k_ref, v_ref, *refs, scale,
                bq, bk, heads, subs, window, nk=0):
    """``nk`` > 0: under a selection (``first_ref`` the active blocks,
    ``n_ref`` their counts, the first of ``refs`` the mask's words)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    bits_ref = refs[0] if nk else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[1:] if nk else refs
    f32 = jnp.float32
    i = pl.program_id(2) // subs  # the query block of this sub-tile
    base = off_ref[0] - off_ref[1] + i * bq
    dv = v_ref.shape[-1]
    m_scr[...] = jnp.full(m_scr.shape, _NEG_BIG, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    def tile(j, delta, masked):
        keys = pl.ds(pl.multiple_of(j * bk, bk), bk)
        kj, vj = k_ref[keys, :], v_ref[keys, :]
        s = jax.lax.dot_general(q_ref[...], kj, _NT,
                                preferred_element_type=f32) * scale
        if masked:
            seen = (_chosen(bits_ref, j, bq, bk, heads, False) if nk else
                    _visible(delta, bq, bk, window, heads, False))
            s = jnp.where(seen, s, _NEG_BIG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_new, (1, bk // _LANES)))
        if masked:
            p = jnp.where(seen, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = (
            acc_scr[...] * jnp.tile(corr, (1, dv // _LANES))
            + jnp.dot(p.astype(vj.dtype), vj, preferred_element_type=f32))

    if nk:
        _chosen_loop(first_ref, n_ref[i], i, nk, tile)
    else:
        _tile_loop(first_ref[i], n_ref[i], base, bq, bk, window, tile)
    l = l_scr[...]
    safe = jnp.maximum(l, 1e-30)
    o_ref[...] = acc_scr[...] / jnp.tile(safe, (1, dv // _LANES))
    lse = jnp.where(l > 0, m_scr[...] + jnp.log(safe), _NEG_BIG)
    lse_ref[...] = lse.T[:1]  # the rows' statistics as one lane row


def _bwd_kernel(first_ref, n_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, g_ref, *refs, scale, bq, bk, heads, subs, window,
                nk=0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    bits_ref = refs[0] if nk else None
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs[1:] if nk else refs
    f32 = jnp.float32
    t = pl.program_id(2)
    i = t // subs
    base = off_ref[0] - off_ref[1] + i * bq

    @pl.when(t == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, f32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, f32)

    dq_scr[...] = jnp.zeros(dq_scr.shape, f32)

    def tile(j, delta, masked):
        keys = pl.ds(pl.multiple_of(j * bk, bk), bk)
        kj, vj = k_ref[keys, :], v_ref[keys, :]
        q, do = q_ref[...], do_ref[...]
        # the score tile transposed: [keys, query rows]
        s = jax.lax.dot_general(kj, q, _NT,
                                preferred_element_type=f32) * scale
        if masked:
            seen = (_chosen(bits_ref, j, bq, bk, heads, True) if nk else
                    _visible(delta, bq, bk, window, heads, True))
            s = jnp.where(seen, s, _NEG_BIG)
        p = jnp.exp(s - lse_ref[...])
        if masked:
            p = jnp.where(seen, p, 0.0)
        dv_scr[keys, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=f32)
        dp = jax.lax.dot_general(vj, do, _NT, preferred_element_type=f32)
        ds = (p * (dp + g_ref[...]) * scale).astype(q.dtype)
        dk_scr[keys, :] += jnp.dot(ds, q, preferred_element_type=f32)
        dq_scr[...] += jax.lax.dot_general(
            ds, kj, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    if nk:
        _chosen_loop(first_ref, n_ref[i], i, nk, tile)
    else:
        _tile_loop(first_ref[i], n_ref[i], base, bq, bk, window, tile)
    dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _specs(heads: int, bq: int, tk: int, d_k: int, d_v: int, subs: int = 1):
    """Block specs by operand: a query sub-tile's rows, one (batch, KV
    head)'s whole keys, a sub-tile's lane row of statistics, the words of
    the selection of the sub-tile's query block."""
    from jax.experimental import pallas as pl

    from pio_tpu.parallel.ring import select_words

    rows = heads * bq
    sub = lambda b, h, t, *_: (b, h, t, 0)
    whole = lambda b, h, t, *_: (b, h, 0, 0)
    return dict(
        q=pl.BlockSpec((None, None, rows, d_k), sub),
        o=pl.BlockSpec((None, None, rows, d_v), sub),
        k=pl.BlockSpec((None, None, tk, d_k), whole),
        v=pl.BlockSpec((None, None, tk, d_v), whole),
        row=pl.BlockSpec((None, None, 1, rows), lambda b, h, t, *_: (b, h, 0, t)),
        bits=pl.BlockSpec((None, select_words(bq), tk),
                          lambda b, h, t, *_: (b, t // subs, 0)),
    )


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


@functools.lru_cache(maxsize=32)
def _fwd_call(b: int, h: int, rows_q: int, tk: int, d_k: int, d_v: int,
              dtype: str, scale: float, bq: int, bk: int, window: int,
              group: int, interpret: bool, selected: bool = False):
    """The forward's ``pallas_call`` for one set of shapes, built once (a
    step calls it from several layers, forward and recomputed, and every
    new ``pallas_call`` is traced again: set-up time)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads = sub_heads(group, bq, TILE_ROWS)
    rows, subs = heads * bq, group // heads
    sp = _specs(heads, bq, tk, d_k, d_v, subs)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          heads=heads, subs=subs, window=window,
                          nk=tk // bk if selected else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, rows_q // rows),
            in_specs=[sp["q"], sp["k"], sp["v"]] + [sp["bits"]] * selected,
            out_specs=[sp["o"], sp["row"]],
            scratch_shapes=[pltpu.VMEM((rows, _LANES), f32),
                            pltpu.VMEM((rows, _LANES), f32),
                            pltpu.VMEM((rows, d_v), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, rows_q, d_v), f32),
                   jax.ShapeDtypeStruct((b, h, 1, rows_q), f32)],
        compiler_params=_params(), interpret=interpret,
        name="attn_tiles_fwd")


@functools.lru_cache(maxsize=32)
def _bwd_call(b: int, h: int, rows_q: int, tk: int, d_k: int, d_v: int,
              dtype: str, scale: float, bq: int, bk: int, window: int,
              group: int, interpret: bool, selected: bool = False):
    """The backward's ``pallas_call`` for one set of shapes, built once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads = sub_heads(group, bq, TILE_ROWS)
    rows, subs = heads * bq, group // heads
    sp = _specs(heads, bq, tk, d_k, d_v, subs)
    f32, dt = jnp.float32, jnp.dtype(dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk,
                          heads=heads, subs=subs, window=window,
                          nk=tk // bk if selected else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, rows_q // rows),
            in_specs=[sp["q"], sp["k"], sp["v"], sp["o"], sp["row"],
                      sp["row"]] + [sp["bits"]] * selected,
            out_specs=[sp["q"], sp["k"], sp["v"]],
            scratch_shapes=[pltpu.VMEM((rows, d_k), f32),
                            pltpu.VMEM((tk, d_k), f32),
                            pltpu.VMEM((tk, d_v), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, rows_q, d_k), dt),
                   jax.ShapeDtypeStruct((b, h, tk, d_k), dt),
                   jax.ShapeDtypeStruct((b, h, tk, d_v), dt)],
        compiler_params=_params(), interpret=interpret,
        name="attn_tiles_bwd")


def _shape_key(q, k, v):
    b, h, rows_q, d_k = q.shape
    return b, h, rows_q, k.shape[2], d_k, v.shape[3], q.dtype.name


def forward(q, k, v, first, n, offs, scale, bq, bk, window, group,
            interpret=False, bits=None):
    """``(o float32, lse)`` of ``[B, H, rows, D]`` operands; ``first`` and
    ``n`` ``[nq]`` int32 are the key-block bounds of each query block and
    ``offs`` ``[2]`` the positions of the first query and key. With the
    selection's ``bits [B, nq * W, Tk]``, ``first`` is ``[nq * nk]``: each
    query block's active key blocks, and ``n`` their counts."""
    selected = bits is not None
    call = _fwd_call(*_shape_key(q, k, v), float(scale), bq, bk, window,
                     group, interpret, selected)
    o, lse = call(first, n, offs, q, k, v, *([bits] * selected))
    return o, lse.reshape(lse.shape[0], lse.shape[1], -1)


def backward(q, k, v, do, lse, g, first, n, offs, scale, bq, bk, window,
             group, interpret=False, bits=None):
    """``(dq, dk, dv)`` in the operands' dtypes; ``do`` in ``q``'s dtype,
    ``lse`` and ``g`` ``[B, H, rows]`` float32; ``bits``, ``first`` and
    ``n`` as :func:`forward` takes them."""
    selected = bits is not None
    call = _bwd_call(*_shape_key(q, k, v), float(scale), bq, bk, window,
                     group, interpret, selected)
    row = lambda a: a.reshape(a.shape[0], a.shape[1], 1, -1)
    return call(first, n, offs, q, k, v, do, row(lse), row(g),
                *([bits] * selected))
