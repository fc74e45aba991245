"""Ring attention — sequence-parallel exact attention over the ``seq`` axis.

Long-context support is first-class in this framework even though the
reference has no sequence models at all (SURVEY.md §5 "long-context:
ABSENT" — its nearest concept is Spark partitioning of the event RDD along
time). The sequence-recommendation template (pio_tpu/templates/sequence.py)
consumes **entire user event histories**, so attention over sequences longer
than one chip's HBM must shard the sequence dimension.

Design (blockwise / ring formulation):

- The sequence is sharded over mesh axis ``seq``: each device holds
  ``[B, T/n, heads, d]`` blocks of Q, K, V.
- K/V blocks rotate around the ring with ``ppermute`` while each device's Q
  stays put; a ``lax.scan`` of ``n`` steps overlaps the neighbour exchange
  with the local block matmuls (both ride the MXU).
- Softmax is computed **online** (running row-max ``m``, normalizer ``l``,
  accumulator ``o``) so the full ``[T, T]`` score matrix never exists —
  exact attention, O(T/n) memory per device.
- Causality uses *global* positions: device ``i`` owns q-positions
  ``i·T/n + [0, T/n)``; after ``s`` rotations it is looking at the K/V block
  that started on device ``(i - s) mod n``. Blocks entirely in the future
  still flow through the ring, but their tiles are skipped: each ring step
  is one :func:`attention_partial`, blocked over local key blocks, whose
  loop runs only over the key blocks at or below the diagonal, and, under
  a ``window``, not before the window's first either.
- Grouped queries: ``k``/``v`` may have fewer heads than ``q``; the query
  heads of a KV head are folded into the query rows of one tile
  (:func:`fold_groups`), so nothing is repeated.
- A learned selection of keys (:func:`attention_partial`'s ``select``): each
  query sees the keys a bit mask names (:func:`pack_selection`), and a query
  block's loop runs over the key blocks in which some query of it selected a
  key, in order; the others are skipped, not masked.

Inside ``jit`` with a sharded mesh this function must be wrapped in
``shard_map`` over the ``seq`` axis (see :func:`ring_attention_sharded`);
on a single device (``axis=None``) it is the same blocked attention over
the whole row (memory linear in T, forward and backward).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


_NEG_BIG = -1e30
#: key/query block edge of the blocked update; the score tile is
#: ``[B, H, block, block]`` float32 whatever the row's length
DEFAULT_BLOCK = 512
#: query rows one word of a selection mask holds (:func:`pack_selection`)
SELECT_BITS = 32


def pick_block(t: int, block: int) -> int:
    """The largest divisor of ``t`` that is at most ``block``."""
    b = max(1, min(int(block), int(t)))
    while t % b:
        b -= 1
    return b


def attention_impl(platform: str, dtype, d_k: int, d_v: int, bq: int, bk: int,
                   causal: bool, key_rows: int = 0, selected: bool = False
                   ) -> str:
    """What runs :func:`attention_partial`'s tiles, from what is visible at
    trace time: ``pallas`` / ``xla``. ``pallas`` is the pair of kernels of
    :mod:`pio_tpu.parallel.ring_kernel` (a tile's scores, softmax and
    accumulators in VMEM): on a TPU, for causal attention with bfloat16
    operands, head widths and blocks that are multiples of the 128 lanes,
    and ``key_rows`` keys whose ``k`` and ``v`` of one head fit VMEM
    (``ring_kernel.fits``); under a ``selected`` mask also a query block
    whose words of the mask are whole sublane tiles (:func:`select_words`
    a power of two, at least 8). ``xla`` is the ``fori_loop`` of this
    module: everywhere else (every CPU run, float32 operands, a head width
    of 64, non-causal calls), and the kernels' oracle."""
    from pio_tpu.parallel.ring_kernel import fits

    dtype = jnp.dtype(dtype)
    tiles = (causal and dtype == jnp.bfloat16
             and all(x % 128 == 0 for x in (d_k, d_v, bq, bk))
             and fits(key_rows, d_k, d_v, dtype.itemsize))
    if selected:
        words = select_words(bq)
        tiles = tiles and words >= 8 and words & (words - 1) == 0
    return "pallas" if platform == "tpu" and tiles else "xla"


def select_words(bq: int) -> int:
    """Rows of int32 words that hold the selection of a block of ``bq``
    queries (:func:`pack_selection`)."""
    return -(-bq // SELECT_BITS)


def pack_selection(sel):
    """A query block's selection ``sel [B, bq, Tk]`` (bool: query ``r`` of
    the block sees key ``s`` where set) -> ``bits [B, W, Tk]`` int32. Row
    ``r`` is bit ``r // W`` of word row ``r mod W`` (``W =
    select_words(bq)``), so that a tile's rows unpack as whole copies of its
    ``W`` word rows (:func:`unpack_selection`)."""
    b, bq, tk = sel.shape
    w = select_words(bq)
    sel = jnp.pad(sel, ((0, 0), (0, SELECT_BITS * w - bq), (0, 0)))
    planes = sel.reshape(b, SELECT_BITS, w, tk).astype(jnp.int32)
    shifts = jnp.arange(SELECT_BITS, dtype=jnp.int32)[None, :, None, None]
    return (planes << shifts).sum(axis=1)  # disjoint bits: a sum is an or


def unpack_selection(bits, bq: int, first=0, rows: int = 0):
    """The inverse of :func:`pack_selection`: one query block's ``bits [B,
    W, n]`` -> its rows ``first .. first + rows`` (all ``bq`` by default),
    ``[B, rows, n]`` bool."""
    w = bits.shape[1]
    r = first + jnp.arange(rows or bq, dtype=jnp.int32)
    words = jnp.take(bits, r % w, axis=1)  # row r: bit r // W of word r mod W
    return ((words >> (r // w)[None, :, None]) & 1) == 1


def selected_blocks(active):
    """``active [nq, nk]`` (whether some query of block ``i`` selected a key
    of block ``j``) -> ``(order [nq, nk], count [nq])`` int32: block ``i``'s
    loop runs over ``order[i, :count[i]]``, its active key blocks in order."""
    order = jnp.argsort(~active, axis=1, stable=True).astype(jnp.int32)
    return order, active.sum(axis=1).astype(jnp.int32)


def needed_key_blocks(i, q_off, k_off, bq: int, bk: int, nk: int, causal: bool):
    """How many leading key blocks query block ``i`` can see: a key block
    whose first position lies past the query block's last is above the
    diagonal and is never computed."""
    if not causal:
        return nk
    last_q = q_off + (i + 1) * bq - 1
    return jnp.clip((last_q - k_off) // bk + 1, 0, nk)


def first_key_block(i, q_off, k_off, bq: int, bk: int, nk: int, window: int):
    """The first key block query block ``i`` can see under a ``window``
    (query ``t`` sees keys ``s`` with ``0 <= t - s < window``; 0 = no
    window): the block that holds the earliest key of the block's first
    query. The blocks before it lie outside the window and are never
    computed."""
    if not window:
        return 0
    earliest = q_off + i * bq - window + 1
    return jnp.clip((earliest - k_off) // bk, 0, nk)


def _scores(qi, kj, q_pos, k_pos, causal, scale, window=0):
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", qi, kj, preferred_element_type=jnp.float32
    ) * scale
    if not causal:
        return s, None
    mask = q_pos[:, None] >= k_pos[None, :]
    if window:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    mask = mask[None, None]
    return jnp.where(mask, s, _NEG_BIG), mask


def _row_positions(bq: int, group: int):
    """Positions, within a query block, of a tile's rows: ``group`` query
    heads of one KV head lie one after the other, ``bq`` positions each."""
    pos = jnp.arange(bq)
    return pos if group == 1 else jnp.tile(pos, group)


def _tile_selection(bits, i, j, bq: int, bk: int, group: int):
    """The selection of tile ``(i, j)`` as :func:`_scores` masks: ``[B, 1,
    group * bq, bk]`` bool, the ``group`` heads' rows alike."""
    w = select_words(bq)
    words = jax.lax.dynamic_slice_in_dim(
        jax.lax.dynamic_slice_in_dim(bits, i * w, w, axis=1), j * bk, bk,
        axis=2)
    seen = unpack_selection(words, bq)
    return jnp.tile(seen, (1, group, 1))[:, None]


def _partial_fwd(q, k, v, q_off, k_off, causal, scale, bq, bk, window=0,
                 group=1, select=None):
    """Blocked online softmax of ``[B, H, T, D]`` operands: ``(o, lse,
    tiles)``, ``o`` float32 and normalised over the keys given here."""
    b, h, rows_q, _ = q.shape
    tk, dv = k.shape[2], v.shape[3]
    rows = bq * group  # query rows of one tile
    nq, nk = rows_q // rows, tk // bk

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=2)
        q_pos = q_off + i * bq + _row_positions(bq, group)

        def body(j, carry):
            o, m, l = carry
            if select is not None:
                j = select[1][i, j]  # the j-th active key block
            kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=2)
            s, mask = _scores(
                qi, kj, q_pos, k_off + j * bk + jnp.arange(bk),
                causal and select is None, scale, window,
            )
            if select is not None:
                mask = _tile_selection(select[0], i, j, bq, bk, group)
                s = jnp.where(mask, s, _NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            if causal:
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            return o, m_new, l

        init = (
            jnp.zeros((b, h, rows, dv), jnp.float32),
            jnp.full((b, h, rows), _NEG_BIG, jnp.float32),
            jnp.zeros((b, h, rows), jnp.float32),
        )
        n = needed_key_blocks(i, q_off, k_off, bq, bk, nk, causal)
        first = first_key_block(i, q_off, k_off, bq, bk, nk, window)
        if select is not None:
            # the selected blocks, and the causal loop's for the counter
            first, n, causal_n = 0, select[2][i], n
        o, m, l = jax.lax.fori_loop(first, n, body, init)
        safe = jnp.maximum(l, 1e-30)
        # the loop's own bounds: the tiles it ran, and those from block 0
        if select is not None:
            ran = jnp.stack([n, causal_n]).astype(jnp.int32)
        else:
            ran = jnp.stack([jnp.maximum(n - first, 0), n]).astype(jnp.int32)
        return (o / safe[..., None],
                jnp.where(l > 0, m + jnp.log(safe), _NEG_BIG), ran)

    o, lse, ran = jax.lax.map(q_block, jnp.arange(nq))  # [nq, B, H, rows, ..]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, rows_q, dv)
    lse = jnp.moveaxis(lse, 0, 2).reshape(b, h, rows_q)
    return o, lse, ran.sum(axis=0)


def _loop_bounds(q, k, q_off, k_off, causal, bq: int, bk: int, window: int,
                 group: int):
    """``(first [nq], n [nq], offs [2])`` int32: every query block's key
    blocks ``first <= j < n`` and the two offsets, as the kernels' loops
    take them (scalar prefetch)."""
    nq, nk = q.shape[2] // (bq * group), k.shape[2] // bk
    i = jnp.arange(nq)
    flat = lambda a: jnp.broadcast_to(jnp.asarray(a, jnp.int32), (nq,))
    return (flat(first_key_block(i, q_off, k_off, bq, bk, nk, window)),
            flat(needed_key_blocks(i, q_off, k_off, bq, bk, nk, causal)),
            jnp.stack([jnp.asarray(q_off, jnp.int32),
                       jnp.asarray(k_off, jnp.int32)]))


def _kernel_fwd(q, k, v, q_off, k_off, causal, scale, bq, bk, window, group,
                select, interpret):
    """:func:`_partial_fwd` on the kernels: the same ``(o, lse, tiles)``,
    ``tiles`` from the very bounds handed to the kernel's loop."""
    from pio_tpu.parallel import ring_kernel

    first, n, offs = _loop_bounds(q, k, q_off, k_off, causal, bq, bk, window,
                                  group)
    if select is not None:
        bits, order, count = select
        o, lse = ring_kernel.forward(q, k, v, order.reshape(-1), count, offs,
                                     scale, bq, bk, window, group, interpret,
                                     bits)
        ran = jnp.stack([count.sum(), n.sum()])
        return o, lse, ran.astype(jnp.int32)
    o, lse = ring_kernel.forward(q, k, v, first, n, offs, scale, bq, bk,
                                 window, group, interpret)
    ran = jnp.stack([jnp.maximum(n - first, 0).sum(), n.sum()])
    return o, lse, ran.astype(jnp.int32)


def _forward(impl: str, *args, select=None):
    if impl == "xla":
        return _partial_fwd(*args, select=select)
    return _kernel_fwd(*args, select, impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _attention(q, k, v, q_off, k_off, causal, scale, bq, bk, window, group,
               impl, select=None):
    return _forward(impl, q, k, v, q_off, k_off, causal, scale, bq, bk,
                    window, group, select=select)


def attention_partial(q, k, v, q_off, k_off, causal, scale, bq, bk, window=0,
                      group=1, select=None):
    """Exact attention of ``q`` over the keys given, in ``bq x bk`` tiles.

    ``q`` ``[B, H, Tq, Dk]``, ``k`` ``[B, H, Tk, Dk]``, ``v`` ``[B, H, Tk,
    Dv]``; ``q_off``/``k_off`` are the global positions of the first query
    and key (int32 scalars, traced on a ring). Returns ``o`` ``[B, H, Tq,
    Dv]`` float32, normalised over these keys, ``lse`` ``[B, H, Tq]``,
    so partial results over disjoint key sets merge exactly
    (:func:`merge_partials`), and ``tiles`` ``[2]`` int32 from the bounds
    the forward's tile loops ran between: the score tiles computed (a head
    and row), and those a loop from key block 0 computes. Matmul operands
    stay in the dtype given; scores, softmax and accumulators are float32.
    Memory is linear in the row's length forward and backward: the backward
    recomputes each score
    tile from ``lse`` and keeps none. Key blocks above the diagonal are
    skipped, not masked, and with a ``window`` (query ``t`` sees keys ``s``
    with ``0 <= t - s < window``; causal only) so are the key blocks that
    lie wholly before it, forward and backward.

    ``group`` query heads may share one head of ``k`` and ``v``: ``q`` then
    comes tile-major (:func:`fold_groups`), ``[B, H, nq * group * bq, Dk]``,
    a tile's rows being the ``group`` heads' ``bq`` positions one after the
    other, so that one tile's matmuls serve the whole group and ``k``/``v``
    are read once a KV head; ``o`` and ``lse`` come back in the same order.

    ``select`` ``(bits, order, count)`` restricts query ``t`` to the keys
    its own row of ``bits [B, nq * W, Tk]`` names (one query block's ``W``
    word rows after another, :func:`pack_selection`; every head alike; a
    selection lies within the causal keys): query block ``i``'s loop runs
    over the key blocks ``order[i, :count[i]]`` (:func:`selected_blocks`)
    and skips the others, forward and backward; ``tiles`` then counts those
    and the causal loop's. The window and the causal bounds are not read.

    What runs the tiles is :func:`attention_impl`'s choice: XLA's loops
    here, or on a TPU the two kernels of :mod:`pio_tpu.parallel.ring_kernel`.
    The arithmetic is the same either way (operands in the dtype given to
    every matmul, float32 scores, softmax and accumulators, ``p`` and ``ds``
    cast to that dtype before their matmuls); the kernels add the float32
    terms of a row in another order, and a key block a query tile sees whole
    runs there without its mask.
    """
    impl = attention_impl(jax.default_backend(), q.dtype, q.shape[-1],
                          v.shape[-1], bq, bk, causal, k.shape[2],
                          select is not None)
    return _attention(q, k, v, q_off, k_off, causal, scale, bq, bk, window,
                      group, impl, select)


def _attention_fwd(q, k, v, q_off, k_off, causal, scale, bq, bk, window,
                   group, impl, select=None):
    o, lse, tiles = _forward(impl, q, k, v, q_off, k_off, causal, scale, bq,
                             bk, window, group, select=select)
    return (o, lse, tiles), (q, k, v, q_off, k_off, o, lse, select)


def _attention_bwd(causal, scale, bq, bk, window, group, impl, res, cts):
    if impl == "xla":
        return _attention_partial_bwd(causal, scale, bq, bk, window, group,
                                      res, cts)
    from pio_tpu.parallel import ring_kernel

    q, k, v, q_off, k_off, o, lse, select = res
    do, dlse, _ = cts
    # d s_ij = p_ij (dp_ij - delta_i + dlse_i): one pass outside the kernel
    g = dlse - (do * o).sum(axis=-1)
    first, n, offs = _loop_bounds(q, k, q_off, k_off, causal, bq, bk, window,
                                  group)
    if select is not None:
        bits, order, count = select
        dq, dk, dv = ring_kernel.backward(
            q, k, v, do.astype(q.dtype), lse, g, order.reshape(-1), count,
            offs, scale, bq, bk, window, group, impl == "pallas_interpret",
            bits)
        return dq, dk, dv, None, None, None
    dq, dk, dv = ring_kernel.backward(
        q, k, v, do.astype(q.dtype), lse, g, first, n, offs, scale, bq, bk,
        window, group, impl == "pallas_interpret")
    return dq, dk, dv, None, None, None


def _attention_partial_bwd(causal, scale, bq, bk, window, group, res, cts):
    q, k, v, q_off, k_off, o, lse, select = res
    do, dlse, _ = cts  # the tile count is an integer: it has no cotangent
    b, h, rows_q, dk_ = q.shape
    tk, dv_ = k.shape[2], v.shape[3]
    rows = bq * group
    nq, nk = rows_q // rows, tk // bk
    # d s_ij = p_ij (dp_ij - delta_i + dlse_i), delta_i = do_i . o_i
    g = dlse - (do * o).sum(axis=-1)
    do = do.astype(q.dtype)

    def q_block(carry, i):
        dk, dv = carry
        qi = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=2)
        doi = jax.lax.dynamic_slice_in_dim(do, i * rows, rows, axis=2)
        lsei = jax.lax.dynamic_slice_in_dim(lse, i * rows, rows, axis=2)
        gi = jax.lax.dynamic_slice_in_dim(g, i * rows, rows, axis=2)
        q_pos = q_off + i * bq + _row_positions(bq, group)

        def body(j, carry):
            dqi, dk, dv = carry
            if select is not None:
                j = select[1][i, j]
            kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=2)
            s, mask = _scores(
                qi, kj, q_pos, k_off + j * bk + jnp.arange(bk),
                causal and select is None, scale, window,
            )
            if select is not None:
                mask = _tile_selection(select[0], i, j, bq, bk, group)
                s = jnp.where(mask, s, _NEG_BIG)
            p = jnp.exp(s - lsei[..., None])
            if causal:
                p = jnp.where(mask, p, 0.0)
            dvj = jnp.einsum(
                "bhqk,bhqd->bhkd", p.astype(do.dtype), doi,
                preferred_element_type=jnp.float32,
            )
            dp = jnp.einsum(
                "bhqd,bhkd->bhqk", doi, vj, preferred_element_type=jnp.float32
            )
            ds = (p * (dp + gi[..., None]) * scale).astype(q.dtype)
            dqi = dqi + jnp.einsum(
                "bhqk,bhkd->bhqd", ds, kj, preferred_element_type=jnp.float32
            )
            dkj = jnp.einsum(
                "bhqk,bhqd->bhkd", ds, qi, preferred_element_type=jnp.float32
            )

            def add(acc, blk):
                old = jax.lax.dynamic_slice_in_dim(acc, j * bk, bk, axis=2)
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, old + blk, j * bk, axis=2
                )

            return dqi, add(dk, dkj), add(dv, dvj)

        n = needed_key_blocks(i, q_off, k_off, bq, bk, nk, causal)
        first = first_key_block(i, q_off, k_off, bq, bk, nk, window)
        if select is not None:
            first, n = 0, select[2][i]
        dqi, dk, dv = jax.lax.fori_loop(
            first, n, body, (jnp.zeros((b, h, rows, dk_), jnp.float32), dk, dv)
        )
        return (dk, dv), dqi

    (dk, dv), dq = jax.lax.scan(
        q_block,
        (jnp.zeros((b, h, tk, dk_), jnp.float32),
         jnp.zeros((b, h, tk, dv_), jnp.float32)),
        jnp.arange(nq),
    )
    dq = jnp.moveaxis(dq, 0, 2).reshape(b, h, rows_q, dk_)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Two normalised partial attentions over disjoint key sets -> one."""
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse)[..., None]
    w_b = jnp.exp(lse_b - lse)[..., None]
    return o_a * w_a + o_b * w_b, lse


def fold_groups(q, bq: int, group: int):
    """``[B, T, H_kv * group, D]`` -> tile-major ``[B, H_kv, T * group, D]``:
    query head ``j`` rides KV head ``j // group``, and the ``group`` heads'
    rows of one ``bq``-block of positions lie together (what
    :func:`attention_partial` takes with ``group`` > 1)."""
    b, t, h, d = q.shape
    q = q.reshape(b, t // bq, bq, h // group, group, d)
    return q.transpose(0, 3, 1, 4, 2, 5).reshape(b, h // group, t * group, d)


def unfold_groups(o, bq: int, group: int):
    """The inverse of :func:`fold_groups`, for ``o [B, H_kv, T * group, D]``
    (or ``lse`` without the last dim) -> ``[B, T, H, D]``."""
    b, hk, rows = o.shape[:3]
    t = rows // group
    o = o.reshape(b, hk, t // bq, group, bq, *o.shape[3:])
    o = jnp.moveaxis(o, (2, 4, 1, 3), (1, 2, 3, 4))
    return o.reshape(b, t, hk * group, *o.shape[5:])


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: Optional[str],
    causal: bool = True,
    block: int = DEFAULT_BLOCK,
    scale: Optional[float] = None,
    window: int = 0,
    with_tiles: bool = False,
):
    """Exact attention over a sequence sharded on mesh axis ``axis``.

    Call from inside ``shard_map``; each device passes its local
    ``[B, T_local, H, D]`` blocks (``v`` may have another width than
    ``q``/``k``; ``k`` and ``v`` may have fewer heads, each shared by a
    group of consecutive query heads, whose rows are folded into one tile:
    nothing is repeated). ``window`` > 0 (with ``causal``) lets query ``t``
    see keys ``s`` with ``0 <= t - s < window`` only; key blocks wholly
    outside it are skipped. With ``axis=None`` it is plain single-device
    attention. Every ring step, and the single step without a ring, is one
    :func:`attention_partial` in ``block``-sized tiles, so no ``[T, T]``
    score matrix exists on any path. Returns the local ``[B, T_local, H,
    Dv]`` output block in ``q``'s dtype; ``with_tiles`` adds
    :func:`attention_partial`'s tile counts, summed over the ring steps.
    """
    b, t_loc, h, d = q.shape
    scale = 1.0 / (d ** 0.5) if scale is None else float(scale)
    n = 1 if axis is None else jax.lax.axis_size(axis)
    idx = jnp.int32(0) if axis is None else jax.lax.axis_index(axis)
    blk = pick_block(t_loc, block)
    group = h // k.shape[2]
    if group == 1:
        qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    else:
        qh = fold_groups(q, blk, group)
        kh, vh = (a.transpose(0, 2, 1, 3) for a in (k, v))
    h_kv, rows = kh.shape[1], t_loc * group
    q_off = idx * t_loc

    def partial(k_blk, v_blk, s):
        src = (idx - s) % n  # which device this K/V block started on
        return attention_partial(
            qh, k_blk, v_blk, q_off, src * t_loc, causal, scale, blk, blk,
            window, group,
        )

    def step(carry, s):
        o, lse, tiles, k_blk, v_blk = carry
        o_s, lse_s, tiles_s = partial(k_blk, v_blk, s)
        o, lse = merge_partials(o, lse, o_s, lse_s)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (o, lse, tiles + tiles_s, k_blk, v_blk), None

    if n > 1:
        # n-1 rotating steps, then the last block's update with no final
        # ppermute (the rotated result would be discarded — wasted ICI).
        o = jnp.zeros((b, h_kv, rows, vh.shape[-1]), jnp.float32)
        lse = jnp.full((b, h_kv, rows), _NEG_BIG, jnp.float32)
        (o, lse, tiles, kh, vh), _ = jax.lax.scan(
            step, (o, lse, jnp.zeros((2,), jnp.int32), kh, vh),
            jnp.arange(n - 1)
        )
        o_s, lse_s, tiles_s = partial(kh, vh, n - 1)
        o, _ = merge_partials(o, lse, o_s, lse_s)
        tiles = tiles + tiles_s
    else:
        o, _, tiles = partial(kh, vh, 0)
    if group == 1:
        o = o.transpose(0, 2, 1, 3).astype(q.dtype)
    else:
        o = unfold_groups(o, blk, group).astype(q.dtype)
    return (o, tiles) if with_tiles else o


def ring_attention_sharded(mesh, q, k, v, *, causal: bool = True):
    """``shard_map``-wrapped ring attention: global [B, T, H, D] in/out.

    Batch rides the ``data`` axis, sequence the ``seq`` axis; heads and
    head-dim stay unsharded (shard heads over ``model`` upstream if needed).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P("data", "seq", None, None)
    fn = functools.partial(ring_attention, axis="seq", causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
