"""The chunks of :func:`pio_tpu.models.seq_layers.ssd_scan`, and the skip
``D x`` beside them, as two Pallas TPU kernels: one forward, one backward.

What ``ssd_scan`` runs as batched einsums around a ``lax.scan`` (every
chunk's ``[Q, Q]`` decay weights written to HBM as float32 and read again,
the carried state updated a chunk a turn in a loop of tiny bodies) runs here
with a head block's state in VMEM:

- **forward**: grid ``(batch, head block, chunk)``, the chunk axis in order.
  A block's ``R`` states, ``[R P, N]`` float32, are VMEM scratch, zero at
  chunk 0. A grid step computes its group's ``C B^T`` and the block's ``C
  S^T`` once (one matmul for all ``R`` heads), then for each head the masked
  decay tile ``exp(cum_t - cum_s)`` and ``y = (L * C B^T)(dt x) + exp(cum)
  C S^T + D x``, and last ``S <- exp(cum_last) S + (to_end dt x)^T B`` for
  the block in one matmul. The state that entered each chunk is written out
  for the backward, and the largest magnitude of a block's states.
- **backward**: the same grid with the chunks in reverse; ``dS`` is VMEM
  scratch, the decay tile and ``C B^T`` are recomputed from ``cum``, ``B``
  and ``C``. Only the two products with a head's decay tile run a head at a
  time; every product with a state, and the ``C B^T`` cotangent summed over
  the block's heads, is one matmul for the block. Out come ``dx``, the
  direct part of ``d dt`` (``dt`` also enters through ``cum``, whose
  gradient ``dcum`` comes out per position: XLA's autodiff of the cumulative
  sum takes it back to ``dt`` and ``a``), ``dB``, ``dC`` summed over a
  block's heads, and ``dD``.

The arithmetic is ``ssd_scan``'s: matmul operands in the compute dtype
``cd``, float32 accumulation; the cumulative sums, every ``exp`` and the
carried state float32; ``to_end dt x`` cast to ``cd`` before it meets ``B``
and the entering state before it meets ``C``. The backward casts each
cotangent to ``cd`` before its matmuls, as the attention kernels do.

Heads are handled a 128-lane tile at a time (two heads of 64 channels): a
head's per-position scalars (``dt``, ``cum``, the decays) come head-major,
``[B, H, T]``, and are spread over its lanes by a broadcast along sublanes
and a transpose, which Mosaic does cheaply where a broadcast along lanes is
not. ``x``, ``B`` and ``C`` are read where they lie in the convolution's
output ``[B, T, H P + 2 G N]`` and ``y`` comes out ``[B, T, H P]``, so that
no operand is sliced or copied into another layout first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
#: most heads of one grid step: the head loop is unrolled, and a group of
#: 64 heads (Granite) then takes eight steps, each reading its group's B, C
HEADS = 8
#: what a kernel may hold in VMEM (a v5e core has 128 MiB)
VMEM_LIMIT = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def head_block(heads_per_group: int, p: int) -> int:
    """Heads of one grid step: the largest divisor of a group's heads, at
    most :data:`HEADS`, that fills whole 8-row tiles of the per-head scalars
    and whose ``x`` columns fill whole 128-lane tiles, each tile of whole
    heads; 0 where none does."""
    if _LANES % p:
        return 0
    return max((r for r in range(8, min(HEADS, heads_per_group) + 1, 8)
                if heads_per_group % r == 0 and r * p % _LANES == 0),
               default=0)


def vmem_bytes(q: int, p: int, n: int, r: int) -> int:
    """What one grid step of the backward (the larger) holds in VMEM: the
    blocks of ``x``, ``dy``, ``dx`` and of the entering states (two buffers
    each), the ``dS`` scratch, six ``[Q, R P]`` float32 staging rows, and
    ``[Q, Q]`` float32 temporaries."""
    rows = 3 * 2 * q * r * p * 4 + 2 * r * p * n * 4 + r * p * n * 4
    return rows + 6 * q * r * p * 4 + 8 * q * n * 4 + 8 * q * q * 4


def fits(q: int, p: int, n: int, r: int) -> bool:
    """Whether a grid step of blocks of ``r`` heads (0: none fits the
    tiles) takes at most half of :data:`VMEM_LIMIT`."""
    return r > 0 and vmem_bytes(q, p, n, r) <= VMEM_LIMIT // 2


def _columns(rows):
    """``[R, Q]`` -> ``[Q, R]``: a head's per-position scalars as a column.
    The rows are padded to a whole tile of lanes first."""
    r, q = rows.shape
    pad = jnp.zeros((_LANES - r % _LANES, q), rows.dtype)
    return jnp.concatenate([rows, pad], axis=0).T


def _rows(columns, r: int):
    """``[Q, 128]`` -> its first ``r`` columns as ``[r, Q]`` rows."""
    return columns.T[:r]


def _decayed(last, rows: int):
    """``exp(last)`` of a ``[1, 1]`` log as a ``[rows, 1]`` column, to scale
    a state's rows (Mosaic broadcasts along one direction at a time: the
    ``exp`` stands between the two)."""
    return jnp.exp(jnp.broadcast_to(last, (rows, 1)))


def _total(a):
    """The sum of a 2-D array as ``[1, 1]``."""
    return a.sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)


def _lower(q: int):
    """``[Q, Q]``: where ``t >= s``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _decay(seen, cum_t, cum_s):
    """``L[t, s] = exp(cum_t - cum_s)`` where ``t >= s``, else 0."""
    return jnp.exp(jnp.where(seen, cum_t - cum_s, -jnp.inf))


def _spread(rows, k: int, p: int):
    """``[R, Q]`` per-head rows -> ``[Q, 128]``, the columns of lane tile
    ``k`` of a ``[Q, R P]`` block: lane ``l`` holds head ``k m + l // p``'s
    value at each position (``m = 128 / p`` heads a tile). Broadcast along
    sublanes and transposed: Mosaic broadcasts rows cheaply, columns not."""
    m, q = _LANES // p, rows.shape[1]
    head = jax.lax.broadcasted_iota(jnp.int32, (_LANES, q), 0) // p
    t = jnp.broadcast_to(rows[k * m:k * m + 1], (_LANES, q))
    for j in range(1, m):
        t = jnp.where(head == j, rows[k * m + j:k * m + j + 1], t)
    return t.T


def _down(row):
    """``[1, Q]`` -> ``[Q, Q]`` whose row ``t`` is ``row[t]`` throughout."""
    q = row.shape[1]
    t = jnp.broadcast_to(row, (_LANES, q)).T
    return jnp.concatenate([t] * (q // _LANES), axis=1) if q > _LANES else t


def _by_head(t, sel):
    """``[Q, R P]`` float32 -> ``[Q, 128]``: lane ``r`` the sum of head
    ``r``'s lanes. ``sel`` is the 0/1 ``[R P, 128]`` bfloat16 map of lanes to
    heads; ``t`` meets it as three bfloat16 parts that add up to it exactly,
    so the products are exact and the sums float32's."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    out = jnp.zeros((t.shape[0], sel.shape[1]), f32)
    for _ in range(3):
        part = t.astype(bf16)
        out += jnp.dot(part, sel, preferred_element_type=f32)
        t = t - part.astype(f32)
    return out


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, s_ref,
                peak_ref, state, z, u, *, heads, p, cd):
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, f32)
        peak_ref[...] = jnp.zeros(peak_ref.shape, f32)

    bq, cq = b_ref[...].astype(cd), c_ref[...].astype(cd)
    cb = jax.lax.dot_general(cq, bq, _NT, preferred_element_type=f32)
    s = state[...]  # [R P, N]: what enters the chunk
    s_ref[...] = s
    peak_ref[...] = jnp.maximum(peak_ref[...], jnp.abs(s).reshape(
        -1, 8, s.shape[1]).max(axis=0))
    z[...] = jax.lax.dot_general(cq, s.astype(cd), _NT,
                                 preferred_element_type=f32)
    cum, dt = cum_ref[...], dt_ref[...]  # [R, Q]
    q = cum.shape[1]
    last = cum[:, q - 1:]
    e, to_end = jnp.exp(cum), jnp.exp(last - cum)
    seen = _lower(q)
    m = _LANES // p
    own = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1) // p
    for k in range(heads // m):
        tile = slice(k * _LANES, (k + 1) * _LANES)
        x = x_ref[:, tile]
        dtx = _spread(dt, k, p) * x
        dtxc = dtx.astype(cd)
        y = _spread(e, k, p) * z[:, tile]
        for j in range(m):  # a head's lanes of the tile
            r = k * m + j
            w = (_decay(seen, _down(cum[r:r + 1]), cum[r:r + 1]) * cb
                 ).astype(cd)
            y += jnp.dot(w, jnp.where(own == j, dtxc, 0),
                         preferred_element_type=f32)
            rows = slice(r * p, (r + 1) * p)
            state[rows, :] = _decayed(last[r:r + 1], p) * s[rows]
        y_ref[:, tile] = y + d_ref[:, tile] * x
        u[:, tile] = _spread(to_end, k, p) * dtx
    state[...] += jax.lax.dot_general(u[...].astype(cd), bq, _TN,
                                      preferred_element_type=f32)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, s_ref, dy_ref,
                sel_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dd_ref,
                dstate, z, dv, dz, u, state_lanes, x_lanes, *, heads, p, cd):
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)  # the last chunk
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, f32)

    bq, cq = b_ref[...].astype(cd), c_ref[...].astype(cd)
    cb = jax.lax.dot_general(cq, bq, _NT, preferred_element_type=f32)
    s, ds = s_ref[...], dstate[...]  # [R P, N]: entering, and its leaving's
    sc, dsc = s.astype(cd), ds.astype(cd)
    z[...] = jax.lax.dot_general(cq, sc, _NT, preferred_element_type=f32)
    dv[...] = jax.lax.dot_general(bq, dsc, _NT, preferred_element_type=f32)
    cum, dt = cum_ref[...], dt_ref[...]  # [R, Q]
    q = cum.shape[1]
    last = cum[:, q - 1:]
    e, to_end = jnp.exp(cum), jnp.exp(last - cum)
    seen = _lower(q)
    m = _LANES // p
    own = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1) // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    dcb = jnp.zeros((q, q), f32)
    span_cols = jnp.zeros((q, _LANES), f32)
    dcum_rows = jnp.zeros((heads, q), f32)
    carried = jnp.zeros((1, _LANES), f32)  # a head's sum of dS * S
    dto_last = jnp.zeros((1, _LANES), f32)
    for k in range(heads // m):
        tile = slice(k * _LANES, (k + 1) * _LANES)
        x, dy = x_ref[:, tile], dy_ref[:, tile]
        dd_ref[:, tile] += (dy * x).sum(axis=0, keepdims=True)  # y += D x
        dt_t, to_end_t = _spread(dt, k, p), _spread(to_end, k, p)
        dtx32 = dt_t * x
        dtxc, dyc = dtx32.astype(cd), dy.astype(cd)
        # y += exp(cum) C S^T; the state carried: g S + (to_end dt x)^T B
        e_t = _spread(e, k, p)
        dz[:, tile] = e_t * dy
        u[:, tile] = to_end_t * dtx32
        dv_t = dv[:, tile]
        # exp(cum) (C S^T) and to_end's (dt x) dS^T B, to be summed by head
        dto_end = to_end_t * dv_t * dtx32
        state_lanes[:, tile] = e_t * dy * z[:, tile] - dto_end
        dto_end = dto_end.sum(axis=0, keepdims=True)
        for j in range(m):
            dto_last = jnp.where(
                lane == k * m + j,
                jnp.where(own[:1] == j, dto_end, 0.0).sum(axis=1, keepdims=True),
                dto_last)
        ddtx = to_end_t * dv_t
        for j in range(m):  # a head's lanes of the tile
            r = k * m + j
            # y += (L * C B^T)(dt x)
            decay = _decay(seen, _down(cum[r:r + 1]), cum[r:r + 1])
            w32 = decay * cb
            dyj = jnp.where(own == j, dyc, 0)
            dw = jax.lax.dot_general(dyj, dtxc, _NT,
                                     preferred_element_type=f32)
            ddtx += jax.lax.dot_general(w32.astype(cd), dyj, _TN,
                                        preferred_element_type=f32)
            dcb += dw * decay
            dspan = dw * w32
            span_cols = jnp.where(lane == r, dspan.sum(axis=1, keepdims=True),
                                  span_cols)
            dcum_rows = jnp.where(sub == r, -dspan.sum(axis=0, keepdims=True),
                                  dcum_rows)
            rows = slice(r * p, (r + 1) * p)
            carried = jnp.where(lane == r, _total(ds[rows] * s[rows]), carried)
            dstate[rows, :] = _decayed(last[r:r + 1], p) * ds[rows]
        dx_ref[:, tile] = dt_t * ddtx + d_ref[:, tile] * dy
        x_lanes[:, tile] = ddtx * x
    sel = sel_ref[...]
    dlast = carried * jnp.exp(_columns(cum)[q - 1:]) + dto_last
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcum_cols = (_by_head(state_lanes[...], sel) + span_cols
                 + jnp.where(at_last, dlast, 0.0))
    dcum_ref[...] = _rows(dcum_cols, heads) + dcum_rows
    ddt_ref[...] = _rows(_by_head(x_lanes[...], sel), heads)
    dzc, dcbc = dz[...].astype(cd), dcb.astype(cd)
    dc_ref[...] = (jnp.dot(dzc, sc, preferred_element_type=f32)
                   + jnp.dot(dcbc, bq, preferred_element_type=f32))
    db_ref[...] = (jnp.dot(u[...].astype(cd), dsc, preferred_element_type=f32)
                   + jax.lax.dot_general(dcbc, cq, _TN,
                                         preferred_element_type=f32))
    dstate[...] += jax.lax.dot_general(dzc, cq, _TN,
                                       preferred_element_type=f32)


def _specs(shape, q: int, chunks: int, reverse: bool):
    """Block specs by operand over the grid ``(batch, head block, step)``;
    ``reverse`` runs the chunks from the last. ``x``, ``B`` and ``C`` are
    read where they lie in the convolution's output, ``[x | B | C]``."""
    from jax.experimental import pallas as pl

    h, p, g, n = shape
    r = head_block(h // g, p)
    per_group, b0, c0 = h // g // r, h * p // n, h * p // n + g
    chunk = (lambda i: chunks - 1 - i) if reverse else (lambda i: i)
    return dict(
        rows=pl.BlockSpec((None, q, r * p), lambda b, h, i: (b, chunk(i), h)),
        heads=pl.BlockSpec((None, r, q), lambda b, h, i: (b, h, chunk(i))),
        b=pl.BlockSpec((None, q, n),
                       lambda b, h, i: (b, chunk(i), b0 + h // per_group)),
        c=pl.BlockSpec((None, q, n),
                       lambda b, h, i: (b, chunk(i), c0 + h // per_group)),
        d=pl.BlockSpec((1, r * p), lambda b, h, i: (0, h)),
        dd=pl.BlockSpec((None, 1, r * p), lambda b, h, i: (b, 0, h)),
        block=pl.BlockSpec((None, q, n), lambda b, h, i: (b, chunk(i), h)),
        state=pl.BlockSpec((None, None, r * p, n),
                           lambda b, h, i: (b, chunk(i), h, 0)),
        peak=pl.BlockSpec((None, None, 8, n), lambda b, h, i: (b, h, 0, 0)),
        sel=pl.BlockSpec((r * p, _LANES), lambda b, h, i: (0, 0)),
    )


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _scratch(q: int, p: int, n: int, r: int, rows: int):
    """The block's ``[R P, N]`` state (or its cotangent) and ``rows``
    ``[Q, R P]`` float32 staging rows, all VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    return ([pltpu.VMEM((r * p, n), f32)]
            + [pltpu.VMEM((q, r * p), f32) for _ in range(rows)])


@functools.lru_cache(maxsize=32)
def _fwd_call(b: int, t: int, shape, q: int, cd: str, interpret: bool):
    """The forward's ``pallas_call`` for one set of shapes, built once (a
    step calls it from several layers, forward and recomputed)."""
    from jax.experimental import pallas as pl

    h, p, g, n = shape
    r = head_block(h // g, p)
    chunks, blocks, f32 = t // q, h // r, jnp.float32
    sp = _specs(shape, q, chunks, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r, p=p, cd=jnp.dtype(cd)),
        grid=(b, blocks, chunks),
        in_specs=[sp["rows"], sp["b"], sp["c"], sp["heads"], sp["heads"],
                  sp["d"]],
        out_specs=[sp["rows"], sp["state"], sp["peak"]],
        scratch_shapes=_scratch(q, p, n, r, 2),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), f32),
                   jax.ShapeDtypeStruct((b, chunks, h * p, n), f32),
                   jax.ShapeDtypeStruct((b, blocks, 8, n), f32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_chunks_fwd")


@functools.lru_cache(maxsize=32)
def _bwd_call(b: int, t: int, shape, q: int, cd: str, interpret: bool):
    """The backward's ``pallas_call`` for one set of shapes, built once."""
    from jax.experimental import pallas as pl

    h, p, g, n = shape
    r = head_block(h // g, p)
    chunks, blocks, f32 = t // q, h // r, jnp.float32
    sp = _specs(shape, q, chunks, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r, p=p, cd=jnp.dtype(cd)),
        grid=(b, blocks, chunks),
        in_specs=[sp["rows"], sp["b"], sp["c"], sp["heads"], sp["heads"],
                  sp["d"], sp["state"], sp["rows"], sp["sel"]],
        out_specs=[sp["rows"], sp["heads"], sp["heads"], sp["block"],
                   sp["block"], sp["dd"]],
        scratch_shapes=_scratch(q, p, n, r, 6),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), f32),
                   jax.ShapeDtypeStruct((b, h, t), f32),
                   jax.ShapeDtypeStruct((b, h, t), f32),
                   jax.ShapeDtypeStruct((b, t, blocks * n), f32),
                   jax.ShapeDtypeStruct((b, t, blocks * n), f32),
                   jax.ShapeDtypeStruct((b, 1, h * p), f32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_chunks_bwd")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _chunks(xbc, dt, cum, d, shape, q, cd, interpret):
    return _chunks_fwd(xbc, dt, cum, d, shape, q, cd, interpret)[0]


def _chunks_fwd(xbc, dt, cum, d, shape, q, cd, interpret):
    B, T, _ = xbc.shape
    call = _fwd_call(B, T, shape, q, cd, interpret)
    y, states, peak = call(xbc, xbc, xbc, dt, cum, d)
    return (y, peak), (xbc, dt, cum, d, states)


def _chunks_bwd(shape, q, cd, interpret, res, cts):
    xbc, dt, cum, d, states = res
    dy = cts[0]  # the peak is a counter: it has no cotangent
    B, T, _ = xbc.shape
    H, P, G, N = shape
    lanes = head_block(H // G, P) * P
    # which head each lane of a block belongs to, as a 0/1 [R P, 128]
    sel = (jnp.arange(lanes)[:, None] // P
           == jnp.arange(_LANES)[None, :]).astype(jnp.bfloat16)
    call = _bwd_call(B, T, shape, q, cd, interpret)
    dx, ddt, dcum, db, dc, dd = call(xbc, xbc, xbc, dt, cum, d, states, dy,
                                     sel)
    # a group's blocks add up to its dB and dC
    by_group = lambda v: v.reshape(B, T, G, -1, N).sum(axis=3).reshape(
        B, T, G * N)
    dxbc = jnp.concatenate([dx, by_group(db), by_group(dc)], axis=-1)
    return dxbc, ddt, dcum, dd.sum(axis=0)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def scan(xbc, dt, a, d, shape, chunk: int, cd, interpret: bool = False):
    """The Mamba-2 mixer's recurrence and skip, ``ssd_scan(x, dt, a, B, C,
    chunk, cd) + D x``, on the kernels. ``xbc [B, T, H P + 2 G N]`` float32
    is ``[x | B | C]`` as the convolution leaves it (``shape = (H, P, G,
    N)``), ``dt [B, T, H]``, ``a``, ``d [H]``; ``chunk`` divides ``T``.
    Returns ``(y [B, T, H P] float32, chunks, absmax, blocks)`` as
    :func:`pio_tpu.models.seq_layers.ssd_scan` counts them, ``blocks`` the
    head blocks of the kernels' grid."""
    B, T, _ = xbc.shape
    H, P = shape[:2]
    Q = chunk
    # log of what the steps up to and with t leave of a state, a chunk apart
    cum = jnp.cumsum((dt * a).reshape(B, T // Q, Q, H), axis=2).reshape(B, T, H)
    by_head = lambda v: jnp.swapaxes(v, 1, 2)
    y, peak = _chunks(xbc, by_head(dt), by_head(cum),
                      jnp.repeat(d, P)[None], tuple(shape), Q,
                      jnp.dtype(cd).name, interpret)
    return (y, jnp.float32(B * (T // Q)),
            jax.lax.stop_gradient(peak).max(), jnp.float32(peak.shape[1]))
