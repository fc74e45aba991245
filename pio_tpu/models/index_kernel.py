"""The lightning indexer's scores of a block of queries
(:func:`pio_tpu.models.seq_layers.index_scores`) as two Pallas TPU kernels:
one forward, one backward.

What ``index_scores`` runs as one einsum and a weighted sum (a block's
``[rows, heads, keys]`` float32 products written to HBM and read again for
``relu(s) w`` and the sum over heads; every key of the row scored and the
keys after each query masked afterwards) runs here with the products in
VMEM:

- **forward** ``index_scores_fwd``: grid ``(batch, key tile)``. A tile's
  keys are taken :data:`KEY_CHUNK` at a time: one matmul of all heads'
  queries (head-major rows, ``[heads n, di]``) against the chunk's keys,
  then ``sum_j w_j relu(s_j)`` into a ``[n, chunk]`` float32 accumulator,
  scaled by ``di ** -0.5`` and masked after each query. Only the scores
  leave the kernel. A chunk wholly after the block's last query is written
  ``-inf`` and not computed; a tile wholly after it also reads no keys (its
  block index is held at the last tile that is computed).
- **backward** ``index_scores_bwd``: the same grid, the key tiles in order.
  A chunk's products are computed again, and ``ds_j = g w_j [s_j > 0]
  di ** -0.5`` (``g`` masked after each query) is staged in VMEM in the
  operands' dtype, the form in which the XLA form's matmuls see it on the
  MXU. ``dqi`` of all heads (one matmul, float32 VMEM scratch across the
  tiles) and ``dki`` of the chunk (one matmul over all heads' queries,
  ``qi`` given transposed so that the keys lie on the lanes) follow; ``dw``
  is summed as ``g relu(s_j)`` a head, by lane tiles in VMEM scratch and
  across lanes once at the last tile. A chunk after the block's last query
  gives ``dki`` zero.

The arithmetic is the XLA form's: operands in the dtype given, float32
products, relu, weights and sums; only the order of the float32 additions
over heads, keys and tiles differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pio_tpu.utils.numutil import round_up

_LANES = 128
#: keys of one grid step, and of one matmul within it. A step costs its
#: overhead and a chunk its share of the block's causal waste: a query block
#: of 128 rows computes half a chunk past its diagonal on average
KEY_TILE, KEY_CHUNK = 2048, 512
#: what a kernel may hold in VMEM (a v5e core has 128 MiB)
VMEM_LIMIT = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def tiles(t: int):
    """``(tile, chunk)``: the keys of a grid step and of a matmul, for a row
    of ``t`` keys."""
    tile = min(KEY_TILE, t)
    return tile, min(KEY_CHUNK, tile)


def vmem_bytes(heads: int, di: int, n: int, t: int, itemsize: int) -> int:
    """What one grid step of the backward (the larger) holds in VMEM: its
    blocks, two buffers each (``qi`` both ways, ``ki``, ``w``, ``g``, ``dqi``,
    ``dki``, ``dw``), the ``dqi`` and ``dw`` accumulators, the staged ``ds``
    and two ``[heads n, chunk]`` float32 temporaries."""
    tile, chunk = tiles(t)
    rows, lanes = heads * n, round_up(di, _LANES)
    blocks = (rows * lanes * itemsize + di * rows * itemsize
              + tile * lanes * itemsize + n * _LANES * 4 + n * tile * 4
              + rows * lanes * itemsize + di * tile * itemsize
              + n * _LANES * 4)
    scratch = rows * lanes * 4 + rows * _LANES * 4 + rows * chunk * itemsize
    return 2 * blocks + scratch + 2 * rows * chunk * 4


def admits(heads: int, di: int, n: int, t: int, itemsize: int) -> bool:
    """Whether the kernels take a block of ``n`` queries of ``heads`` heads
    of ``di`` against ``t`` keys: whole tiles and chunks of whole lane tiles,
    head blocks of whole bfloat16 sublane tiles, at most 128 heads (``dw``
    comes out as a lane each), and half of :data:`VMEM_LIMIT` at most."""
    tile, chunk = tiles(t)
    return (t % tile == 0 and tile % chunk == 0 and chunk % _LANES == 0
            and n % 16 == 0 and di % 16 == 0 and heads <= _LANES
            and vmem_bytes(heads, di, n, t, itemsize) <= VMEM_LIMIT // 2)


def _weights(w_ref, heads: int, n: int, chunk: int):
    """Each head's weights as ``[n, chunk]`` columns: row ``r`` holds ``w[r,
    j]`` throughout. Broadcast along lanes once, then repeated by whole lane
    tiles."""
    return [jnp.tile(jnp.broadcast_to(w_ref[:, j:j + 1], (n, _LANES)),
                     (1, chunk // _LANES)) for j in range(heads)]


def _seen(start, t0, n: int, chunk: int):
    """``[n, chunk]``: where key ``start + c`` is at or before query ``t0 +
    r``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1)
    return start + c <= t0 + r


def _fwd_kernel(t0_ref, qi_ref, ki_ref, w_ref, out_ref, *, heads, n, chunk,
                scale):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    tile = out_ref.shape[1]
    t0 = t0_ref[0]
    last = t0 + n - 1  # the block's last query
    k0 = pl.program_id(1) * tile
    wj = _weights(w_ref, heads, n, chunk)
    for c in range(tile // chunk):
        start, cols = k0 + c * chunk, slice(c * chunk, (c + 1) * chunk)

        @pl.when(start <= last)
        def _():
            s = jax.lax.dot_general(qi_ref[...], ki_ref[cols, :], _NT,
                                    preferred_element_type=f32)
            acc = jnp.maximum(s[:n], 0.0) * wj[0]
            for j in range(1, heads):
                acc += jnp.maximum(s[j * n:(j + 1) * n], 0.0) * wj[j]
            out_ref[:, cols] = jnp.where(_seen(start, t0, n, chunk),
                                         acc * scale, -jnp.inf)

        @pl.when(start > last)
        def _():
            out_ref[:, cols] = jnp.full((n, chunk), -jnp.inf, f32)


def _bwd_kernel(t0_ref, qi_ref, qit_ref, ki_ref, w_ref, g_ref, dqi_ref,
                dkit_ref, dw_ref, dqi_scr, dw_scr, ds_scr, *, heads, n, chunk,
                scale):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    kt = pl.program_id(1)
    tile = g_ref.shape[1]
    t0 = t0_ref[0]
    last = t0 + n - 1
    k0 = kt * tile

    @pl.when(kt == 0)
    def _():
        dqi_scr[...] = jnp.zeros(dqi_scr.shape, f32)
        dw_scr[...] = jnp.zeros(dw_scr.shape, f32)

    wj = _weights(w_ref, heads, n, chunk)
    for c in range(tile // chunk):
        start, cols = k0 + c * chunk, slice(c * chunk, (c + 1) * chunk)

        @pl.when(start <= last)
        def _():
            kc = ki_ref[cols, :]
            s = jax.lax.dot_general(qi_ref[...], kc, _NT,
                                    preferred_element_type=f32)
            gs = jnp.where(_seen(start, t0, n, chunk), g_ref[:, cols],
                           0.0) * scale
            for j in range(heads):
                sj, head = s[j * n:(j + 1) * n], slice(j * n, (j + 1) * n)
                ds_scr[head, :] = jnp.where(sj > 0, gs * wj[j], 0.0).astype(
                    ds_scr.dtype)
                part = gs * jnp.maximum(sj, 0.0)
                lanes = part[:, :_LANES]
                for i in range(1, chunk // _LANES):
                    lanes += part[:, i * _LANES:(i + 1) * _LANES]
                dw_scr[head, :] += lanes
            ds = ds_scr[...]
            dqi_scr[...] += jnp.dot(ds, kc, preferred_element_type=f32)
            dkit_ref[:, cols] = jnp.dot(
                qit_ref[...], ds, preferred_element_type=f32).astype(
                    dkit_ref.dtype)

        @pl.when(start > last)
        def _():
            dkit_ref[:, cols] = jnp.zeros((dkit_ref.shape[0], chunk),
                                          dkit_ref.dtype)

    @pl.when(kt == pl.num_programs(1) - 1)
    def _():
        dqi_ref[...] = dqi_scr[...].astype(dqi_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        dw = jnp.zeros((n, _LANES), f32)
        for j in range(heads):
            dw = jnp.where(lane == j, dw_scr[j * n:(j + 1) * n, :].sum(
                axis=1, keepdims=True), dw)
        dw_ref[...] = dw


def _specs(n: int, heads: int, di: int, tile: int):
    """Block specs by operand over the grid ``(batch, key tile)``; ``t0``
    arrives by scalar prefetch. Key tiles wholly after the block's last query
    are held at the last one computed, so that they fetch nothing."""
    from jax.experimental import pallas as pl

    rows = heads * n
    held = lambda k, t0: jnp.minimum(k, (t0[0] + n - 1) // tile)
    whole = lambda b, k, t0: (b, 0, 0)
    by_tile = lambda b, k, t0: (b, 0, k)
    return dict(
        qi=pl.BlockSpec((None, rows, di), whole),
        qit=pl.BlockSpec((None, di, rows), whole),
        ki=pl.BlockSpec((None, tile, di),
                        lambda b, k, t0: (b, held(k, t0), 0)),
        w=pl.BlockSpec((None, n, heads), whole),
        g=pl.BlockSpec((None, n, tile), lambda b, k, t0: (b, 0, held(k, t0))),
        scores=pl.BlockSpec((None, n, tile), by_tile),
        dkit=pl.BlockSpec((None, di, tile), by_tile),
        dw=pl.BlockSpec((None, n, _LANES), whole),
    )


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.lru_cache(maxsize=32)
def _fwd_call(b: int, n: int, heads: int, di: int, t: int, tile: int,
              chunk: int, interpret: bool):
    """The forward's ``pallas_call`` for one set of shapes, built once (a
    layer calls it for every block, for the selection and for the loss)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sp = _specs(n, heads, di, tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, n=n, chunk=chunk,
                          scale=float(di ** -0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, t // tile),
            in_specs=[sp["qi"], sp["ki"], sp["w"]],
            out_specs=sp["scores"]),
        out_shape=jax.ShapeDtypeStruct((b, n, t), jnp.float32),
        compiler_params=_params(("parallel", "parallel")),
        interpret=interpret, name="index_scores_fwd")


@functools.lru_cache(maxsize=32)
def _bwd_call(b: int, n: int, heads: int, di: int, t: int, tile: int,
              chunk: int, dtype: str, interpret: bool):
    """The backward's ``pallas_call`` for one set of shapes, built once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, dt, f32 = heads * n, jnp.dtype(dtype), jnp.float32
    sp = _specs(n, heads, di, tile)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, n=n, chunk=chunk,
                          scale=float(di ** -0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, t // tile),
            in_specs=[sp["qi"], sp["qit"], sp["ki"], sp["w"], sp["g"]],
            out_specs=[sp["qi"], sp["dkit"], sp["dw"]],
            scratch_shapes=[pltpu.VMEM((rows, di), f32),
                            pltpu.VMEM((rows, _LANES), f32),
                            pltpu.VMEM((rows, chunk), dt)]),
        out_shape=[jax.ShapeDtypeStruct((b, rows, di), dt),
                   jax.ShapeDtypeStruct((b, di, t), dt),
                   jax.ShapeDtypeStruct((b, n, _LANES), f32)],
        compiler_params=_params(("parallel", "arbitrary")),
        interpret=interpret, name="index_scores_bwd")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scores(qi, ki, w, t0, interpret):
    return _scores_fwd(qi, ki, w, t0, interpret)[0]


def _scores_fwd(qi, ki, w, t0, interpret):
    b, n, heads, di = qi.shape
    rows = jnp.swapaxes(qi, 1, 2).reshape(b, heads * n, di)  # head-major
    call = _fwd_call(b, n, heads, di, ki.shape[1], *tiles(ki.shape[1]),
                     interpret)
    return call(t0, rows, ki, w), (rows, ki, w, t0)


def _scores_bwd(interpret, res, g):
    rows, ki, w, t0 = res
    b, n, heads = w.shape
    di = ki.shape[-1]
    call = _bwd_call(b, n, heads, di, ki.shape[1], *tiles(ki.shape[1]),
                     ki.dtype.name, interpret)
    dqi, dkit, dw = call(t0, rows, jnp.swapaxes(rows, 1, 2), ki, w, g)
    dqi = jnp.swapaxes(dqi.reshape(b, heads, n, di), 1, 2)
    return dqi, jnp.swapaxes(dkit, 1, 2), dw[..., :heads], None


_scores.defvjp(_scores_fwd, _scores_bwd)


def scores(qi, ki, w, t0, interpret: bool = False):
    """``index_scores(qi, ki, w, t0, cd)`` on the kernels: ``qi [B, n, Hi,
    di]`` and ``ki [B, T, di]`` in the compute dtype, ``w [B, n, Hi]``
    float32, ``t0`` the block's first position (traced or not) -> ``[B, n,
    T]`` float32, ``-inf`` at the keys after each query. Differentiable in
    ``qi``, ``ki`` and ``w``."""
    t0 = jnp.reshape(jnp.asarray(t0, jnp.int32), (1,))
    return _scores(qi, ki, w.astype(jnp.float32), t0, interpret)
