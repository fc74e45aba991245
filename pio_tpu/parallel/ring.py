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
  still flow through the ring (uniform program on every device — XLA cannot
  skip them) but contribute zero weight.

Inside ``jit`` with a sharded mesh this function must be wrapped in
``shard_map`` over the ``seq`` axis (see :func:`ring_attention_sharded`);
on a single device (``axis=None``) it degrades to plain blockwise attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


_NEG_BIG = -1e30


def _block_attn_update(o, m, l, q, k, v, q_pos, k_pos, causal, scale):
    """One online-softmax accumulation of a (q-block, kv-block) pair.

    Shapes: q [B, Tq, H, D], k/v [B, Tk, H, D]; o/m/l accumulators.
    """
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: Optional[str],
    causal: bool = True,
) -> jax.Array:
    """Exact attention over a sequence sharded on mesh axis ``axis``.

    Call from inside ``shard_map``; each device passes its local
    ``[B, T_local, H, D]`` blocks. With ``axis=None`` computes plain
    single-device attention (same code path, ring of size 1).
    Returns the local ``[B, T_local, H, D]`` output block.
    """
    b, t_loc, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    n = 1 if axis is None else jax.lax.axis_size(axis)
    idx = 0 if axis is None else jax.lax.axis_index(axis)

    q32 = q.astype(jnp.float32)
    o = jnp.zeros((b, h, t_loc, d), jnp.float32)
    m = jnp.full((b, h, t_loc), _NEG_BIG, jnp.float32)
    l = jnp.zeros((b, h, t_loc), jnp.float32)
    q_pos = idx * t_loc + jnp.arange(t_loc)

    def update(o, m, l, k_blk, v_blk, s):
        src = (idx - s) % n  # which device this K/V block started on
        k_pos = src * t_loc + jnp.arange(t_loc)
        return _block_attn_update(
            o, m, l, q32, k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32), q_pos, k_pos, causal, scale,
        )

    def step(carry, s):
        o, m, l, k_blk, v_blk = carry
        o, m, l = update(o, m, l, k_blk, v_blk, s)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (o, m, l, k_blk, v_blk), None

    if n > 1:
        # n-1 rotating steps, then the last block's update with no final
        # ppermute (the rotated result would be discarded — wasted ICI).
        (o, m, l, k, v), _ = jax.lax.scan(
            step, (o, m, l, k, v), jnp.arange(n - 1)
        )
    o, m, l = update(o, m, l, k, v, n - 1)
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


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
