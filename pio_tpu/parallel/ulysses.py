"""Ulysses-style all-to-all sequence parallelism — the second SP mode.

Complement to ring attention (pio_tpu/parallel/ring.py). Where the ring
rotates K/V blocks with ``ppermute`` (n steps, O(T/n) memory, bandwidth
spread over the whole computation), the all-to-all formulation re-shards
ONCE per attention call: heads scatter across the ``seq`` axis while the
sequence gathers, every device computes exact attention over the FULL
sequence for its head subset, and a second all-to-all restores the
sequence sharding. Two collectives per call; the local compute
materializes the ``[B, H/n, T, T]`` score matrix, so per-device memory is
quadratic in the FULL sequence length (for 1/n of the heads).

Trade-off guide (why both exist):

- **ring**: the O(T²) score matrix would not fit — memory-bound long
  contexts; online softmax keeps O(T/n · T_blk) and overlaps the
  ppermute hops with block matmuls.
- **ulysses (all-to-all)**: T moderate enough that full-T scores fit for
  H/n heads; two ICI collectives beat n ppermute hops — latency-bound
  regimes. Requires ``n_heads % n == 0``.

The reference has no sequence models at all (SURVEY.md §5 "long-context:
ABSENT"); this subsystem is a deliberate capability extension, first-class
per the rebuild's goals.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


_NEG_BIG = -1e30


def _dense_causal_attention(q, k, v, causal: bool, scale: float):
    """Plain exact attention on full-sequence [B, T, h, D] blocks."""
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32,
    )
    return out


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: Optional[str],
    causal: bool = True,
) -> jax.Array:
    """Exact attention over a sequence sharded on mesh axis ``axis``.

    Call from inside ``shard_map``; each device passes its local
    ``[B, T_local, H, D]`` blocks, with ``H`` divisible by the axis size.
    all-to-all #1: [B, T/n, H, D] → [B, T, H/n, D] (scatter heads, gather
    sequence); local dense attention; all-to-all #2 restores the layout.
    With ``axis=None`` computes plain single-device attention.
    """
    b, t_loc, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    if axis is None:
        return _dense_causal_attention(
            q.astype(jnp.float32), k, v, causal, scale
        ).astype(q.dtype)

    n = jax.lax.axis_size(axis)
    if h % n != 0:
        raise ValueError(
            f"ulysses attention needs n_heads divisible by the '{axis}' "
            f"axis size ({h} heads over {n} devices)"
        )
    # scatter heads (axis 2), gather sequence (axis 1); inputs cross the
    # interconnect in their own (possibly bf16) dtype — upcasting happens
    # AFTER the collective so the wire carries half the bytes
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis, split_axis=2, concat_axis=1,
        tiled=True,
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    out = _dense_causal_attention(
        qg.astype(jnp.float32), kg, vg, causal, scale
    ).astype(q.dtype)
    # inverse: scatter sequence back, gather heads
    out = jax.lax.all_to_all(
        out, axis_name=axis, split_axis=1, concat_axis=2, tiled=True
    )
    return out


def ulysses_attention_sharded(mesh, q, k, v, *, causal: bool = True):
    """``shard_map``-wrapped all-to-all attention: global [B, T, H, D]
    in/out, batch on ``data``, sequence on ``seq`` (same contract as
    :func:`pio_tpu.parallel.ring.ring_attention_sharded`)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P("data", "seq", None, None)
    fn = functools.partial(ulysses_attention, axis="seq", causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
