"""Pipeline parallelism over the ``pipe`` mesh axis (GPipe-style).

Absent in the reference (Spark knows only data partitioning — SURVEY.md
§2.6); first-class here so deep towers can span chips. The formulation is
the SPMD one: every device runs the same program over its *stage slice* of a
layer-stacked parameter pytree, microbatches enter at stage 0, activations
hop stage→stage with ``ppermute``, and results drain from the last stage.
The schedule is a single ``lax.scan`` of ``n_micro + n_stages - 1`` ticks —
steady-state keeps every stage busy; bubble fraction is the usual
``(n_stages-1)/(n_micro+n_stages-1)``. Reverse-mode AD differentiates
through ``ppermute``/``scan``, so the same helper serves training.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp



def pipeline_apply(params, x, stage_fn: Callable, *, axis: str = "pipe"):
    """Run ``x`` through ``n_stages`` chained applications of ``stage_fn``.

    Call from inside ``shard_map``. Args:
        params: this device's stage parameters (pytree; caller shards the
            layer-stacked tree over ``axis`` and squeezes the stage dim).
        x: ``[n_micro, micro_b, ...]`` microbatched input, replicated over
            ``axis`` (only stage 0 reads it).
        stage_fn: ``(params, [micro_b, ...]) -> [micro_b, ...]`` — one
            stage's compute; activation shape must be stage-invariant.

    Returns ``[n_micro, micro_b, ...]`` outputs of the final stage,
    identical on every device of the axis (psum-reconciled), so callers can
    use ``out_specs=P(...)`` with the pipe dim unsharded.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    n_micro = x.shape[0]
    ticks = n_micro + n - 1
    perm_fwd = [(i, i + 1) for i in range(n - 1)]

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped; extra ticks feed garbage
        # that never reaches the output window)
        feed = jax.lax.dynamic_index_in_dim(
            x, jnp.minimum(t, n_micro - 1), keepdims=False
        )
        inp = jnp.where(idx == 0, feed, state)
        out = stage_fn(params, inp)
        # last stage's tick-t output is microbatch t-(n-1)
        slot = t - (n - 1)
        contrib = jnp.where(idx == n - 1, out, jnp.zeros_like(out))
        outputs = jax.lax.cond(
            slot >= 0,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, contrib.astype(o.dtype), jnp.maximum(slot, 0), 0
            ),
            lambda o: o,
            outputs,
        )
        if n > 1:
            state = jax.lax.ppermute(out, axis, perm_fwd)
        else:
            state = out
        return (state, outputs), None

    state0 = jnp.zeros_like(x[0])
    out0 = jnp.zeros((n_micro,) + x.shape[1:], x.dtype)
    (_, outputs), _ = jax.lax.scan(
        tick, (state0, out0), jnp.arange(ticks)
    )
    # outputs are nonzero only on the last stage; make them uniform
    return jax.lax.psum(outputs, axis)


def stage_slice(params_stacked, *, axis: str = "pipe"):
    """Inside shard_map: squeeze the per-device stage dim of a stacked tree.

    The caller shards a ``[n_stages, ...]``-stacked parameter pytree with
    ``P(axis)`` so each device's block has leading dim 1; this drops it.
    """
    return jax.tree.map(lambda a: a[0], params_stacked)
