"""Plain reference ALS: float32, ``highest`` matmul precision, exact solves.

Imports nothing of ``pio_tpu`` and takes nothing the program made. What it
shares with the program is the specification of ``train_als``:

- ``iterations`` rounds of (user half-step, item half-step), starting from
  ``Q0 = |N(0,1)| / sqrt(K)`` drawn with the second half of
  ``jax.random.split(PRNGKey(uint32(seed)))`` (the trainer's documented
  initialisation; the first half is unused there too);
- explicit: ``(sum_i q_i q_i^T + reg I) p_u = sum_i r_ui q_i``;
- implicit (Hu, Koren, Volinsky 2008): ``(Q^T Q + sum_i alpha r_ui q_i q_i^T
  + reg I) p_u = sum_i (1 + alpha r_ui) q_i``.

Layout: entities are bucketed by degree rounded up to a power of two, and
each bucket is a dense ``[rows, width]`` table of neighbour ids (-1 = empty),
built on the device by gathers from the entity-sorted edge list.
A half-step walks the buckets in blocks of rows: gather the neighbours'
factor rows, one batched outer-product sum per row, one Cholesky solve per
row. No scatter, no blocked partial sums, no iterative solver.

``quantize = (exponent_bits, mantissa_bits)`` rounds the factor table that a
half-step gathers from to that format first. That is the control for
``correct``: the configuration states bf16 gather operands, and the nearest
precision below is fp8 (e4m3: ``(4, 3)``).
"""

from __future__ import annotations

import functools

import numpy as np

SLOTS = 1 << 20  # gathered rows per block: [SLOTS, K] float32
MAX_ROWS = 8192  # entities per block: [MAX_ROWS, K, K] float32
MIN_WIDTH = 8


def bucket_plan(counts):
    """Which entities share a table: ``[(ids[n], width, rows_per_block)]``,
    ``ids`` padded to a whole number of blocks with ``len(counts)`` (an
    entity that does not exist, whose rows come out empty)."""
    n_entities = len(counts)
    width = np.maximum(
        MIN_WIDTH, 1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64)
    )
    plan = []
    for w in np.unique(width):
        ids = np.nonzero(width == w)[0]
        rows = int(max(8, min(MAX_ROWS, SLOTS // int(w))))
        ids_pad = np.full(-(-len(ids) // rows) * rows, n_entities, np.int32)
        ids_pad[: len(ids)] = ids
        plan.append((ids_pad, int(w), rows))
    return plan


@functools.lru_cache(maxsize=None)
def _build_side(widths: tuple, n_entities: int):
    """Jitted: sort one side's edges by entity and lay each bucket's
    neighbour and value tables out by gathers (slot -> edge), on the device:
    the host never holds a second copy of the 25M edges."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(ent, other, val, ids_list):
        order = jnp.argsort(ent, stable=True)
        oth_s, val_s = other[order], val[order]
        start = jnp.searchsorted(
            ent[order], jnp.arange(n_entities + 2, dtype=ent.dtype), side="left"
        )  # entity n_entities (padding) gets an empty span
        count = start[1:] - start[:-1]
        out = []
        for ids, w in zip(ids_list, widths):
            col = jnp.arange(w, dtype=start.dtype)[None, :]
            valid = col < count[ids][:, None]
            src = jnp.where(valid, start[ids][:, None] + col, 0)
            out.append((ids,
                        jnp.where(valid, oth_s[src], -1),
                        jnp.where(valid, val_s[src], jnp.float32(0))))
        return out

    return build


def _solve_rows(F, nbr, val, gram, reg, alpha, implicit):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    K = F.shape[1]
    mask = (nbr >= 0).astype(jnp.float32)
    G = F[jnp.maximum(nbr, 0)] * mask[:, :, None]  # [n, w, K]
    if implicit:
        wgt, rhs = alpha * val * mask, (1.0 + alpha * val) * mask
    else:
        wgt, rhs = mask, val * mask
    A = jnp.einsum("nwk,nwl->nkl", G * wgt[:, :, None], G, precision=hi)
    A = A + reg * jnp.eye(K, dtype=jnp.float32) + gram
    b = jnp.einsum("nwk,nw->nk", G, rhs, precision=hi)
    L = jnp.linalg.cholesky(A)
    y = jax.scipy.linalg.solve_triangular(L, b[:, :, None], lower=True)
    x = jax.scipy.linalg.solve_triangular(
        jnp.swapaxes(L, 1, 2), y, lower=False
    )
    return x[:, :, 0]


@functools.lru_cache(maxsize=None)
def _half_step(implicit: bool, quantize, rows_per_block: tuple,
               n_entities: int):
    """One jitted program per side: every bucket's blocks through
    ``lax.map``, results written back by entity id."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def half_step(F, buckets, reg, alpha):
        K = F.shape[1]
        if implicit:
            gram = jnp.einsum("ik,il->kl", F, F,
                              precision=jax.lax.Precision.HIGHEST)
        else:
            gram = jnp.zeros((K, K), jnp.float32)
        if quantize is not None:
            # reduce_precision, not astype there and back: XLA may drop a
            # convert pair as excess precision (it did, on the v5e, PR 25)
            F = jax.lax.reduce_precision(F, *quantize)
        out = jnp.zeros((n_entities + 1, K), jnp.float32)  # +1: padding rows
        for (ids, nbr, val), rows in zip(buckets, rows_per_block):
            w = nbr.shape[1]
            x = jax.lax.map(
                lambda blk: _solve_rows(F, blk[0], blk[1], gram, reg, alpha,
                                        implicit),
                (nbr.reshape(-1, rows, w), val.reshape(-1, rows, w)),
            )
            out = out.at[ids].set(x.reshape(-1, K))
        return out[:n_entities]

    return half_step


def _run_half(F, side, n_entities, reg, alpha, implicit, quantize):
    import jax.numpy as jnp

    buckets, rows = side
    step = _half_step(bool(implicit), quantize, rows, int(n_entities))
    return step(F, buckets, jnp.float32(reg), jnp.float32(alpha))


def train(user_idx, item_idx, rating, n_users: int, n_items: int, *,
          rank: int, iterations: int, reg: float, implicit: bool,
          alpha: float, seed: int, quantize=None):
    """Reference factors ``(P[n_users, K], Q[n_items, K])`` as numpy."""
    import jax
    import jax.numpy as jnp

    u_dev, i_dev, r_dev = (jnp.asarray(a) for a in (user_idx, item_idx, rating))

    def side(ent, ent_dev, oth_dev, n):
        plan = bucket_plan(np.bincount(ent, minlength=n))
        build = _build_side(tuple(w for _, w, _ in plan), n)
        tables = build(ent_dev, oth_dev, r_dev,
                       [jnp.asarray(ids) for ids, _, _ in plan])
        return tables, tuple(rows for *_, rows in plan)

    by_user = side(user_idx, u_dev, i_dev, n_users)
    by_item = side(item_idx, i_dev, u_dev, n_items)
    del u_dev, i_dev, r_dev
    _ku, ki = jax.random.split(jax.random.PRNGKey(np.uint32(seed)))
    Q = jnp.abs(jax.random.normal(ki, (n_items, rank), jnp.float32)) * (
        jnp.float32(rank) ** -0.5
    )
    P = None
    for _ in range(int(iterations)):
        P = _run_half(Q, by_user, n_users, reg, alpha, implicit, quantize)
        Q = _run_half(P, by_item, n_items, reg, alpha, implicit, quantize)
    return np.asarray(P), np.asarray(Q)
