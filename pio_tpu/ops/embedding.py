"""Pallas TPU kernel: weighted embedding-bag lookup (sparse × dense matmul).

The sparse hot loop of the text-classification and two-tower templates is

    out[b] = Σ_l weights[b, l] · table[ids[b, l]]        # [B, D]

i.e. a TF-IDF document (or a feature-bag) times an embedding table. On the
reference's substrate this is a Spark-side sparse-vector dot
(MLlib ``HashingTF``/``IDF`` pipelines — UNVERIFIED paths; SURVEY.md §2.6).
The XLA lowering materializes the gathered ``[B, L, D]`` tensor in HBM and
contracts it on the MXU. The Pallas kernel instead streams table rows
HBM→VMEM with an N-deep ring of async DMAs and accumulates in float32 on
the VPU — the ``[B, L, D]`` intermediate never exists.

Measured on v5e-1 (V=50k, D=256, f32; the bench records these each round
in ``secondary.textclassification``):

- At B=4096, L=64 (intermediate 268 MB, fits HBM): jitted XLA wins —
  23.3M tokens/s at max err 9e-8 vs f64 (the jitted default contracts
  f32 inputs via 3-pass bf16, so there is NO accuracy gap to close);
  the kernel does 13.9M tokens/s at err 2.6e-7.
- At B=16384, L=1436 the intermediate alone would be **24 GB — over
  v5e HBM, XLA cannot run at all**; the kernel streams it at 11.3M
  tokens/s through a 4 KB VMEM ring.

So the kernel is the MEMORY-robust path and ``embedding_bag`` dispatches
by intermediate size: shapes whose ``[B, L, D]`` gather fits comfortably
take XLA, larger ones take the kernel
(``PIO_TPU_EMBED_PALLAS_OVER_MB`` overrides the cutoff; CPU always XLA).

Layout notes (Mosaic constraints):

- ids/weights ride in **SMEM input blocks** of one bag-tile each — whole-
  array scalar prefetch overflows the 1 MB SMEM at large B·L.
- The table is viewed ``[V, 1, D]`` so a one-row slice has trailing dims
  equal to the array's — single-row HBM DMAs are otherwise rejected
  (8-sublane alignment rule).
- The DMA ring is statically unrolled (slot = token index mod depth): a
  ``lax.switch`` over slots measured ~2× slower (scalar-unit bound).

Gradients: ``embedding_bag`` carries a custom VJP — d(table) is a
segment-sum scatter-add in plain XLA (scatters don't ride the MXU; there is
nothing for Pallas to win), d(weights) re-uses the gathered rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pio_tpu.utils import knobs
from pio_tpu.utils.numutil import round_up as _round_up




# --------------------------------------------------------------------- kernel
BAGS_PER_TILE = 8  # sublane granule: output blocks are [8, D]
DMA_DEPTH = 4  # in-flight row fetches (ring of VMEM row buffers)


def _make_bag_kernel(L: int, D: int, depth: int):
    import jax.lax as lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = BAGS_PER_TILE * L  # flat token stream per tile
    assert T % depth == 0

    def kernel(id_ref, w_ref, table_ref, out_ref, bufs, sems):
        """One grid step = 8 bags: stream their 8·L table rows, accumulate.

        id_ref/w_ref: per-tile [1, 1, T] SMEM blocks (row id, weight).
        table_ref: [V, 1, D] table in HBM; rows DMA'd one at a time.
        out_ref: [8, D] VMEM block for this bag tile.
        bufs: [depth, 1, D] VMEM DMA ring; sems: depth DMA semaphores.
        The ring spans bag boundaries — padding rows (weight 0) keep the
        stream dense, so DMA overlap never stalls between bags.
        """

        def start(slot, t):
            pltpu.make_async_copy(
                table_ref.at[pl.ds(id_ref[0, 0, t], 1)],
                bufs.at[pl.ds(slot, 1)],
                sems.at[slot],
            ).start()

        def wait(slot, t):
            pltpu.make_async_copy(
                table_ref.at[pl.ds(id_ref[0, 0, t], 1)],
                bufs.at[pl.ds(slot, 1)],
                sems.at[slot],
            ).wait()

        for s in range(depth):
            start(s, s)

        def body(chunk, acc):
            base = chunk * depth
            # static unroll: each position owns a fixed ring slot, so slot
            # choice costs no scalar branching
            for s in range(depth):
                t = base + s
                wait(s, t)
                row = bufs[s, 0, :]
                acc = acc + w_ref[0, 0, t] * row.astype(jnp.float32)

                # re-arm this slot for the token one ring-turn ahead; the
                # row read above has retired (in-order core), so the DMA
                # cannot clobber it
                @pl.when(t + depth < T)
                def _():
                    start(s, t + depth)

                bag_done = lax.rem(t + 1, L) == 0

                @pl.when(bag_done)
                def _():  # flush this bag's row of the output tile
                    out_ref[pl.ds(t // L, 1), :] = acc[None, :].astype(
                        out_ref.dtype
                    )

                acc = jnp.where(bag_done, jnp.zeros_like(acc), acc)
            return acc

        lax.fori_loop(0, T // depth, body, jnp.zeros((D,), jnp.float32))

    return kernel


def _embedding_bag_pallas(
    table: jax.Array,
    ids: jax.Array,
    weights: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L = ids.shape
    V, D = table.shape

    # pad bags to the 8-bag tile; extra bags read row 0 with weight 0.
    # Pad L so the DMA ring divides the token stream.
    L_pad = _round_up(L, DMA_DEPTH)
    if L_pad != L:
        ids = jnp.pad(ids, ((0, 0), (0, L_pad - L)))
        weights = jnp.pad(weights, ((0, 0), (0, L_pad - L)))
        L = L_pad
    B_pad = _round_up(B, BAGS_PER_TILE)
    if B_pad != B:
        ids = jnp.pad(ids, ((0, B_pad - B), (0, 0)))
        weights = jnp.pad(weights, ((0, B_pad - B), (0, 0)))

    n_tiles = B_pad // BAGS_PER_TILE
    T = BAGS_PER_TILE * L
    tiled_ids = ids.reshape(n_tiles, 1, T)
    tiled_w = weights.reshape(n_tiles, 1, T).astype(jnp.float32)

    smem_blk = pl.BlockSpec(
        (1, 1, T), lambda b: (b, 0, 0), memory_space=pltpu.SMEM
    )
    out = pl.pallas_call(
        _make_bag_kernel(L, D, DMA_DEPTH),
        out_shape=jax.ShapeDtypeStruct((B_pad, D), jnp.float32),
        grid=(n_tiles,),
        in_specs=[
            smem_blk,  # row ids
            smem_blk,  # weights
            pl.BlockSpec(memory_space=pl.ANY),  # table in HBM
        ],
        out_specs=pl.BlockSpec(
            (BAGS_PER_TILE, D), lambda b: (b, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((DMA_DEPTH, 1, D), table.dtype),
            pltpu.SemaphoreType.DMA((DMA_DEPTH,)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * B_pad * L * D,
            bytes_accessed=B_pad * L * D * table.dtype.itemsize
            + B_pad * D * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(tiled_ids, tiled_w, table.reshape(V, 1, D))
    return out[:B]


# ----------------------------------------------------------------- fallback
def _embedding_bag_xla(
    table: jax.Array, ids: jax.Array, weights: jax.Array
) -> jax.Array:
    """Gather + weighted sum; materializes [B, L, D] in HBM.

    Precision is PINNED to HIGHEST: the jitted default already contracts
    f32 inputs via 3-pass bf16 (f32-level accuracy, measured err 9e-8),
    but the eager default and ``jax_default_matmul_precision='bfloat16'``
    would silently drop to single-pass bf16 (~2 digits) — the public op
    must not lose accuracy based on how it's called."""
    rows = table[ids]  # [B, L, D]
    return jnp.einsum(
        "bld,bl->bd",
        rows.astype(jnp.float32),
        weights.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


#: dispatch cutoff: intermediates up to this many MB take the (faster)
#: XLA path; beyond it the kernel's O(1) scratch wins (a [B, L, D] gather
#: several GB deep crowds HBM; past HBM size XLA cannot run at all)
_PALLAS_OVER_MB_DEFAULT = 2048.0


def _pallas_cutoff_bytes() -> float:

    return knobs.knob_float("PIO_TPU_EMBED_PALLAS_OVER_MB") * 2 ** 20


def _use_pallas(table) -> bool:
    # Mosaic single-row DMA slices must be lane-aligned: D % 128. Smaller
    # tables are cheap XLA gathers anyway (they fit VMEM).
    if table.shape[1] % 128 != 0:
        return False
    if isinstance(table, jax.Array) and not isinstance(table, jax.core.Tracer):
        # a committed concrete array knows its platform — a CPU-resident
        # table under jax.default_device(cpu) must NOT take the Mosaic
        # path even when the process default backend is TPU
        return next(iter(table.devices())).platform == "tpu"
    return jax.default_backend() == "tpu"  # tracers, host arrays


# ------------------------------------------------------------------- public
@jax.custom_vjp
def embedding_bag(table, ids, weights):
    """``out[b] = Σ_l weights[b,l] · table[ids[b,l]]`` → float32 [B, D].

    ``ids`` int32 [B, L] (pad with any valid row + weight 0), ``weights``
    [B, L]. Differentiable in ``table`` and ``weights``. Dispatch: XLA
    while the gathered ``[B, L, D]`` intermediate fits comfortably (it
    measured faster at equal accuracy — see module docstring), the
    Pallas streaming kernel beyond that (O(1) scratch; shapes XLA OOMs
    on)."""
    B, L = ids.shape
    D = table.shape[1]
    intermediate = B * L * D * max(4, table.dtype.itemsize)
    if _use_pallas(table) and intermediate > _pallas_cutoff_bytes():
        return _embedding_bag_pallas(table, ids, weights)
    return _embedding_bag_xla(table, ids, weights)


def _fwd(table, ids, weights):
    return embedding_bag(table, ids, weights), (table, ids, weights)


def _bwd(res, g):
    table, ids, weights = res
    V, D = table.shape
    B, L = ids.shape
    # d table: scatter-add of g[b] * w[b,l] into row ids[b,l] — a segment
    # sum over the flattened edge list (XLA; scatters don't ride the MXU).
    contrib = (g[:, None, :] * weights[:, :, None].astype(g.dtype)).reshape(
        B * L, D
    )
    d_table = jax.ops.segment_sum(
        contrib, ids.reshape(-1), num_segments=V
    ).astype(table.dtype)
    # d weights: dot of g[b] with the gathered row.
    rows = table[ids].astype(g.dtype)  # [B, L, D]
    d_w = jnp.einsum("bld,bd->bl", rows, g).astype(weights.dtype)
    return d_table, None, d_w


embedding_bag.defvjp(_fwd, _bwd)


# --------------------------------------------------- host-side bag packing
def pack_bags(
    indices_per_bag, weights_per_bag, max_len: int | None = None
):
    """Ragged per-bag (ids, weights) lists → padded int32/float32 arrays.

    Pads with id 0 / weight 0 (contributes exactly zero). ``max_len`` is
    rounded up to a multiple of 8 so the token stream tiles evenly.
    """
    B = len(indices_per_bag)
    L = max_len or max((len(x) for x in indices_per_bag), default=1)
    L = max(1, _round_up(L, 8))
    ids = np.zeros((B, L), np.int32)
    w = np.zeros((B, L), np.float32)
    for b, (ix, wx) in enumerate(zip(indices_per_bag, weights_per_bag)):
        n = min(len(ix), L)
        ids[b, :n] = np.asarray(ix[:n], np.int32)
        w[b, :n] = np.asarray(wx[:n], np.float32)
    return ids, w
