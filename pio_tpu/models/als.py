"""ALS matrix factorization — TPU-native replacement for Spark MLlib ALS.

The reference's Recommendation/Similar-Product templates call
``org.apache.spark.mllib.recommendation.ALS.train`` / ``trainImplicit``
(reference: examples/scala-parallel-recommendation ALSAlgorithm.scala,
UNVERIFIED path; see SURVEY.md). MLlib's ALS block-partitions the rating
matrix into in/out-link blocks and shuffles factor updates between executors
every half-iteration. This module is the TPU-first re-design:

- The host sorts the COO rating list by (user, item) and ships it as it
  is (``int32`` item ids, ``float32`` ratings, the two degree histograms);
  the device packs it ONCE per orientation (by-user and by-item) into
  **fixed-width dense blocks**: each entity's adjacency split into
  ``[block_width]`` slices, padded slots carrying weight 0. Static shapes,
  no ragged gathers.
- One half-iteration (e.g. the user update) is::

      A_u = Σ_{i ∈ R(u)} q_i q_iᵀ + λI        b_u = Σ_i r_ui q_i
      p_u = A_u⁻¹ b_u

  computed per block as one **batched MXU matmul**
  (``einsum('bwk,bwl->bkl')`` over ``[blocks, width, K]`` gathered factors)
  followed by a ``segment_sum`` of the ~E/width block partials onto entities
  with ``indices_are_sorted=True`` — the scatter is over blocks, not edges,
  so the VPU-hostile part shrinks by the block width while the FLOPs ride
  the systolic array. On a TPU, at rank 64 and width 64, both are one
  Pallas kernel (``_accum_fused``): the block products never leave VMEM
  and each entity's sum is written once.
- Cross-device combine is ``psum_scatter`` (reduce-scatter) over the entity
  dimension: each device sums partial normal equations from its block shard,
  receives 1/D of the entities, solves its slice with a batched
  ``jnp.linalg.solve``, and ``all_gather``s the factors back. Two ICI
  collectives per half-step replace MLlib's shuffle — the scaling-book
  recipe for data-parallel normal equations.
- Implicit feedback (Hu-Koren-style): confidence c = 1 + α·r, preference 1;
  the shared ``QᵀQ`` gram term is one MXU matmul, and only the
  ``(c-1) q qᵀ`` correction rides the blocked path.

The jitted trainer is cached per (mesh, static config) so repeated
``train_als`` calls — serving retrains, evaluation sweeps, benchmarks —
recompile only on shape changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
from pio_tpu.utils import knobs
from pio_tpu.obs import active_span, devicewatch, monotonic_s, trainwatch
from pio_tpu.obs.profile import ScopeCapture, device_stats
from typing import Optional, Tuple

import numpy as np

from pio_tpu.utils.numutil import (
    n_stream_chunks as _n_stream_chunks,
    round_up as _round_up,
)

from pio_tpu.parallel.context import ComputeContext

log = logging.getLogger("pio_tpu.als")

#: the two half-steps' scopes: the outermost segment of a device scope
#: path, dropped where a ``stats`` map sums the sides
_SIDE_SCOPES = ("als.user", "als.item")
#: what one edge costs on the host→device link: its ``int32`` item id and
#: its ``float32`` rating (the user column is rebuilt from the counts)
_EDGE_BYTES = 8


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 10
    iterations: int = 10
    reg: float = 0.1
    implicit: bool = False
    alpha: float = 40.0
    #: edges per dense block; None → power of two near half the mean degree
    #: (bounds padding waste at ~width/2 per entity)
    block_width: Optional[int] = None
    #: blocks per scan step — bounds the gathered ``[chunk, width, K]`` rows
    #: and, on XLA's path, the ``[chunk, K, K]`` block products behind them
    #: (the fused kernel keeps those in VMEM: _accum_impl)
    blocks_per_chunk: int = 4096
    #: dtype for the factor gather + normal-equation matmuls. "auto"
    #: picks bfloat16 on accelerator backends — the MXU's native rate,
    #: halving the gather bandwidth — and float32 on CPU, where bf16 is
    #: emulated (no rate or bandwidth win) and its table rounding only
    #: compounds across iterations. Explicit "bfloat16" / "float32"
    #: override; accumulation and the solves stay float32 either way.
    matmul_dtype: str = "auto"
    #: per-entity K×K solver: "auto" uses exact Cholesky for small entity
    #: counts and switches to Jacobi-preconditioned CG (no factorization;
    #: per-entity matvecs on the vector unit) above ~32k entities, where
    #: XLA's batched factorizations serialize badly on TPU. Explicit "cg" /
    #: "cholesky" / "lu" override. On a TPU, at a rank that is a multiple
    #: of 8 up to 128, CG runs as a VMEM-resident Pallas kernel
    #: (_solve_impl).
    solver: str = "auto"
    seed: int = 0


@dataclasses.dataclass
class ALSFactors:
    """Trained factors (host numpy; replicated on device during training)."""

    user_factors: np.ndarray  # [n_users, rank]
    item_factors: np.ndarray  # [n_items, rank]




def _native_packer():
    """The C++ edge sorter (pio_tpu/native/als_pack.cpp), or None when
    no toolchain is available (tests cover both paths)."""
    if knobs.knob_str("PIO_TPU_NO_NATIVE"):
        return None
    from pio_tpu.native import NativeUnavailable, als_pack_lib

    try:
        return als_pack_lib()
    except (NativeUnavailable, OSError):  # no toolchain / unloadable .so
        return None


def _ptr(a: np.ndarray, dtype, ctype):
    """C pointer to a's buffer. Asserts rather than converts: a silent
    ascontiguousarray copy would send native WRITES into a discarded
    temporary (these helpers are used for output buffers too)."""
    import ctypes

    assert a.dtype == dtype and a.flags.c_contiguous, (a.dtype, a.flags)
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def _i32p(a: np.ndarray):
    import ctypes

    return _ptr(a, np.int32, ctypes.c_int32)


def _i64p(a: np.ndarray):
    import ctypes

    return _ptr(a, np.int64, ctypes.c_int64)


def _f32p(a: np.ndarray):
    import ctypes

    return _ptr(a, np.float32, ctypes.c_float)


def _auto_width(n_edges: int, n_entities: int) -> int:
    # Narrow blocks: padding waste (≈ width/2 per entity) is gathered and
    # multiplied like real edges, traded against the extra segment-sum
    # rows (16-64 at MovieLens scales; not re-fitted on the chip).
    mean_deg = max(1.0, n_edges / max(1, n_entities))
    w = 1 << int(np.ceil(np.log2(max(8.0, mean_deg / 4))))
    return int(min(64, max(16, w)))


def _pack_blocks(
    ent_idx: np.ndarray,
    other_idx: np.ndarray,
    rating: np.ndarray,
    n_entities: int,
    width: int,
    pad_blocks_to: int,
    counts: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack a COO edge list into dense [n_blocks, width] CSR-style blocks.

    Returns (block_ent [S], block_other [S,W], block_rating [S,W]);
    ``block_ent`` ascending so downstream segment sums take the
    sorted-indices fast path. Padded slots carry ``other = -1`` — the
    validity mask is derived on device from the sign, so no separate mask
    array rides the host→device link.
    """
    order = np.argsort(ent_idx, kind="stable")
    e = ent_idx[order]
    if counts is None:
        counts = np.bincount(e, minlength=n_entities)
    blocks_per_ent = -(-counts // width)  # zero for empty entities
    n_blocks = int(blocks_per_ent.sum())
    S = max(pad_blocks_to, _round_up(max(n_blocks, 1), pad_blocks_to))

    block_start = np.zeros(n_entities + 1, dtype=np.int64)
    np.cumsum(blocks_per_ent, out=block_start[1:])
    edge_start = np.zeros(n_entities + 1, dtype=np.int64)
    np.cumsum(counts, out=edge_start[1:])

    # position of each (sorted) edge within its entity's adjacency
    pos = np.arange(len(e), dtype=np.int64) - edge_start[e]
    flat = (block_start[e] + pos // width) * width + pos % width

    block_other = np.full(S * width, -1, dtype=np.int32)
    block_rating = np.zeros(S * width, dtype=np.float32)
    block_other[flat] = other_idx[order]
    block_rating[flat] = rating[order]

    # padding blocks target the LAST entity (masked out) to keep ids
    # ascending for the segment-sum sorted fast path
    block_ent = np.full(S, n_entities - 1, dtype=np.int32)
    reps = np.repeat(np.arange(n_entities, dtype=np.int32), blocks_per_ent)
    block_ent[: len(reps)] = reps
    return (
        block_ent,
        block_other.reshape(S, width),
        block_rating.reshape(S, width),
    )


def _resolve_matmul_dtype(matmul_dtype: str) -> str:
    """``"auto"`` → bfloat16 where the MXU pays for it, float32 on CPU
    (emulated bf16: same FLOP rate, strictly more rounding)."""
    if matmul_dtype != "auto":
        return matmul_dtype
    import jax

    return "float32" if jax.default_backend() == "cpu" else "bfloat16"


#: entities per grid step of the resident CG kernel (its lane dimension)
_CG_TILE = 128
#: the largest rank whose five [K, K, tile] float32 blocks fit a v5e's
#: 128 MiB of VMEM (rank 256 is refused by the chip's compiler)
_CG_MAX_RANK = 128


def _solve_impl(solver: str, n_entities: int, rank: int,
                platform: str) -> str:
    """Which implementation ``solve_block`` runs for one side, from what
    is visible at trace time: ``cholesky`` / ``lu`` / ``xla_cg`` /
    ``resident_cg``. ``auto`` is exact Cholesky while it is cheap and CG
    at the batch sizes where XLA's TPU factorizations serialize; CG runs
    as the Pallas kernel (:func:`_cg_solve_resident`) on a TPU at a rank
    its blocks tile (a multiple of 8, at most ``_CG_MAX_RANK``), and as
    the XLA loop elsewhere."""
    if solver not in ("auto", "cg", "cholesky", "lu"):
        raise ValueError(
            f"unknown ALS solver {solver!r}; use auto/cg/cholesky/lu"
        )
    if solver == "auto":
        solver = "cg" if n_entities > 32768 else "cholesky"
    if solver != "cg":
        return solver
    tiles = rank % 8 == 0 and rank <= _CG_MAX_RANK
    return "resident_cg" if platform == "tpu" and tiles else "xla_cg"


#: lanes of a TPU vector row: every tiling pads an array's minor dimension
#: to a multiple of it, in VMEM as in a gather's result
_LANES = 128
#: the largest factor table, in bytes as tiled, that the chip's compiler
#: keeps in VMEM for the whole scan of a half-step beside the chunk's
#: gathered rows. Fitted on ``finalize`` of the streamed trainer compiled
#: for a v5e (128 MiB of VMEM) at MovieLens-25M's shapes, rank 64, bf16,
#: with the user count varied: the plain table is the gather's operand in
#: memory space 1 up to 110,000 rows (28.2 MB padded) and in HBM from
#: 120,000 (30.7 MB); packed, 162,541 rows (20.8 MB) are in memory space
#: 1 (tests/test_tpu_compile.py holds that end). A smaller program takes
#: more (``partial_normal_eq`` alone: 41.6 MB), so this is the trainer's.
_GATHER_VMEM_BYTES = 24 << 20


def _gather_impl(platform: str, n_rows: int, rank: int,
                 itemsize: int) -> str:
    """Which layout ``partial_normal_eq`` gathers its factor rows from,
    from what is visible at trace time: ``packed`` / ``plain``. On a TPU
    a table whose rows are narrower than the ``_LANES`` lanes is padded
    to them wherever it is tiled, so the compiler's memory-space
    assignment refuses VMEM to a table it would take unpadded, and every
    row gathered from HBM is then a short read of its own (9.7 ns a row
    on a v5e against 1.7-1.9 from VMEM). ``packed`` lays ``_LANES // rank``
    entity rows side by side in one lane row (:func:`_pack_table`); the
    select behind its gather costs 0.2 ms a chunk of 262,144 rows, so it
    is picked only where it buys the VMEM: on a TPU, at a rank that divides
    the lanes, for a table that is over ``_GATHER_VMEM_BYTES`` padded and
    within them packed. ``plain`` is the table as it is: everywhere else
    (CPU, rank 10, rank 128 and up, a table that fits as it is or not at
    all), and the tests' oracle. Both give the same rows to the bit."""
    if platform != "tpu" or rank >= _LANES or _LANES % rank:
        return "plain"
    padded = n_rows * _LANES * itemsize
    packed = -(-n_rows // (_LANES // rank)) * _LANES * itemsize
    fits_only_packed = packed <= _GATHER_VMEM_BYTES < padded
    return "packed" if fits_only_packed else "plain"


def _pack_table(table):
    """``[n, K]`` → ``[ceil(n / r), r * K]`` with ``r = _LANES // K``:
    entity ``i`` is lanes ``(i % r) * K`` onward of row ``i // r``."""
    import jax.numpy as jnp

    n, K = table.shape
    r = _LANES // K
    rows = -(-n // r)
    return jnp.pad(table, ((0, rows * r - n), (0, 0))).reshape(rows, r * K)


def _gather_rows(table, idx, K: int):
    """Rows ``idx`` of the ``[n, K]`` factor table that ``table`` is
    (plain) or packs (:func:`_pack_table`): gather the lane row, then
    select the entity's ``K``-wide group. Gathered and selected as one
    flat list of rows, ``[idx.size, ...]``: on that shape XLA fuses the
    select with the lane slices behind the gather and relayouts the
    result once for the matmuls, as it does the plain gather's; selected
    on ``idx``'s own shape, each lane slice was written to HBM and
    relayouted on its own (+0.5 ms a chunk of 262,144 rows on a v5e)."""
    import jax.numpy as jnp

    r = table.shape[1] // K
    if r == 1:
        return table[idx]
    flat = idx.reshape(-1)
    wide = table[flat // r]
    group = (flat % r)[:, None]
    q = wide[:, :K]
    for g in range(1, r):
        q = jnp.where(group == g, wide[:, g * K:(g + 1) * K], q)
    return q.reshape(*idx.shape, K)


def _cg_solve_resident(A, b, reg, interpret: bool = False):
    """``_cg_solve`` of ``A + reg`` (``reg`` the K×K regulariser every
    entity shares: λI, plus the gram when implicit) as one Pallas TPU
    kernel: a tile of ``_CG_TILE`` entities stays in VMEM for the whole
    solve, so ``A`` crosses HBM once per half-step, not once per sweep.

    Entities ride the lanes: ``A`` is transposed to ``[l, k, entity]``
    outside the kernel (in the trainers XLA writes that layout with the
    copy it already makes of the scan's result), every vector is
    ``[K, tile]``, and ``Ap[k] = Σ_l A[k, l]·p[l]`` is K multiply-adds of
    ``[K, tile]`` slabs by a sublane-broadcast row of ``p`` on the vector
    unit. Nothing crosses lanes, so what the last, partial tile reads
    beyond the batch stays in lanes that are never written back. The
    recurrence, the guards and the K+8 sweeps are ``_cg_solve``'s, in
    float32; only the summation order inside a matvec differs, and it
    is kept as shallow as a tree reduction's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, K = b.shape
    T = _CG_TILE
    A_t = jnp.transpose(A, (2, 1, 0))  # A_t[l, k, e] = A[e, k, l]
    reg_t = jnp.broadcast_to(reg.T[:, :, None], (K, K, T))

    def kernel(A_ref, reg_ref, b_ref, x_ref, As_ref):
        rows = jax.lax.broadcasted_iota(jnp.int32, (K, T), 0)
        d = jnp.zeros((K, T), jnp.float32)
        for l in range(K):
            slab = A_ref[l] + reg_ref[l]
            As_ref[l] = slab
            d = jnp.where(rows == l, slab, d)  # row l of slab l: A[l, l]

        def matvec(p):
            # runs of 8 columns summed in order, the runs pairwise: 64
            # terms added one after another lose a digit that CG on an
            # ill-conditioned system multiplies (relative error 1.3e-3
            # against 5.0e-4 for this order and for XLA's reduction)
            sums = []  # (tree level, partial sum), levels falling
            for l0 in range(0, K, 8):
                acc = As_ref[l0] * p[l0:l0 + 1, :]
                for l in range(l0 + 1, l0 + 8):
                    acc = acc + As_ref[l] * p[l:l + 1, :]
                level = 0
                while sums and sums[-1][0] == level:
                    acc = sums.pop()[1] + acc
                    level += 1
                sums.append((level, acc))
            acc = sums.pop()[1]
            while sums:  # K / 8 is no power of two
                acc = sums.pop()[1] + acc
            return acc

        def dot(u, v):
            return jnp.sum(u * v, axis=0, keepdims=True)

        inv_d = 1.0 / d
        b_ = b_ref[...]
        x = b_ * inv_d
        r = b_ - matvec(x)
        z = r * inv_d
        p = z
        rz = dot(r, z)

        def body(_, st):
            x, r, p, rz = st
            Ap = matvec(p)
            denom = dot(p, Ap)
            alpha_c = rz / jnp.where(denom != 0, denom, 1.0)
            x = x + alpha_c * p
            r = r - alpha_c * Ap
            z = r * inv_d
            rz2 = dot(r, z)
            beta = rz2 / jnp.where(rz != 0, rz, 1.0)
            p = z + beta * p
            return (x, r, p, rz2)

        x, *_ = jax.lax.fori_loop(0, K + 8, body, (x, r, p, rz))
        x_ref[...] = x

    vec = pl.BlockSpec((K, T), lambda i: (0, i))
    x_t = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, T),),
        in_specs=[
            pl.BlockSpec((K, K, T), lambda i: (0, 0, i)),
            pl.BlockSpec((K, K, T), lambda i: (0, 0, 0)),
            vec,
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((K, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K, T), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # A's block twice (the pipeline), reg's twice, the scratch
            vmem_limit_bytes=max(32 << 20, 6 * K * K * T * 4),
        ),
        interpret=interpret,
        name="als_cg_resident",
    )(A_t, reg_t, b.T)
    return x_t.T


#: blocks per grid step of the fused accumulation kernel: a
#: ``[_ACCUM_TILE * width, K]`` operand tile in flight twice (the pipeline)
#: and half as many ``[K + 16, 128]`` products waiting in VMEM
_ACCUM_TILE = 64
#: finished entity records on their way to HBM at once (the staging ring)
_ACCUM_RING = 8
#: the rank and block width :func:`_accum_fused` is written for: two
#: blocks' ``K``-wide products fill the ``_LANES`` lanes side by side, and
#: two blocks' slots are one lane row of weights
_ACCUM_RANK = 64
_ACCUM_WIDTH = 64


def _accum_impl(platform: str, rank: int, width: int, itemsize: int) -> str:
    """Which way ``partial_normal_eq`` turns a chunk's gathered rows into
    its entities' normal equations, from what is visible at trace time:
    ``fused`` / ``xla``. ``fused`` is the Pallas kernel
    (:func:`_accum_fused`): on a TPU, for bfloat16 operands, at the rank
    and block width it tiles (``_ACCUM_RANK``, ``_ACCUM_WIDTH``: what
    MovieLens-25M at rank 64 has on both sides, and the one shape measured
    on a v5e), alone on a device or under ``shard_map``. ``xla`` is the
    batched einsum and the ``segment_sum`` through HBM: everywhere else
    (CPU, ranks 10, 16 and 128, float32 operands), and the kernel's
    oracle."""
    tiles = (rank == _ACCUM_RANK and width == _ACCUM_WIDTH
             and itemsize == 2)
    return "fused" if platform == "tpu" and tiles else "xla"


def _accum_fused(AB, acc, held, ent, q, w, rhs, interpret: bool = False):
    """One chunk's contribution to the normal equations as one Pallas TPU
    kernel: the per-block products ``(q·w)ᵀ q`` and ``rhsᵀ q`` and their
    per-entity sums stay in VMEM; what crosses HBM is the gathered rows
    in and one finished record per entity out (the XLA path writes every
    block's ``K×K`` product, 64 MB a chunk at rank 64, and scatter-adds
    them back row by row).

    ``AB [n, K/2 + 8, 128]`` stays in HBM and is aliased in and out, so a
    scan's carry is updated in place. An entity's record is lane-dense:
    row ``r < K/2`` holds rows ``2r`` and ``2r + 1`` of its ``K×K`` sum
    side by side (the row-major matrix, so that entity-minor it IS the
    solve kernel's operand), row ``K/2`` its ``K`` vector
    (:func:`_accum_unpack`; ``f32[n, 64, 64]`` pads every row to 128
    lanes on a TPU, twice the bytes). ``ent [chunk]`` (ascending) rides
    in SMEM; ``q [chunk, W, K]``, ``w`` and ``rhs [chunk, W]`` come in
    tiles of ``_ACCUM_TILE`` blocks.

    Blocks go in pairs: the pair's 128 gathered rows, transposed, are the
    lanes of one ``[K, 128]`` operand; times the weights' lane row, over
    a row of ``rhs``, against the block-diagonal of the same two blocks it
    is ONE full-width MXU matmul (float32 accumulation) whose result is
    the two blocks' ``[K + 1, K]`` products side by side. Then the pairs
    are walked in order: while ``ent`` is the entity in hand the pair is
    added to the running sum ``acc [K + 16, 128]`` (even blocks left, odd
    blocks right: the halves are added when the entity is finished), when
    it changes the finished record goes to ``AB[entity]`` by an
    asynchronous copy from a ring of ``_ACCUM_RING`` staging slots. Every
    entity is written once and never read: the sum still open when the
    chunk ends is handed on (``acc``, ``held [1]``) and comes back with
    the next chunk, so an entity whose blocks straddle chunks sums in one
    piece, blocks in ascending order; the caller starts from
    :func:`_accum_init` and adds the last one after its scan
    (:func:`_accum_unpack`). Records that no block names are not
    touched."""
    import jax.numpy as jnp

    C, W, K = q.shape
    assert (K, W) == (_ACCUM_RANK, _ACCUM_WIDTH), (K, W)
    if C % 16:
        # a chunk the tiles do not divide (a small input, an odd
        # ``blocks_per_chunk``): blocks of weight 0 on the last entity
        more = (0, -C % 16)
        ent = jnp.pad(ent, more, mode="edge")
        q = jnp.pad(q, (more, (0, 0), (0, 0)))
        w, rhs = jnp.pad(w, (more, (0, 0))), jnp.pad(rhs, (more, (0, 0)))
        C = q.shape[0]
    call = _accum_call(AB.shape[0], C, W, K, q.dtype.name, interpret)
    return call(ent, held, q.reshape(C * W, K), w.reshape(C // 2, 2 * W),
                rhs.reshape(C // 2, 2 * W), acc, AB)


@functools.lru_cache(maxsize=16)
def _accum_call(n: int, C: int, W: int, K: int, dtype: str, interpret: bool):
    """:func:`_accum_fused`'s ``pallas_call`` for one set of shapes, built
    once: ``pallas_call`` hands back a jitted function that traces its
    kernel whenever it is new, and a trainer calls the kernel from ten
    programs at two shapes (a trace of its unrolled pairs is 0.5 s of
    set-up on the chip's host)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, L, H = K + 16, 2 * K, K // 2  # product rows, lanes, record rows of A
    T = next(t for t in (_ACCUM_TILE, 32, 16) if C % t == 0)
    ring = _ACCUM_RING
    dt = jnp.dtype(dtype)
    f32 = jnp.float32

    def kernel(ent_ref, held_ref, q_ref, w_ref, rhs_ref, acc_in, _AB_in,
               AB_ref, acc_out, held_out, P_ref, acc_ref, stage_ref, sem,
               state):
        i = pl.program_id(0)
        # lanes of the even block of a pair (a sliced mask does not compile)
        left, left_k, left_h = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1) < K
            for rows in (R, K, H))

        @pl.when(i == 0)
        def _():
            acc_ref[...] = acc_in[...]
            state[0] = held_ref[0]
            state[1] = 0

        def copy(slot, e):
            return pltpu.make_async_copy(
                stage_ref.at[slot], AB_ref.at[e], sem.at[slot])

        def flush():
            """The finished sum → its record, on its way to ``AB``."""
            done = state[1]
            slot = done % ring

            @pl.when(done >= ring)
            def _():
                copy(slot, 0).wait()

            # even + odd blocks (the lane halves), two matrix rows a lane
            # row: the row-major [K, K] as [K/2, 128]
            both = lambda x: x + pltpu.roll(x, K, 1)
            stage_ref[slot, :H] = jnp.where(
                left_h, both(acc_ref[pl.ds(0, H, stride=2), :]),
                both(acc_ref[pl.ds(1, H, stride=2), :]))
            stage_ref[slot, H:] = both(acc_ref[K:K + 8, :])
            copy(slot, state[0]).start()
            state[1] = done + 1

        # every pair's products, branch-free: [P_even | P_odd], rows :K
        # the matrices, row K the vectors, the rest repeat it (the
        # operand's sublane tile). Unrolled: the pairs are independent and
        # the scheduler overlaps them (as a loop, 2.5 times the kernel)
        for p in range(T // 2):
            xt = q_ref[p * 2 * W:(p + 1) * 2 * W, :].T  # [K, 2W]
            # the weight multiplied in the operands' dtype, as XLA's path
            # does it: the product of two such numbers is exact in float32
            w_row = w_ref[p:p + 1, :].astype(dt).astype(f32)
            lhs = jnp.concatenate([
                xt.astype(f32) * w_row,
                jnp.broadcast_to(rhs_ref[p:p + 1, :], (R - K, L)),
            ], axis=0).astype(dt)
            zero = jnp.zeros_like(xt)
            diag_t = jnp.concatenate([
                jnp.where(left_k, xt, zero), jnp.where(left_k, zero, xt),
            ], axis=0)  # the transposed block-diagonal of the pair's rows
            P_ref[p] = jax.lax.dot_general(
                lhs, diag_t, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)

        def take(e, part):
            """One block's product (its half of the lanes) into the sum."""
            @pl.when(e == state[0])
            def _():
                acc_ref[...] += part

            @pl.when(e != state[0])
            def _():
                flush()
                acc_ref[...] = part
                state[0] = e

        def pair(p, _):
            e0 = ent_ref[i * T + 2 * p]
            e1 = ent_ref[i * T + 2 * p + 1]
            same = jnp.logical_and(e0 == state[0], e1 == state[0])

            @pl.when(same)
            def _():
                acc_ref[...] += P_ref[p]

            @pl.when(jnp.logical_not(same))
            def _():
                both = P_ref[p]
                take(e0, jnp.where(left, both, 0.0))
                take(e1, jnp.where(left, 0.0, both))

            return 0

        jax.lax.fori_loop(0, T // 2, pair, 0)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            acc_out[...] = acc_ref[...]
            held_out[0] = state[0]
            for slot in range(ring):
                @pl.when(slot < state[1])
                def _():
                    copy(slot, 0).wait()

    tile = lambda rows, cols: pl.BlockSpec((rows, cols), lambda i, *_: (i, 0))
    whole = pl.BlockSpec((R, L), lambda i, *_: (0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C // T,),
            in_specs=[tile(T * W, K), tile(T // 2, L), tile(T // 2, L),
                      whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole,
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                pltpu.VMEM((T // 2, R, L), f32),
                pltpu.VMEM((R, L), f32),
                pltpu.VMEM((ring, H + 8, L), f32),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, H + 8, L), f32),
            jax.ShapeDtypeStruct((R, L), f32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        # operand numbers count the two scalar-prefetch arguments
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="als_accum_fused",
    )


def _accum_init(n_entities: int, K: int, block_ent):
    """:func:`_accum_fused`'s carry before a scan's first chunk: empty
    records, an empty sum, and the first block's entity to hold it."""
    import jax.numpy as jnp

    return (jnp.zeros((n_entities, K // 2 + 8, 2 * K), jnp.float32),
            jnp.zeros((K + 16, 2 * K), jnp.float32), block_ent[:1])


def _accum_unpack(AB, acc, held):
    """``(A [n, K, K], b [n, K])`` of :func:`_accum_fused`'s records, the
    sum it left open added in."""
    n, _, L = AB.shape
    K = L // 2
    H = K // 2
    A = AB[:, :H].reshape(n, K, K)
    b = AB[:, H, :K]
    last = acc[:, :K] + acc[:, K:]
    return A.at[held[0]].add(last[:K]), b.at[held[0]].add(last[K])


def _make_math(reg: float, implicit: bool, alpha: float,
               matmul_dtype: str, solver: str):
    """Shared jittable ALS math: blocked normal-equation accumulation and
    the batched solvers. Closed over the static config and used by BOTH
    the monolithic trainer (:func:`_build_trainer`) and the streamed
    trainer (:func:`_build_stream_trainer`) so the two paths cannot drift
    apart numerically. Three rules, each read when a trainer is traced,
    pick between an XLA form and a TPU form of one algorithm:
    :func:`_gather_impl` (the factor table's layout), :func:`_accum_impl`
    (the block products and their per-entity sums) and :func:`_solve_impl`
    (the solver)."""
    import types

    import jax
    import jax.numpy as jnp

    lam = jnp.float32(reg)
    alpha_f = jnp.float32(alpha)
    mm_dtype = jnp.dtype(matmul_dtype)

    @jax.named_scope("als.normal_eq")
    def partial_normal_eq(block_ent, block_other, block_r, factors,
                          n_entities, chunk, varying_axis=None):
        """Blocked scan: Σ w·q qᵀ and Σ rhs·q per entity (one shard).

        A scan step gathers a chunk's factor rows ``[chunk, W, K]`` (the
        one intermediate that crosses HBM whatever the path, unless the
        compiler keeps it in VMEM) and adds the chunk to the carry: on
        XLA's path a batched einsum writes every block's ``K×K`` product
        and a ``segment_sum`` adds them into ``A [n, K, K]``; on the
        fused path (:func:`_accum_impl`) one kernel multiplies and sums
        in VMEM and writes each finished entity's lane-dense record, the
        carry is ``[n, K/2 + 8, 128]`` and ``A``, ``b`` are cut from it
        after the scan (:func:`_accum_unpack`). Same sums, the blocks of
        an entity in ascending order on both."""
        K = factors.shape[1]
        # cast ONCE per half-step: the scan then gathers from the low-
        # precision table (half the HBM traffic) and the einsums hit the
        # MXU at its native bf16 rate; accumulation stays f32 below
        factors_mm = factors.astype(mm_dtype)
        # the layout is static at trace time too (the rule: _gather_impl)
        if _gather_impl(jax.default_backend(), factors.shape[0], K,
                        mm_dtype.itemsize) == "packed":
            with jax.named_scope("gather"):
                factors_mm = _pack_table(factors_mm)

        # ...and so is the way the rows become normal equations (the
        # rule: _accum_impl); off a TPU only a test's steering picks the
        # kernel, and it is interpreted
        backend = jax.default_backend()
        fused = _accum_impl(backend, K, block_other.shape[1],
                            mm_dtype.itemsize) == "fused"

        def chunk_step(carry, ch):
            ent, other, r_c = ch
            # padded slots are other == -1; validity derives from the sign
            m_c = (other >= 0).astype(jnp.float32)
            with jax.named_scope("gather"):
                # [chunk, W, K] gather
                q = _gather_rows(factors_mm, jnp.maximum(other, 0), K)
            if implicit:
                # confidence c = 1 + α r; correction weight (c-1)·mask
                w = alpha_f * r_c * m_c
                rhs = (1.0 + alpha_f * r_c) * m_c  # c · preference(=1)
            else:
                w = m_c
                rhs = r_c * m_c
            if fused:
                # products and per-entity sums in VMEM: one kernel
                with jax.named_scope("segment_sum"):
                    return _accum_fused(*carry, ent, q, w, rhs,
                                        interpret=backend != "tpu"), None
            A, b = carry
            # batched MXU matmul: [chunk, K, W] @ [chunk, W, K], f32 acc
            with jax.named_scope("outer"):
                A_blk = jnp.einsum(
                    "cwk,cwl->ckl", q * w[:, :, None].astype(mm_dtype), q,
                    preferred_element_type=jnp.float32,
                )
                b_blk = jnp.einsum(
                    "cwk,cw->ck", q, rhs.astype(mm_dtype),
                    preferred_element_type=jnp.float32,
                )
            with jax.named_scope("segment_sum"):
                A = A + jax.ops.segment_sum(
                    A_blk, ent, num_segments=n_entities,
                    indices_are_sorted=True,
                )
                b = b + jax.ops.segment_sum(
                    b_blk, ent, num_segments=n_entities,
                    indices_are_sorted=True,
                )
            return (A, b), None

        S = block_ent.shape[0]
        n_chunks = S // chunk
        chunks = tuple(
            x.reshape(n_chunks, chunk, *x.shape[1:])
            for x in (block_ent, block_other, block_r)
        )
        if fused:
            init = _accum_init(n_entities, K, block_ent)
        else:
            init = (jnp.zeros((n_entities, K, K), jnp.float32),
                    jnp.zeros((n_entities, K), jnp.float32))
        if varying_axis is not None:
            # Inside shard_map the carry becomes device-varying after the
            # first chunk; mark the zeros accordingly so scan types match.
            from jax.lax import pcast

            init = tuple(
                pcast(x, (varying_axis,), to="varying") for x in init)
        carry, _ = jax.lax.scan(chunk_step, init, chunks)
        return _accum_unpack(*carry) if fused else carry

    @jax.named_scope("cg")
    def _cg_solve(A, b):
        """Batched Jacobi-preconditioned CG: no factorization, so it
        avoids XLA's serialized batched Cholesky/LU on TPU (measured ~8x
        faster than LU at MovieLens-25M entity counts). The matvec is per
        entity, so it runs on the vector unit, not the MXU, and every
        sweep streams the whole ``A`` from HBM: the path on CPU, for
        ranks the resident kernel does not tile, and that kernel's
        oracle. A is SPD (normal equations + λI); K+8 iterations ≥ the
        Krylov dimension with margin for f32 rounding on ill-conditioned
        systems."""
        K = b.shape[1]
        inv_d = 1.0 / jnp.diagonal(A, axis1=1, axis2=2)
        x = b * inv_d
        r = b - jnp.einsum("nkl,nl->nk", A, x)
        z = r * inv_d
        p = z
        rz = (r * z).sum(-1)

        def body(_, st):
            x, r, p, rz = st
            Ap = jnp.einsum("nkl,nl->nk", A, p)
            denom = (p * Ap).sum(-1)
            alpha_c = rz / jnp.where(denom != 0, denom, 1.0)
            x = x + alpha_c[:, None] * p
            r = r - alpha_c[:, None] * Ap
            z = r * inv_d
            rz2 = (r * z).sum(-1)
            beta = rz2 / jnp.where(rz != 0, rz, 1.0)
            p = z + beta[:, None] * p
            return (x, r, p, rz2)

        x, *_ = jax.lax.fori_loop(0, K + 8, body, (x, r, p, rz))
        return x

    @jax.named_scope("als.solve")
    def solve_block(A, b, gram):
        """Regularized batched solve on a block of entities."""
        K = b.shape[1]
        # A.shape[0] and the backend are static at trace time, so this is
        # a compile-time branch (the rule: _solve_impl)
        backend = jax.default_backend()
        impl = _solve_impl(solver, A.shape[0], K, backend)
        reg_kk = lam * jnp.eye(K, dtype=jnp.float32)
        if impl == "resident_cg":
            # the kernel adds the regulariser in VMEM: no second copy of A
            if implicit:
                reg_kk = reg_kk + gram
            # off a TPU only a test's steering gets here: interpreted
            with jax.named_scope("cg"), jax.named_scope("resident"):
                return _cg_solve_resident(
                    A, b, reg_kk, interpret=backend != "tpu")
        A = A + reg_kk[None, :, :]
        if implicit:
            A = A + gram[None, :, :]
        if impl == "xla_cg":
            return _cg_solve(A, b)
        if impl == "cholesky":
            L = jnp.linalg.cholesky(A)
            y = jax.scipy.linalg.solve_triangular(
                L, b[:, :, None], lower=True
            )
            x = jax.scipy.linalg.solve_triangular(
                jnp.swapaxes(L, 1, 2), y, lower=False
            )
            return x[:, :, 0]
        return jnp.linalg.solve(A, b[:, :, None])[:, :, 0]

    @jax.named_scope("als.gram")
    def gram_of(factors):
        if implicit:
            return jnp.einsum("ik,il->kl", factors, factors)
        return jnp.zeros((factors.shape[1], factors.shape[1]), jnp.float32)

    def half_local(blocks, factors, n_entities, chunk):
        """One single-device half-step from a blocked layout."""
        A, b = partial_normal_eq(*blocks, factors, n_entities, chunk)
        return solve_block(A, b, gram_of(factors))

    return types.SimpleNamespace(
        partial_normal_eq=partial_normal_eq,
        solve_block=solve_block,
        gram_of=gram_of,
        half_local=half_local,
    )


@functools.lru_cache(maxsize=32)
def _build_trainer(mesh, axis: str, iterations: int, reg: float,
                   implicit: bool, alpha: float,
                   chunk_user: int, chunk_item: int,
                   matmul_dtype: str = "bfloat16", solver: str = "cg",
                   packed_shapes=None, rank: int = 0,
                   U_pad: int = 0, I_pad: int = 0,
                   mesh_span_lens=None):
    """Jitted ALS trainer for one (mesh, static-config) combination.

    With ``packed_shapes`` (``(S_user, w_user, S_item, w_item)``) the
    returned function is ``run_packed(counts_u, counts_i, i, r, seed)``:
    the (user, item)-sorted edges as plain ``int32`` item ids and
    ``float32`` ratings with the two degree histograms, from which it
    builds both blocked layouts on the device (:func:`device_pack`) before
    it iterates. On a mesh ``i`` and ``r`` are tuples of spans sharded
    over ``axis``, ``mesh_span_lens`` their true lengths. Without
    ``packed_shapes`` it takes the two blocked layouts themselves
    (:func:`_train_mesh_host_packed`, the tests' oracle). Nothing here
    depends on the values of the data: shapes specialize inside
    jax.jit's own cache.
    """
    import jax
    import jax.numpy as jnp

    math = _make_math(reg, implicit, alpha, matmul_dtype, solver)
    partial_normal_eq = math.partial_normal_eq
    solve_block = math.solve_block
    gram_of = math.gram_of

    if mesh is not None and mesh.shape[axis] > 1:
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        blk_spec = (P(axis), P(axis), P(axis))

        def half_step(ent, other, r, factors, n_entities, chunk):
            """shard_map body: block-parallel accumulate → reduce-scatter →
            local solve → all-gather (the MLlib-shuffle replacement)."""

            def body(ent, other, r, factors):
                A, b = partial_normal_eq(
                    ent, other, r, factors, n_entities, chunk,
                    varying_axis=axis,
                )
                # reduce-scatter the normal equations over the entity dim:
                # each device ends up owning n_entities/D rows, fully summed.
                A = jax.lax.psum_scatter(A, axis, scatter_dimension=0, tiled=True)
                b = jax.lax.psum_scatter(b, axis, scatter_dimension=0, tiled=True)
                new_local = solve_block(A, b, gram_of(factors))  # [n/D, K]
                return jax.lax.all_gather(new_local, axis, axis=0, tiled=True)

            # check_vma=False: after the tiled all_gather every device holds
            # identical factors, but the varying-axis type system can't
            # infer that replication statically.
            return shard_map(
                body,
                mesh=mesh,
                in_specs=blk_spec + (P(),),
                out_specs=P(),
                check_vma=False,
            )(ent, other, r, factors)
    else:

        def half_step(ent, other, r, factors, n_entities, chunk):
            A, b = partial_normal_eq(
                ent, other, r, factors, n_entities, chunk
            )
            return solve_block(A, b, gram_of(factors))

    def run_body(by_user, by_item, seed):
        # factor init on device, inside the one compiled program:
        # MLlib-style |N(0,1)|/√rank — POSITIVE entries matched to the
        # nonnegative ratings. A tiny symmetric init (±0.01) makes the
        # first reg-dominated half-step collapse every factor onto one
        # direction, and ALS (monotone) then converges inside that
        # rank-deficient basin on some seeds
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        scale = jnp.float32(rank) ** -0.5
        P_init = jnp.abs(jax.random.normal(ku, (U_pad, rank), jnp.float32)) * scale
        Q_init = jnp.abs(jax.random.normal(ki, (I_pad, rank), jnp.float32)) * scale

        def iteration(_, PQ):
            P_f, Q_f = PQ
            with jax.named_scope("als.user"):
                P_f = half_step(*by_user, Q_f, U_pad, chunk_user)
            with jax.named_scope("als.item"):
                Q_f = half_step(*by_item, P_f, I_pad, chunk_item)
            return (P_f, Q_f)

        return jax.lax.fori_loop(0, iterations, iteration, (P_init, Q_init))

    if packed_shapes is None:
        return jax.jit(run_body)

    # the edge list ships ONCE, sorted by (user, item) on the host, and
    # BOTH blocked layouts are built here, inside the same dispatch. The
    # per-edge user ids never cross the link: the sorted order makes them
    # one repeat of the per-user counts.
    su, wu, si, wi = packed_shapes

    @jax.jit
    def run_packed(counts_u, counts_i, i32, r32, seed):
        if mesh is not None and mesh_span_lens is not None:
            # the edge arrays arrived SHARDED over the mesh axis (the
            # host link crossed once) as one or more spans
            # (PIO_TPU_ALS_STREAM_MB: chunked puts pipeline the
            # per-device transfers); re-replicate each span over ICI,
            # drop its shard-divisibility padding and splice the stream
            # back together: device_pack copies runs of the whole list
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())

            def gather_cat(spans):
                parts = [
                    jax.lax.with_sharding_constraint(c, repl)[:n]
                    for c, n in zip(spans, mesh_span_lens)
                ]
                return parts[0] if len(parts) == 1 \
                    else jnp.concatenate(parts)

            i32 = gather_cat(i32)
            r32 = gather_cat(r32)
        with jax.named_scope("als.pack"):
            u32 = _entity_column(counts_u, i32.shape[0])
        # both degree histograms ride the link (0.9 MB at ml-25m): the
        # on-device bincount is a 25M-edge scatter-add, the host count is
        # a pass the sort already made
        by_user = device_pack(u32, i32, r32, U_pad, wu, su,
                              assume_sorted=True, counts=counts_u)
        by_item = device_pack(i32, u32, r32, I_pad, wi, si,
                              counts=counts_i)
        return run_body(by_user, by_item, seed)

    return run_packed


@functools.lru_cache(maxsize=16)
def _build_stream_trainer(iterations: int, reg: float, implicit: bool,
                          alpha: float, matmul_dtype: str, solver: str,
                          rank: int, U_pad: int, I_pad: int,
                          w_user: int, w_item: int, S_item: int,
                          chunk_stream: int, chunk_item: int,
                          chunk_spec: tuple):
    """Double-buffered single-device trainer: the sorted edges (plain
    ``int32`` item ids, ``float32`` ratings) arrive in
    ``len(chunk_spec)`` slices and each slice's by-user block pack + its
    contribution to iteration 1's user-side normal equations run WHILE the
    next slice is still crossing the host↔device link (the queued
    ``device_put``s ride the transfer stream; each chunk program only waits
    on its own inputs). ``chunk_spec`` is a tuple of per-chunk
    ``(S_c, pad_entity, first_user)``: the chunk's static padded block
    count, the entity its padding blocks alias (the chunk's LAST user,
    which keeps the concatenated block layout globally ascending for the
    segment-sum sorted fast path), and the first user present (the sliced
    local-counts offset).

    The finalize program concatenates the chunk-local block layouts into
    the full by-user layout (no repack), solves P1 from the streamed
    normal equations, packs the item side from the concatenated edge
    slices (still on the device), and runs the remaining iterations.
    Numerically this differs from the monolithic path only in iteration-1
    accumulation grouping (float reduction order). The programs depend
    on the shapes alone (the two degree sequences through ``chunk_spec``
    and the slice lengths), never on the ids or the ratings."""
    import jax
    import jax.numpy as jnp

    math = _make_math(reg, implicit, alpha, matmul_dtype, solver)

    def _lc_full(local_counts, u0_c):
        """Expand a chunk's sliced local-counts span to full U_pad."""
        return jax.lax.dynamic_update_slice(
            jnp.zeros(U_pad, jnp.int32),
            local_counts.astype(jnp.int32), (u0_c,),
        )

    @jax.jit
    def init(seed):
        # same key split as run_body: ku (P_init) is unused — the first
        # half-step overwrites P — so only Q0 must match the monolithic
        # trainer's draw
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        del ku
        Q0 = jnp.abs(
            jax.random.normal(ki, (I_pad, rank), jnp.float32)
        ) * (jnp.float32(rank) ** -0.5)
        A0 = jnp.zeros((U_pad, rank, rank), jnp.float32)
        b0 = jnp.zeros((U_pad, rank), jnp.float32)
        return Q0, A0, b0

    def _make_accum(S_c: int, pad_c: int, u0_c: int):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def accum(A, b, Q0, local_counts, i32, r32):
            # local_counts arrives sliced to the chunk's present-user span
            # [u0_c, pad_c] (ships span·4 B instead of U_pad·4 B per
            # chunk); expand to full length on device
            with jax.named_scope("als.pack"):
                lc = _lc_full(local_counts, u0_c)
            blocks = device_pack(
                None, i32, r32, U_pad, w_user, S_c,
                assume_sorted=True, counts=lc, pad_entity=pad_c,
            )
            with jax.named_scope("als.user"):
                dA, db = math.partial_normal_eq(
                    *blocks, Q0, U_pad, chunk_stream
                )
                A, b = A + dA, b + db
            return A, b, blocks

        return accum

    accums = tuple(_make_accum(*spec) for spec in chunk_spec)

    @jax.jit
    def finalize(A, b, Q0, counts_u, counts_i, user_blocks, edge_chunks):
        # full by-user layout = concat of the chunk-local packs (padding
        # aliases each chunk's last user, so ids stay ascending)
        with jax.named_scope("als.pack"):
            by_user = tuple(
                jnp.concatenate([blk[k] for blk in user_blocks])
                for k in range(3)
            )
            # item side needs the full COO: the (device-resident) slices
            i32 = jnp.concatenate([i_c for i_c, _ in edge_chunks])
            r32 = jnp.concatenate([r_c for _, r_c in edge_chunks])
            u32 = _entity_column(counts_u, i32.shape[0])
        by_item = device_pack(i32, u32, r32, I_pad, w_item, S_item,
                              counts=counts_i)
        # iteration 1: user half is already accumulated (streamed)
        with jax.named_scope("als.user"):
            P = math.solve_block(A, b, math.gram_of(Q0))
        with jax.named_scope("als.item"):
            Q = math.half_local(by_item, P, I_pad, chunk_item)

        def iteration(_, PQ):
            P, Q = PQ
            with jax.named_scope("als.user"):
                P = math.half_local(by_user, Q, U_pad, chunk_stream)
            with jax.named_scope("als.item"):
                Q = math.half_local(by_item, P, I_pad, chunk_item)
            return (P, Q)

        return jax.lax.fori_loop(0, iterations - 1, iteration, (P, Q))

    return init, accums, finalize


def device_pack(ent, oth, rat, n_entities: int, width: int, S: int,
                assume_sorted: bool = False, counts=None,
                pad_entity=None):
    """On-device COO→blocked-CSR packing (traceable; jnp throughout).

    Layout is bit-identical to the host packer (_pack_blocks), enforced
    by tests/test_als.py ``test_device_pack_matches_host_packers``.
    ``S``, ``width``, and ``n_entities`` are static. ``assume_sorted``
    says that the edges arrive in ascending ``ent`` order (the streamed
    chunks, the counts-rebuilt user column); otherwise one stable sort
    by ``ent`` carries ``oth`` and ``rat`` along as payloads.

    Formulated as a COPY OF RUNS: in entity order a block's ``width``
    slots hold consecutive edges, from ``edge_start[entity] +
    block_in_entity * width``, masked by the entity's count. So every
    block fetches its run as whole lane rows of the edge list
    (:func:`_copy_runs`: a gather of ``S`` rows of 128, not of ``S *
    width`` scalars; a TPU gather costs by the row, and a scalar is a
    row). Forms to avoid: the scatter (``.at[flat].set`` over the slot
    space serializes on a TPU), the slot-wise gather ``oth[src]`` (1.57 s
    of a 5.09 s call at MovieLens-25M, rank 64: ledger, PR 33), and a
    batched ``dynamic_slice`` of ``width`` elements out of the flat list,
    which XLA expands into a ``while`` of ``S`` slices.

    ``pad_entity`` redirects the padding blocks' (masked) entity id —
    the streamed trainer points them at a chunk's LAST present entity so
    concatenated chunk layouts stay globally ascending. Only valid when
    no real block belongs to an entity beyond it. ``ent`` may be ``None``
    when ``counts`` is supplied with ``assume_sorted`` (it is unused).
    """
    import jax
    import jax.numpy as jnp

    with jax.named_scope("als.pack"):
        if counts is None:
            counts = jnp.bincount(ent, length=n_entities)  # order-free
        else:
            counts = counts.astype(jnp.int32)  # caller-supplied (wire input)
        if not assume_sorted:
            _, oth, rat = jax.lax.sort(
                (ent, oth, rat), num_keys=1, is_stable=True)
        blocks = -(-counts // width)
        zero = jnp.zeros(1, counts.dtype)
        block_start = jnp.concatenate([zero, jnp.cumsum(blocks)])
        edge_start = jnp.concatenate([zero, jnp.cumsum(counts)])

        # per block: owning entity (padding blocks → pad_entity, masked out)
        pad_tgt = (n_entities - 1) if pad_entity is None else pad_entity
        # (unrolled: the scan form is a ``while`` of log2(n) turns)
        bids = jnp.searchsorted(block_start[1:], jnp.arange(S), side="right",
                                method="scan_unrolled")
        block_ent = jnp.minimum(bids, pad_tgt).astype(jnp.int32)

        # per block: where its run starts within the entity's adjacency
        # and how many edges are left there; per slot: one of them or not
        first = (jnp.arange(S) - block_start[block_ent]) * width  # [S]
        left = counts[block_ent] - first
        valid = jnp.arange(width)[None, :] < left[:, None]  # [S, W]
        # a padding block (nothing left) reads the list's head: its own
        # start may lie past the end, and every slot of it is masked
        start = jnp.where(left > 0, edge_start[block_ent] + first, 0)
        block_other = jnp.where(
            valid, _copy_runs(oth, start, width), jnp.int32(-1))
        block_rating = jnp.where(
            valid, _copy_runs(rat, start, width), jnp.float32(0.0))
        return block_ent, block_other, block_rating


def _entity_column(counts, n_edges: int):
    """The entity of every edge of a list in entity order, from the degree
    counts: ``repeat(arange(n), counts)``. A one at each entity's first
    edge (an empty entity's falls on its successor's, a trailing one's
    off the end), prefix-summed: ``jnp.repeat`` computes the same and
    then gathers one scalar an edge out of the ``arange``, which is the
    identity and cost 0.17 s of a call at 25 M edges (my chip run, PR
    34)."""
    import jax.numpy as jnp

    counts = counts.astype(jnp.int32)
    first_edge = jnp.cumsum(counts) - counts
    ones = jnp.zeros(n_edges, jnp.int32).at[first_edge].add(
        1, indices_are_sorted=True, mode="drop")
    return jnp.cumsum(ones) - 1


def _copy_runs(flat, start, width: int):
    """``out[s, :] = flat[start[s] : start[s] + width]`` as ``[S, width]``,
    for starts inside ``flat``; what lies past its end reads 0.

    The list is viewed as rows of ``_LANES``; a run is fetched as the
    whole rows it touches (two, for a width up to 128 at any offset: one
    native row gather each, no ``while``), joined, and shifted left by
    the start's offset within its row: one select between two static
    slices per bit of the offset, the highest first, each pass keeping
    only the columns a remaining shift can still reach."""
    import jax.numpy as jnp

    n = flat.shape[0]
    k = (width + _LANES - 2) // _LANES + 1  # rows a run can touch
    n_rows = -(-n // _LANES) + k - 1  # so the last run's rows exist
    rows = jnp.pad(flat, (0, n_rows * _LANES - n)).reshape(n_rows, _LANES)
    row, off = start // _LANES, start % _LANES
    x = jnp.concatenate([rows[row + j] for j in range(k)], axis=1)
    for bit in reversed(range(_LANES.bit_length() - 1)):
        step = 1 << bit
        keep = width + step - 1
        x = jnp.where((off & step != 0)[:, None],
                      x[:, step:step + keep], x[:, :keep])
    return x


def _edge_spans(n_edges: int, n_stream: int) -> list:
    """The ``(e0, e1)`` edge spans of a chunked shipment: ``n_stream``
    near-even cuts on even edge numbers, empty spans dropped. In the
    streamed trainer the cuts group iteration 1's sums, so moving one
    moves the trained floats (on a mesh the spans are spliced back)."""
    bounds = [min(n_edges, (n_edges * c // n_stream) // 2 * 2)
              for c in range(n_stream)] + [n_edges]
    return [(e0, e1) for e0, e1 in zip(bounds[:-1], bounds[1:]) if e1 > e0]


def _run_streamed(config: "ALSConfig", rank: int, U_pad: int, I_pad: int,
                  w_user: int, w_item: int, S_item: int, chunk_item: int,
                  counts_u: np.ndarray, counts_i: np.ndarray,
                  i_sorted: np.ndarray, r_sorted: np.ndarray,
                  n_stream: int, seed, stats: Optional[dict],
                  capture: ScopeCapture):
    """Dispatch the double-buffered single-device training run.

    Slices the (user, item)-sorted edges into ``n_stream`` spans (views:
    nothing is encoded or copied), queues every span's ``device_put`` up
    front (async: they drain on the transfer stream in order), then
    chains the per-chunk accumulate programs: chunk k's pack +
    normal-equation accumulation executes while chunk k+1 is still
    crossing the link. A user whose adjacency straddles a cut is in both
    chunks' local counts with its share of each. With ``stats`` the
    phases are serialized (block between h2d and compute) to measure
    them: overlap off.
    """
    import jax

    with active_span("als.build"):
        edge_start = np.zeros(U_pad + 1, np.int64)
        np.cumsum(counts_u, out=edge_start[1:])
        spans = _edge_spans(i_sorted.shape[0], n_stream)

        local_slices, n_blocks, chunk_spec = [], [], []
        for e0, e1 in spans:
            lc = np.diff(np.clip(edge_start, e0, e1))
            u0 = int(np.searchsorted(edge_start, e0, side="right")) - 1
            pad_c = int(np.searchsorted(edge_start, e1 - 1, side="right")) - 1
            local_slices.append(
                np.ascontiguousarray(lc[u0:pad_c + 1], np.int32)
            )
            n_blocks.append(int((-(-lc // w_user)).sum()))
            chunk_spec.append([0, pad_c, u0])  # S_c filled below
        chunk_stream = min(
            config.blocks_per_chunk,
            _round_up(max(1, -(-sum(n_blocks) // len(spans))), 8),
        )
        for spec, nb in zip(chunk_spec, n_blocks):
            spec[0] = _round_up(max(nb, 1), chunk_stream)

        init, accums, finalize = _build_stream_trainer(
            config.iterations, float(config.reg), bool(config.implicit),
            float(config.alpha), _resolve_matmul_dtype(str(config.matmul_dtype)), str(config.solver),
            rank, U_pad, I_pad, w_user, w_item, S_item,
            chunk_stream, chunk_item,
            tuple(tuple(s) for s in chunk_spec),
        )

    # the shared streamed-feed executor (parallel/stream.py) runs the
    # slice → queued-put → chained-dispatch loop; ALS retains the edge
    # chunks (finalize packs the item side from them) so it rides the
    # queue-ahead mode (lookahead=0). The executor's encode phase is the
    # slicing alone and lands under the ``pack_s`` stats key.
    from pio_tpu.parallel.stream import stream_feed

    def encode(chunk):
        (e0, e1), lc = chunk
        return lc, i_sorted[e0:e1], r_sorted[e0:e1]

    extra = {}

    def put_extra():
        extra["cu"] = jax.device_put(counts_u.astype(np.int32))
        extra["ci"] = jax.device_put(
            np.ascontiguousarray(counts_i, np.int32)
        )
        return extra["cu"], extra["ci"]

    def init_carry():
        Q0, A, b = init(seed)
        return Q0, A, b, ()

    def dispatch(carry, dev, c):
        Q0, A, b, user_blocks = carry
        A, b, blk = accums[c](A, b, Q0, *dev)
        # chunk progress for the telemetry plane: ALS has no per-step
        # loss (normal equations), so progress is edges accumulated
        e0, e1 = spans[c]
        trainwatch.record_steps(0, examples=e1 - e0)
        return Q0, A, b, user_blocks + (blk,)

    def fin(carry, devs):
        Q0, A, b, user_blocks = carry
        return finalize(A, b, Q0, extra["cu"], extra["ci"], user_blocks,
                        tuple((i_c, r_c) for _lc, i_c, r_c in devs))

    return stream_feed(
        list(zip(spans, local_slices)),
        encode=encode, put_extra=put_extra,
        init_carry=init_carry, dispatch=dispatch, finalize=fin,
        stats=stats, encode_stat_key="pack_s", device_phase=capture,
    )


def _sort_edges_by_user(user_idx, item_idx, rating, n_edges, U_pad,
                        counts_u):
    """(user, item)-sorted item/rating columns: native two-pass sort
    (counting sort by user + per-adjacency stable item sort) with a numpy
    lexsort fallback. The order fixes every float sum downstream, and
    item-sorted adjacencies improve factor-gather locality on device; ALS
    itself is order-invariant within a user."""
    native = _native_packer()
    if native is not None:
        i_sorted = np.empty(n_edges, np.int32)
        r_sorted = np.empty(n_edges, np.float32)
        native.als_sort_by_entity(
            _i32p(user_idx), _i32p(item_idx), _f32p(rating),
            n_edges, U_pad, _i64p(counts_u),
            _i32p(i_sorted), _f32p(r_sorted),
        )
        rc = native.als_sort_within_entity(
            _i32p(i_sorted), _f32p(r_sorted), U_pad, _i64p(counts_u)
        )
        if rc != 0:  # a single entity with ≥2^32 edges: the radix
            # sorter's 32-bit cursors would wrap, so it refuses
            # wholesale. Training is order-invariant so this is safe;
            # say so instead of silently diverging from the numpy
            # lexsort path in the last float digits.
            log.warning(
                "within-user item sort skipped (an entity exceeds "
                "2^24 edges); its edges stay in arrival order"
            )
    else:
        order = np.lexsort((item_idx, user_idx))
        i_sorted = np.ascontiguousarray(item_idx[order])
        r_sorted = np.ascontiguousarray(rating[order])
    return i_sorted, r_sorted


def _counts_layout(ent, width: int, n_entities: int, n_shards: int,
                   blocks_per_chunk: int):
    """counts + (chunk, padded block count S) for one side."""
    native = _native_packer()
    if native is not None:
        counts = np.zeros(n_entities, np.int64)
        n_blocks = int(native.als_pack_count(
            _i32p(ent), len(ent), n_entities, width, _i64p(counts)
        ))
        if n_blocks < 0:
            raise ValueError("entity index out of range")
    else:
        counts = np.bincount(ent, minlength=n_entities)
        n_blocks = int((-(-counts // width)).sum())
    per_shard = max(1, -(-n_blocks // n_shards))
    chunk = min(blocks_per_chunk, _round_up(per_shard, 8))
    pad_to = n_shards * chunk
    # single home for the padded block count: the numpy packer is handed
    # S directly so the oracle cannot drift from the device's layout
    S = max(pad_to, _round_up(max(n_blocks, 1), pad_to))
    return counts, chunk, S


def _run_mesh_compact(config, mesh, axis, n_shards, user_idx, item_idx,
                      rating, n_edges, U_pad, I_pad, w_user, w_item,
                      trainer, seed, stats, capture):
    """Multi-shard training from the sorted plain edges.

    The host link (PCIe on a TPU VM) is the slow hop and ICI the fast
    one, so the edges cross the host link exactly once: the two
    edge-indexed arrays (``int32`` item ids, ``float32`` ratings) ship
    SHARDED over the mesh axis (each device receives 1/n of 8 B/edge),
    and the jitted trainer re-replicates them with an all-gather that
    rides ICI before the on-device dual blocked-layout construction
    (``device_pack``). The constructed block arrays come out sharded by
    block index (the layout the shard_map half-steps consume), so block
    CONTENT never needs host-side shard routing. Bit-identical to
    :func:`_train_mesh_host_packed` by the device_pack parity guarantee."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    with active_span("als.sort") as sort:
        counts_u, chunk_user, S_u = _counts_layout(
            user_idx, w_user, U_pad, n_shards, config.blocks_per_chunk)
        counts_i, chunk_item, S_i = _counts_layout(
            item_idx, w_item, I_pad, n_shards, config.blocks_per_chunk)
        if S_u * w_user >= 2 ** 31 or S_i * w_item >= 2 ** 31:
            raise ValueError(
                "edge set too large for int32 block addressing; raise "
                "block width or shard the edge set first"
            )
        counts_u = np.ascontiguousarray(counts_u, np.int64)
        i_sorted, r_sorted = _sort_edges_by_user(
            user_idx, item_idx, rating, n_edges, U_pad, counts_u
        )
    # chunked shipment (the single-device stream discipline applied to
    # the sharded puts): slice both arrays into ≤8 spans so the
    # per-device transfers of span k+1 pipeline behind span k instead of
    # one monolithic put per array serializing the whole h2d. The
    # trainer splices the trimmed spans back together before packing.
    with active_span("als.build"):
        spans = _edge_spans(n_edges, _n_stream_chunks(
            _EDGE_BYTES * n_edges, "PIO_TPU_ALS_STREAM_MB"))
        run = trainer(
            chunk_user, chunk_item, (S_u, w_user, S_i, w_item),
            mesh_span_lens=tuple(e1 - e0 for e0, e1 in spans),
        )
    if stats is not None:
        stats["pack_s"] = sort.seconds
        stats["wire_bytes"] = _EDGE_BYTES * n_edges + 4 * (U_pad + I_pad)
        stats["n_stream"] = len(spans)
    shard1 = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def pad_to_shards(a):
        p = (-len(a)) % n_shards
        return np.concatenate([a, np.zeros(p, a.dtype)]) if p else a

    with active_span("als.put") as put:
        counts_dev = (
            jax.device_put(counts_u.astype(np.int32), repl),
            jax.device_put(np.ascontiguousarray(counts_i, np.int32), repl),
        )
        # a span of both arrays goes out together so early spans of each
        # are in flight at once; per-span timings land in stats on
        # profiled runs
        i_dev, r_dev, chunk_ts = [], [], []
        for e0, e1 in spans:
            tc = monotonic_s()
            i_dev.append(
                jax.device_put(pad_to_shards(i_sorted[e0:e1]), shard1))
            r_dev.append(
                jax.device_put(pad_to_shards(r_sorted[e0:e1]), shard1))
            if stats is not None:
                jax.block_until_ready((i_dev[-1], r_dev[-1]))
                chunk_ts.append(round(monotonic_s() - tc, 3))
        args = (*counts_dev, tuple(i_dev), tuple(r_dev))
        if stats is not None:
            jax.block_until_ready(args)
    if stats is not None:
        stats["h2d_s"] = put.seconds
        stats["h2d_chunk_s"] = chunk_ts
    return _run_whole(run, (*args, seed), stats, capture)


def _train_mesh_host_packed(ctx: ComputeContext, user_idx, item_idx, rating,
                            n_users: int, n_items: int,
                            config: "ALSConfig") -> "ALSFactors":
    """The mesh route's bit-exact oracle; ``train_als`` never takes it,
    tests/test_als.py and the dry run of ``__graft_entry__.py`` do.

    Both blocked layouts are packed on the host in numpy
    (:func:`_pack_blocks` over the ``lexsort``ed edges) and shipped as
    they are, 16 times the bytes an edge; the shard_map trainer is the one
    ``train_als`` runs, less its ``device_pack``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axis = ctx.mesh, ctx.batch_axis
    n_shards = mesh.shape[axis]
    U_pad = _round_up(max(n_users, 1), n_shards)
    I_pad = _round_up(max(n_items, 1), n_shards)
    order = np.lexsort((item_idx, user_idx))
    u = np.ascontiguousarray(np.asarray(user_idx, np.int32)[order])
    i = np.ascontiguousarray(np.asarray(item_idx, np.int32)[order])
    r = np.ascontiguousarray(np.asarray(rating, np.float32)[order])

    def layout(ent, other, n_true, n_pad):
        width = config.block_width or _auto_width(len(ent), n_true)
        counts, chunk, S = _counts_layout(
            ent, width, n_pad, n_shards, config.blocks_per_chunk)
        blocks = _pack_blocks(ent, other, r, n_pad, width, S, counts=counts)
        assert blocks[0].shape[0] == S
        return blocks, chunk

    by_user, chunk_user = layout(u, i, n_users, U_pad)
    by_item, chunk_item = layout(i, u, n_items, I_pad)
    run = _build_trainer(
        mesh, axis, config.iterations, float(config.reg),
        bool(config.implicit), float(config.alpha), chunk_user, chunk_item,
        _resolve_matmul_dtype(str(config.matmul_dtype)), str(config.solver),
        None, config.rank, U_pad, I_pad,
    )
    blk = NamedSharding(mesh, P(axis))
    blk2 = NamedSharding(mesh, P(axis, None))

    def put_blocks(t):
        return (jax.device_put(t[0], blk), jax.device_put(t[1], blk2),
                jax.device_put(t[2], blk2))

    P_f, Q_f = jax.device_get(
        run(put_blocks(by_user), put_blocks(by_item), np.uint32(config.seed)))
    return ALSFactors(user_factors=np.asarray(P_f)[:n_users],
                      item_factors=np.asarray(Q_f)[:n_items])


def _run_whole(run, args, stats: Optional[dict], capture: ScopeCapture):
    """Dispatch the one program of the routes that do not stream, in the
    leaf span ``als.run``. A ``stats`` call also waits for it there, inside
    the scope capture, and the span is its ``device_s``."""
    import jax

    with capture if stats is not None else contextlib.nullcontext():
        with active_span("als.run") as ran:
            out = run(*args)
            if stats is not None:
                jax.block_until_ready(out)
    if stats is not None:
        stats["device_s"] = ran.seconds
    return out


def _fill_device_stats(stats: dict, capture: ScopeCapture,
                       xla_before: Optional[dict]) -> None:
    """What the scope capture and the compile listener saw of one call,
    as JSON-plain ``stats`` entries (see :func:`train_als`)."""
    seen = capture.result
    if seen is not None:
        summed: dict = {}
        for path, sec in seen["scope_s"].items():
            head, _, rest = path.partition("/")
            key = rest if head in _SIDE_SCOPES and rest else path
            summed[key] = summed.get(key, 0.0) + sec
        stats.update(device_stats(seen))
        stats["device_scope_summed_s"] = summed
        if seen["busy_s"] > 0 and not seen["scope_s"]:
            log.warning(
                "the device trace names no als.* scope: the executables "
                "were probably loaded from a compile cache keyed without op "
                "metadata (see place_compile_cache); retrain with a fresh "
                "JAX_COMPILATION_CACHE_DIR"
            )
    xla = devicewatch.xla_totals()
    if xla is not None and xla_before is not None:
        stats["xla"] = dict(
            xla, in_call={k: xla[k] - xla_before[k] for k in xla})


def train_als(
    ctx: ComputeContext,
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig = ALSConfig(),
    stats: Optional[dict] = None,
) -> ALSFactors:
    """Train ALS over the context's mesh (or a single device).

    Entity counts are padded to mesh multiples; factor rows beyond the true
    counts are dropped on the way out.

    The feed is one on every route: the host sorts the edges by (user,
    item) and ships them as what they are, ``int32`` item ids and
    ``float32`` ratings with the two degree histograms; the device builds
    both blocked layouts (:func:`device_pack`). Whole in one program
    (small inputs), in ``n_stream`` chunks overlapped with iteration 1's
    user half-step (:func:`_run_streamed`), or sharded over a mesh
    (:func:`_run_mesh_compact`). The compiled programs depend on the
    shapes (counts, both degree sequences), never on ids or ratings.

    ``stats``, when a dict, is filled with a per-phase breakdown —
    ``{pack_s, wire_bytes, n_stream, h2d_s, device_s}`` and
    ``solve_impl`` (``{"user", "item"}``: which solver each side's batch
    gets, :func:`_solve_impl`), ``gather_impl`` (which table layout each
    half-step gathers the other side's rows from, :func:`_gather_impl`) and
    ``accum_impl`` (``fused`` / ``xla``: whether the Pallas kernel sums each
    half-step's normal equations, :func:`_accum_impl`) — by
    BLOCKING between the host-pack / host→device / device-compute phases.
    That serialization disables the streamed path's transfer/compute
    overlap, so pass ``stats`` only on profiling runs, not timed ones.

    On a TPU, with no profiler session already running, the device phase
    of a ``stats`` call is also traced and reduced to the program's named
    scopes (:class:`pio_tpu.obs.profile.ScopeCapture`; elsewhere these
    keys are absent): ``device_scope_s`` (``{scope path: device
    self-seconds}``, per side: ``als.item/als.solve/cg``),
    ``device_scope_summed_s`` (the same with ``als.user``/``als.item``
    dropped and the sides summed: ``als.solve/cg``), ``device_unscoped_s``
    (operations outside every scope), ``device_busy_s`` (union of the
    device operations' intervals; the scopes and the unscoped seconds sum
    to it) and ``device_program_s`` (``{jit name: seconds}``). ``xla`` is
    the process's real compiles so far, ``{compiles, compile_s,
    cache_loads, cache_load_s}`` from JAX's monitoring events
    (:func:`pio_tpu.obs.devicewatch.xla_totals`), with ``in_call``, the
    same four over this call: 0 compiles and 0 loads once warm.

    Host work is marked by leaf spans (:func:`pio_tpu.obs.active_span`):
    ``als.sort``, ``als.build`` (the chunks' layout and the trainer's
    programs, looked up or made), the feed's ``stream.*`` (or, on the
    routes that do not stream, ``als.put`` in a ``stats`` call and
    ``als.run``) and ``als.readback``; they tile the call and never nest,
    and no span encloses it. The call itself is numbered on the process
    timeline (:meth:`pio_tpu.obs.tracing.ProcessTimeline.train_call`),
    whose record a ``stats`` call reports as ``stats["process"]``.
    """
    from pio_tpu.obs.tracing import PROCESS

    with PROCESS.train_call(stats):
        return _train_als(ctx, user_idx, item_idx, rating, n_users, n_items,
                          config, stats)


def _train_als(ctx: ComputeContext, user_idx, item_idx, rating, n_users: int,
               n_items: int, config: ALSConfig,
               stats: Optional[dict]) -> ALSFactors:
    import jax
    import jax.numpy as jnp

    if len(user_idx) == 0:
        raise ValueError("ALS needs at least one rating")

    mesh = ctx.mesh
    axis = ctx.batch_axis
    n_shards = mesh.shape[axis] if mesh is not None else 1
    K = config.rank
    n_edges = len(user_idx)
    capture = ScopeCapture("als.")  # entered by a ``stats`` call only
    xla_before = devicewatch.xla_totals()

    user_idx = np.asarray(user_idx, np.int32)
    item_idx = np.asarray(item_idx, np.int32)
    rating = np.asarray(rating, np.float32)

    U_pad = _round_up(max(n_users, 1), n_shards)
    I_pad = _round_up(max(n_items, 1), n_shards)

    # telemetry window: ALS "steps" are the alternating solve iterations
    # (no per-step loss — normal equations); edges count as examples
    trainwatch.begin_algo(
        "als", total_steps=int(config.iterations),
        per_device_bytes=(U_pad + I_pad) * K * 4 // max(1, n_shards),
    )
    edges_recorded = False

    w_user = config.block_width or _auto_width(n_edges, n_users)
    w_item = config.block_width or _auto_width(n_edges, n_items)

    # what solve_block will pick for each side's batch (one device's share
    # of the entities on a mesh); also refuses an unknown solver up front
    solve_impl = {
        side: _solve_impl(str(config.solver), n_pad // n_shards, K,
                          jax.default_backend())
        for side, n_pad in (("user", U_pad), ("item", I_pad))
    }
    trainwatch.set_solve_impl(solve_impl)
    # ...and the layout partial_normal_eq will gather the OTHER side's
    # table from (whole on every device of a mesh)
    mm_itemsize = jnp.dtype(
        _resolve_matmul_dtype(str(config.matmul_dtype))).itemsize
    gather_impl = {
        side: _gather_impl(jax.default_backend(), n_table, K, mm_itemsize)
        for side, n_table in (("user", I_pad), ("item", U_pad))
    }
    trainwatch.set_gather_impl(gather_impl)
    # ...and the way each half-step turns the gathered rows into normal
    # equations
    accum_impl = {
        side: _accum_impl(jax.default_backend(), K, width, mm_itemsize)
        for side, width in (("user", w_user), ("item", w_item))
    }
    trainwatch.set_accum_impl(accum_impl)
    if stats is not None:
        stats["solve_impl"] = solve_impl
        stats["gather_impl"] = gather_impl
        stats["accum_impl"] = accum_impl

    seed = np.uint32(config.seed)

    def _trainer(chunk_user, chunk_item, packed_shapes, mesh_span_lens=None):
        # one call site for the long positional signature so the mesh and
        # single-device branches can never drift apart
        return _build_trainer(
            mesh, axis, config.iterations, float(config.reg),
            bool(config.implicit), float(config.alpha),
            chunk_user, chunk_item,
            _resolve_matmul_dtype(str(config.matmul_dtype)), str(config.solver),
            packed_shapes, K, U_pad, I_pad, mesh_span_lens,
        )

    if n_shards > 1:
        P_f, Q_f = _run_mesh_compact(
            config, mesh, axis, n_shards, user_idx, item_idx, rating,
            n_edges, U_pad, I_pad, w_user, w_item, _trainer, seed, stats,
            capture,
        )
    else:
        # Single-device path: ship the edges sorted by (user, item) and
        # let the jitted trainer build both blocked layouts on device
        # (_build_trainer's ``run_packed``). Above a size threshold the
        # shipment is STREAMED in chunks overlapped with the chunk packs +
        # iteration-1 accumulation (_build_stream_trainer).
        with active_span("als.sort") as sort:
            counts_u, chunk_user, S_u = _counts_layout(
                user_idx, w_user, U_pad, 1, config.blocks_per_chunk)
            counts_i, chunk_item, S_i = _counts_layout(
                item_idx, w_item, I_pad, 1, config.blocks_per_chunk)
            if S_u * w_user >= 2 ** 31 or S_i * w_item >= 2 ** 31:
                raise ValueError(
                    "edge set too large for int32 block addressing; "
                    "use a multi-device mesh"
                )

            counts_u = np.ascontiguousarray(counts_u, np.int64)
            i_sorted, r_sorted = _sort_edges_by_user(
                user_idx, item_idx, rating, n_edges, U_pad, counts_u
            )
        edge_bytes = _EDGE_BYTES * n_edges
        if stats is not None:
            stats["pack_s"] = sort.seconds
            stats["wire_bytes"] = (
                edge_bytes + 4 * (U_pad + I_pad)  # + the two count arrays
            )

        # stream threshold: chunked double-buffered shipment once the
        # edges exceed one chunk (default 30 MiB: 7 chunks at ml-25m);
        # tiny runs keep the single-dispatch path. <= 0 disables
        # streaming entirely.
        n_stream = _n_stream_chunks(edge_bytes, "PIO_TPU_ALS_STREAM_MB")
        if config.iterations < 1:
            # the streamed trainer fuses iteration 1's user half-step into
            # the chunk accumulation, so it can't express "0 iterations";
            # route those runs through the monolithic path
            n_stream = 1
        if stats is not None:
            stats["n_stream"] = max(1, n_stream)
        if n_stream > 1:
            trainwatch.set_stream(True, n_stream)
            edges_recorded = True  # _run_streamed records per chunk
            P_f, Q_f = _run_streamed(
                config, K, U_pad, I_pad, w_user, w_item, S_i, chunk_item,
                counts_u, counts_i, i_sorted, r_sorted, n_stream, seed,
                stats, capture,
            )
        else:
            with active_span("als.build"):
                run = _trainer(
                    chunk_user, chunk_item, (S_u, w_user, S_i, w_item))
                args = (
                    counts_u.astype(np.int32),
                    np.ascontiguousarray(counts_i, np.int32),
                    i_sorted, r_sorted,
                )
            if stats is not None:
                with active_span("als.put") as put:
                    args = tuple(jax.device_put(a) for a in args)
                    jax.block_until_ready(args)
                stats["h2d_s"] = put.seconds
            P_f, Q_f = _run_whole(run, (*args, seed), stats, capture)

    with active_span("als.readback"):
        P_f, Q_f = jax.device_get((P_f, Q_f))
    if stats is not None:
        _fill_device_stats(stats, capture, xla_before)
    trainwatch.record_steps(
        int(config.iterations),
        examples=0 if edges_recorded else n_edges,
    )
    return ALSFactors(
        user_factors=np.asarray(P_f)[:n_users],
        item_factors=np.asarray(Q_f)[:n_items],
    )


def predict_scores(
    user_factors: np.ndarray, item_factors: np.ndarray, user: int
) -> np.ndarray:
    """Scores of every item for one user (host-side; serving keeps factors
    on device — see the recommendation template)."""
    return user_factors[user] @ item_factors.T


def top_n(
    scores: np.ndarray, n: int, exclude: Optional[np.ndarray] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-n item indices + scores, optionally excluding seen items."""
    s = scores.copy()
    if exclude is not None and len(exclude):
        s[exclude] = -np.inf
    n = min(n, len(s))
    idx = np.argpartition(-s, n - 1)[:n] if n < len(s) else np.argsort(-s)
    idx = idx[np.argsort(-s[idx])]
    return idx, s[idx]
