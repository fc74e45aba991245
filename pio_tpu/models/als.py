"""ALS matrix factorization — TPU-native replacement for Spark MLlib ALS.

The reference's Recommendation/Similar-Product templates call
``org.apache.spark.mllib.recommendation.ALS.train`` / ``trainImplicit``
(reference: examples/scala-parallel-recommendation ALSAlgorithm.scala,
UNVERIFIED path; see SURVEY.md). MLlib's ALS block-partitions the rating
matrix into in/out-link blocks and shuffles factor updates between executors
every half-iteration. This module is the TPU-first re-design:

- Host-side, the COO rating list is packed ONCE per orientation (by-user and
  by-item) into **fixed-width dense blocks**: edges sorted by entity, each
  entity's adjacency split into ``[block_width]`` slices, padded slots
  carrying weight 0. Static shapes, no ragged gathers.
- One half-iteration (e.g. the user update) is::

      A_u = Σ_{i ∈ R(u)} q_i q_iᵀ + λI        b_u = Σ_i r_ui q_i
      p_u = A_u⁻¹ b_u

  computed per block as one **batched MXU matmul**
  (``einsum('bwk,bwl->bkl')`` over ``[blocks, width, K]`` gathered factors)
  followed by a ``segment_sum`` of the ~E/width block partials onto entities
  with ``indices_are_sorted=True`` — the scatter is over blocks, not edges,
  so the VPU-hostile part shrinks by the block width while the FLOPs ride
  the systolic array.
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

import dataclasses
import functools
import itertools
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
    #: blocks per scan step — bounds the [chunk, width, K] HBM intermediate
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
    """The C++ packer (pio_tpu/native/als_pack.cpp), or None when no
    toolchain is available (tests cover both paths)."""
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
    # Narrow blocks: padding waste (≈ width/2 per entity) costs real
    # host→device bytes, traded against the extra scatter rows (optimum
    # 16-64 at MovieLens scales on the link it was tuned on; ROADMAP C1).
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


def _make_math(reg: float, implicit: bool, alpha: float,
               matmul_dtype: str, solver: str, rating_wire: str = "f32",
               item_wire: str = "planes"):
    """Shared jittable ALS math: blocked normal-equation accumulation, the
    batched solvers, and the wire decode. Closed over the static config and
    used by BOTH the monolithic trainer (:func:`_build_trainer`) and the
    streamed trainer (:func:`_build_stream_trainer`) so the two paths
    cannot drift apart numerically."""
    import types

    import jax
    import jax.numpy as jnp

    lam = jnp.float32(reg)
    alpha_f = jnp.float32(alpha)
    mm_dtype = jnp.dtype(matmul_dtype)

    @jax.named_scope("als.normal_eq")
    def partial_normal_eq(block_ent, block_other, block_r, factors,
                          n_entities, chunk, varying_axis=None):
        """Blocked scan: Σ w·q qᵀ and Σ rhs·q per entity (one shard)."""
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

        def chunk_step(carry, ch):
            A, b = carry
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
        A0 = jnp.zeros((n_entities, K, K), jnp.float32)
        b0 = jnp.zeros((n_entities, K), jnp.float32)
        if varying_axis is not None:
            # Inside shard_map the carry becomes device-varying after the
            # first chunk; mark the zeros accordingly so scan types match.
            from jax.lax import pcast

            A0 = pcast(A0, (varying_axis,), to="varying")
            b0 = pcast(b0, (varying_axis,), to="varying")
        (A, b), _ = jax.lax.scan(chunk_step, (A0, b0), chunks)
        return A, b

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

    def decode_items(i_lo, i_hi, ovf_idx=None, ovf_val=None, counts=None):
        """Wire → int32 item ids.

        ``planes``: uint16 low plane + optional uint8 high plane.
        ``delta12``: 12-bit gaps over the (user, item)-sorted adjacency —
        ``i_lo`` u8 low byte, ``i_hi`` nibble-packed high 4 bits (2
        edges/byte), plus a sparse overflow list (``delta >> 12`` in
        ``ovf_val``). Ids reconstruct as a segmented cumsum: global
        uint32 cumsum of deltas minus each user's prefix (gathered at
        segment starts from ``counts``) — wraparound-exact because every
        true id < 2^16.
        """
        if item_wire == "delta12":
            E = i_lo.shape[0]
            lo = i_lo.astype(jnp.uint32)
            hi = jnp.stack(
                [i_hi & 0xF, i_hi >> 4], axis=1
            ).reshape(-1)[:E].astype(jnp.uint32)
            delta = lo | (hi << 8)
            delta = delta.at[ovf_idx].add(
                ovf_val.astype(jnp.uint32) << 12
            )
            G = jnp.cumsum(delta, dtype=jnp.uint32)
            cnt = counts.astype(jnp.int32)
            es = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(cnt)]
            )[:-1]
            g_prev = jnp.where(es > 0, G[jnp.maximum(es - 1, 0)], 0)
            offs = jnp.repeat(g_prev, cnt, total_repeat_length=E)
            return (G - offs).astype(jnp.int32)
        i32 = i_lo.astype(jnp.int32)
        if i_hi.shape[0]:
            i32 = i32 | (i_hi.astype(jnp.int32) << 16)
        return i32

    def decode_ratings(r, n_edges):
        """Wire → float32 ratings per the static ``rating_wire`` kind:
        ``u4`` nibble-packed half-star codes (2 edges/byte), ``u8``
        half-star codes, ``f16``/``f32`` raw floats."""
        if rating_wire == "u4":
            lo = (r & 0xF).astype(jnp.float32)
            hi = (r >> 4).astype(jnp.float32)
            pairs = jnp.stack([lo, hi], axis=1).reshape(-1)
            return pairs[:n_edges] * jnp.float32(0.5)
        if rating_wire == "u8":
            return r.astype(jnp.float32) * jnp.float32(0.5)
        return r.astype(jnp.float32)

    return types.SimpleNamespace(
        partial_normal_eq=partial_normal_eq,
        solve_block=solve_block,
        gram_of=gram_of,
        half_local=half_local,
        decode_items=decode_items,
        decode_ratings=decode_ratings,
    )


@functools.lru_cache(maxsize=32)
def _build_trainer(mesh, axis: str, iterations: int, reg: float,
                   implicit: bool, alpha: float,
                   chunk_user: int, chunk_item: int,
                   matmul_dtype: str = "bfloat16", solver: str = "cg",
                   packed_shapes=None, rank: int = 0,
                   U_pad: int = 0, I_pad: int = 0,
                   rating_wire: str = "f32", item_wire: str = "planes",
                   mesh_wire_lens=None):
    """Jitted ALS trainer for one (mesh, static-config) combination.

    The returned function takes the two packed-block layouts + initial
    factors; shapes specialize inside jax.jit's own cache.
    """
    import jax
    import jax.numpy as jnp

    math = _make_math(reg, implicit, alpha, matmul_dtype, solver,
                      rating_wire, item_wire)
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

    # COO variant (single-device): ship the edge list ONCE, pre-sorted by
    # (user, item) on the host (native two-pass sort), and build BOTH
    # blocked layouts on device inside the same jit dispatch. Sorting
    # host-side means the per-edge USER ids never cross the wire at all —
    # one per-user counts array replaces them and the device rebuilds the
    # id column with a single repeat. Items ship as 12-bit adjacency gaps
    # (delta12) or uint16 planes, ratings as 4-bit half-star codes —
    # ~2 B/edge total vs 12 B raw COO (measured 175 MB → ~50 MB at
    # MovieLens-25M); where the host↔device link is the training
    # bottleneck, wire bytes are throughput.
    su, wu, si, wi = packed_shapes

    @jax.jit
    def run_packed(counts_u, counts_i, i_lo, i_hi, ovf_idx, ovf_val, r,
                   seed):
        # wire decode (all static dispatch on the wire kinds):
        #   items: uint16 plane (+uint8 high plane < 2^24), or 12-bit
        #   deltas over the item-sorted adjacency + sparse overflow
        #   ratings: u4 nibble-packed half-star codes (2 edges/byte) when
        #   every code ≤ 15, u8 codes, else fp16/f32 raw
        if mesh is not None and mesh_wire_lens is not None:
            # mesh compact wire: edge arrays arrived SHARDED over the
            # mesh axis (host link crossed once) as one or more CHUNKS
            # per array (PIO_TPU_ALS_STREAM_MB — chunked puts pipeline
            # the per-device transfers); re-replicate each chunk over
            # ICI here, drop its shard-divisibility padding, and splice
            # the stream back together — the decode's cumsum needs the
            # whole stream on every device. Chunking never re-encodes:
            # concat(trimmed chunks) is byte-identical to the
            # monolithic array.
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            lens_lo, lens_hi, lens_r = mesh_wire_lens

            def gather_cat(chunks, lens):
                parts = [
                    jax.lax.with_sharding_constraint(c, repl)[:n]
                    for c, n in zip(chunks, lens)
                ]
                return parts[0] if len(parts) == 1 \
                    else jnp.concatenate(parts)

            i_lo = gather_cat(i_lo, lens_lo)
            i_hi = gather_cat(i_hi, lens_hi)
            r = gather_cat(r, lens_r)
        E = i_lo.shape[0]
        with jax.named_scope("als.decode"):
            i32 = math.decode_items(i_lo, i_hi, ovf_idx, ovf_val, counts_u)
            r32 = math.decode_ratings(r, E)
        with jax.named_scope("als.pack"):
            u32 = jnp.repeat(
                jnp.arange(U_pad, dtype=jnp.int32), counts_u,
                total_repeat_length=E,
            )
        # both degree histograms ride the wire (0.9 MB total) — the
        # on-device bincount is a 25M-edge scatter-add, the host count is
        # a pass the native packer already made
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
                          rating_wire: str, item_wire: str,
                          chunk_spec: tuple):
    """Double-buffered single-device trainer: the wire arrays arrive in
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
    normal equations, packs the item side, and runs the remaining
    iterations. Numerically this differs from the monolithic path only in
    iteration-1 accumulation grouping (float reduction order)."""
    import jax
    import jax.numpy as jnp

    math = _make_math(reg, implicit, alpha, matmul_dtype, solver,
                      rating_wire, item_wire)

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
        def accum(A, b, Q0, local_counts, i_lo, i_hi, ovf_idx, ovf_val, r):
            E_c = i_lo.shape[0]
            # local_counts arrives sliced to the chunk's present-user span
            # [u0_c, pad_c] (ships span·4 B instead of U_pad·4 B per
            # chunk); expand to full length on device
            with jax.named_scope("als.decode"):
                lc = _lc_full(local_counts, u0_c)
                i32 = math.decode_items(i_lo, i_hi, ovf_idx, ovf_val, lc)
                r32 = math.decode_ratings(r, E_c)
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
    def finalize(A, b, Q0, counts_u, counts_i, user_blocks, wire_chunks,
                 lc_slices):
        # full by-user layout = concat of the chunk-local packs (padding
        # aliases each chunk's last user, so ids stay ascending)
        with jax.named_scope("als.pack"):
            by_user = tuple(
                jnp.concatenate([blk[k] for blk in user_blocks])
                for k in range(3)
            )
        # item side needs the full COO: re-decode the (device-resident)
        # wire chunks — elementwise, cheap; the delta item wire is
        # chunk-segmented, so each chunk decodes against its own
        # local-counts span
        with jax.named_scope("als.decode"):
            i32 = jnp.concatenate([
                math.decode_items(
                    lo, hi, ovf_i, ovf_v, _lc_full(lc, chunk_spec[c][2])
                )
                for c, ((lo, hi, ovf_i, ovf_v, _r), lc)
                in enumerate(zip(wire_chunks, lc_slices))
            ])
            r32 = jnp.concatenate(
                [math.decode_ratings(r, lo.shape[0])
                 for lo, hi, ovf_i, ovf_v, r in wire_chunks]
            )
        E = i32.shape[0]
        with jax.named_scope("als.pack"):
            u32 = jnp.repeat(
                jnp.arange(U_pad, dtype=jnp.int32), counts_u,
                total_repeat_length=E,
            )
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

    Layout is bit-identical to the host packers (_pack_blocks /
    native als_pack_fill) — enforced by tests/test_als.py
    ``test_device_pack_matches_host_packers``. ``S``, ``width``, and
    ``n_entities`` are static. ``assume_sorted`` skips the stable argsort
    when the caller guarantees ``ent`` is already ascending (the
    counts-rebuilt user column is sorted by construction).

    Formulated as pure GATHERS: every [S, W] slot computes which edge (if
    any) it holds — block's entity via searchsorted over the block prefix
    sum, position within the entity's adjacency from the block offset —
    and gathers it, composing through the argsort permutation when the
    input isn't pre-sorted. The scatter formulation (`.at[flat].set` over
    the S·W slot space) measured ~3.2 s per 25M edges on v5e where the
    gathers take ~0.3 s: scatters serialize on TPU, gathers tile.

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
        blocks = -(-counts // width)
        zero = jnp.zeros(1, counts.dtype)
        block_start = jnp.concatenate([zero, jnp.cumsum(blocks)])
        edge_start = jnp.concatenate([zero, jnp.cumsum(counts)])

        # per block: owning entity (padding blocks → pad_entity, masked out)
        pad_tgt = (n_entities - 1) if pad_entity is None else pad_entity
        bids = jnp.searchsorted(block_start[1:], jnp.arange(S), side="right")
        block_ent = jnp.minimum(bids, pad_tgt).astype(jnp.int32)

        # per slot: position within the entity's adjacency, then edge index
        blk_in_ent = jnp.arange(S) - block_start[block_ent]  # [S]
        pos = blk_in_ent[:, None] * width + jnp.arange(width)[None, :]
        valid = pos < counts[block_ent][:, None]  # [S, W]
        src = jnp.where(valid, edge_start[block_ent][:, None] + pos, 0)
        if not assume_sorted:
            # compose through the stable sort permutation: one fused gather
            src = jnp.argsort(ent, stable=True)[src]
        block_other = jnp.where(valid, oth[src], jnp.int32(-1))
        block_rating = jnp.where(valid, rat[src], jnp.float32(0.0))
        return block_ent, block_other, block_rating


def _run_streamed(config: "ALSConfig", rank: int, U_pad: int, I_pad: int,
                  w_user: int, w_item: int, S_item: int, chunk_item: int,
                  counts_u: np.ndarray, counts_i: np.ndarray,
                  i_sorted: np.ndarray, r_ship: np.ndarray,
                  rating_wire: str, item_wire: str,
                  n_stream: int, seed, stats: Optional[dict],
                  capture: ScopeCapture):
    """Dispatch the double-buffered single-device training run.

    Slices the (user, item)-sorted edges into ``n_stream`` spans, encodes
    each span's item wire CHUNK-LOCALLY (the delta wire restarts each
    user's gap chain at the chunk boundary — a straddling user's first
    in-chunk edge ships its absolute id, so chunks decode independently
    against their local counts), queues every span's ``device_put`` up
    front (async — they drain on the transfer stream in order), then
    chains the per-chunk accumulate programs: chunk k's pack +
    normal-equation accumulation executes while chunk k+1 is still
    crossing the link. With ``stats`` the phases are serialized (block
    between h2d and compute) to measure them — overlap off. Chunk
    boundaries are even so nibble-packed planes split on byte boundaries.
    """
    import jax

    E = i_sorted.shape[0]
    edge_start = np.zeros(U_pad + 1, np.int64)
    np.cumsum(counts_u, out=edge_start[1:])
    bounds = [min(E, (E * c // n_stream) // 2 * 2)
              for c in range(n_stream)] + [E]
    spans = [(bounds[c], bounds[c + 1]) for c in range(n_stream)
             if bounds[c + 1] > bounds[c]]

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
        chunk_stream, chunk_item, rating_wire, item_wire,
        tuple(tuple(s) for s in chunk_spec),
    )

    def _encode_chunk(e0, e1, lc):
        if item_wire == "delta12":
            d_lo, d_hi, ovf_idx, ovf_val, _ = _encode_items_delta(
                i_sorted[e0:e1], lc
            )
        else:
            d_lo, d_hi = _planes(i_sorted[e0:e1], I_pad)
            ovf_idx = np.zeros(0, np.int32)
            ovf_val = np.zeros(0, np.uint8)
        r_c = (r_ship[e0 // 2:(e1 + 1) // 2] if rating_wire == "u4"
               else r_ship[e0:e1])
        return d_lo, d_hi, ovf_idx, ovf_val, r_c

    # the shared streamed-feed executor (parallel/stream.py) runs the
    # encode → queued-put → chained-dispatch loop; ALS retains the wire
    # chunks (finalize re-decodes them for the item side) so it rides
    # the queue-ahead mode (lookahead=0), and maps the executor's
    # encode phase onto its historical ``pack_s`` stats key
    from pio_tpu.parallel.stream import stream_feed

    def encode(chunk):
        (e0, e1), lc = chunk
        return (*_encode_chunk(e0, e1, lc), lc)

    def put(host, _idx):
        *wire, lc = host
        return tuple(jax.device_put(a) for a in wire), jax.device_put(lc)

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
        wire, lc = dev
        A, b, blk = accums[c](A, b, Q0, lc, *wire)
        # chunk progress for the telemetry plane: ALS has no per-step
        # loss (normal equations), so progress is edges accumulated
        e0, e1 = spans[c]
        trainwatch.record_steps(0, examples=e1 - e0)
        return Q0, A, b, user_blocks + (blk,)

    def fin(carry, devs):
        Q0, A, b, user_blocks = carry
        return finalize(A, b, Q0, extra["cu"], extra["ci"], user_blocks,
                        tuple(d[0] for d in devs),
                        tuple(d[1] for d in devs))

    return stream_feed(
        list(zip(spans, local_slices)),
        encode=encode, put=put, put_extra=put_extra,
        init_carry=init_carry, dispatch=dispatch, finalize=fin,
        stats=stats, encode_stat_key="pack_s", device_phase=capture,
    )


def _nibble_pack(codes: np.ndarray) -> np.ndarray:
    """Pack uint8 codes ≤ 15 two-per-byte: byte k = edge 2k (low nibble)
    | edge 2k+1 (high nibble). Mirrors ``decode_ratings('u4')``."""
    n = len(codes)
    if n % 2:
        codes = np.concatenate([codes, np.zeros(1, np.uint8)])
    pair = codes.reshape(-1, 2)
    return (pair[:, 0] | (pair[:, 1] << 4)).astype(np.uint8)


def _planes(idx: np.ndarray, n_pad: int):
    """(low, high) item wire planes: uint16 alone below 2^16, uint16 +
    uint8 high plane below 2^24 (3 B/id instead of 4), raw int32 beyond.
    The empty high plane means "unused"."""
    none = np.zeros(0, np.uint8)
    if n_pad < 65536:
        return idx.astype(np.uint16), none
    if n_pad < (1 << 24):
        return (
            (idx & 0xFFFF).astype(np.uint16),
            (idx >> 16).astype(np.uint8),
        )
    return idx, none


def _u8p(a: np.ndarray):
    import ctypes

    return _ptr(a, np.uint8, ctypes.c_uint8)


def _np_deltas(ids: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-edge gap to the previous same-segment id (first edge of each
    segment gaps from 0). Numpy reference for the native delta encoder."""
    E = len(ids)
    cnt = counts[counts > 0].astype(np.int64)
    starts = np.zeros(len(cnt), np.int64)
    np.cumsum(cnt[:-1], out=starts[1:])
    prev = np.empty(E, np.int32)
    prev[0] = 0
    prev[1:] = ids[:-1]
    prev[starts] = 0
    return ids.astype(np.int32) - prev


def _delta_wire_size(
    ids: np.ndarray, counts: np.ndarray
) -> Optional[Tuple[int, int]]:
    """``(wire_bytes, n_ovf)`` for the delta12 encoding WITHOUT
    materializing it (one count pass), or None when the encoding is
    inapplicable (ids not segment-sorted, or a gap ≥ 2^16)."""
    E = len(ids)
    if E == 0:
        return 0, 0
    native = _native_packer()
    if native is not None:
        cnt64 = np.ascontiguousarray(counts, np.int64)
        n_ovf = int(native.als_delta_count(
            _i32p(ids), _i64p(cnt64), len(cnt64)
        ))
        if n_ovf < 0:
            return None
    else:
        delta = _np_deltas(ids, counts)
        if len(delta) and (
            int(delta.min()) < 0 or int(delta.max()) >= 65536
        ):
            return None
        n_ovf = int((delta > 0xFFF).sum())
    return E + (E + 1) // 2 + 5 * n_ovf, n_ovf


def _encode_items_delta(ids: np.ndarray, counts: np.ndarray,
                        n_ovf: Optional[int] = None):
    """12-bit delta item wire over a (user, item)-sorted edge slice.

    ``counts`` segments ``ids`` into per-user runs (zero entries allowed;
    nonzero entries must sum to ``len(ids)``). Each edge ships the gap to
    the previous item of the same user (the first edge of a run ships its
    absolute id) as u8 low byte + nibble-packed high 4 bits — 1.5 B/edge
    — plus a sparse overflow list carrying ``delta >> 12`` for the rare
    gaps ≥ 4096. Exact for any id space < 2^16 (see
    ``_make_math.decode_items``). Native single-pass encoder when the
    toolchain is available; the numpy path is the format's reference.
    Returns ``(d_lo, d_hi, ovf_idx i32, ovf_val u8, wire_bytes)``.
    """
    E = len(ids)
    if E == 0:
        z8 = np.zeros(0, np.uint8)
        return z8, z8, np.zeros(0, np.int32), z8, 0
    native = _native_packer()
    if native is not None:
        cnt64 = np.ascontiguousarray(counts, np.int64)
        if n_ovf is None:  # caller may pass _delta_wire_size's count
            n_ovf = int(native.als_delta_count(
                _i32p(ids), _i64p(cnt64), len(cnt64)
            ))
        if n_ovf >= 0:
            d_lo = np.empty(E, np.uint8)
            d_hi = np.zeros((E + 1) // 2, np.uint8)
            ovf_idx = np.empty(n_ovf, np.int32)
            ovf_val = np.empty(n_ovf, np.uint8)
            native.als_delta_fill(
                _i32p(ids), _i64p(cnt64), len(cnt64), E,
                _u8p(d_lo), _u8p(d_hi), _i32p(ovf_idx), _u8p(ovf_val),
            )
            bytes_ = (d_lo.nbytes + d_hi.nbytes + ovf_idx.nbytes
                      + ovf_val.nbytes)
            return d_lo, d_hi, ovf_idx, ovf_val, bytes_
    delta = _np_deltas(ids, counts)
    ovf = np.nonzero(delta > 0xFFF)[0]
    d_lo = (delta & 0xFF).astype(np.uint8)
    d_hi = _nibble_pack(((delta >> 8) & 0xF).astype(np.uint8))
    ovf_idx = ovf.astype(np.int32)
    ovf_val = (delta[ovf] >> 12).astype(np.uint8)
    bytes_ = d_lo.nbytes + d_hi.nbytes + ovf_idx.nbytes + ovf_val.nbytes
    return d_lo, d_hi, ovf_idx, ovf_val, bytes_


def _encode_ratings(r_sorted: np.ndarray) -> Tuple[np.ndarray, str]:
    """Choose the densest lossless rating wire format.

    Returns ``(wire array, kind)`` where kind ∈ {u4, u8, f16, f32}:
    nibble-packed half-star codes (2 edges/byte — MovieLens's 0.5..5.0
    grid and implicit r=1 both qualify), byte codes to 127.5 stars, fp16
    when that cast is exact, else raw f32. The decode lives in
    ``_make_math.decode_ratings``; every kind round-trips exactly. The
    grid check + byte coding is one fused native pass when available
    (the numpy pipeline was ~10% of the whole host pack)."""
    native = _native_packer()
    if native is not None and r_sorted.size:
        codes = np.empty(r_sorted.size, np.uint8)
        mx = native.als_rating_codes(
            _f32p(r_sorted), r_sorted.size, _u8p(codes)
        )
        if mx >= 0:
            if mx <= 15:
                return _nibble_pack(codes), "u4"
            return codes, "u8"
    else:
        r2 = r_sorted * np.float32(2.0)
        if r2.size and np.all(r2 == np.round(r2)) \
                and float(r2.min()) >= 0.0:
            if float(r2.max()) <= 15.0:
                return _nibble_pack(r2.astype(np.uint8)), "u4"
            if float(r2.max()) <= 255.0:
                return r2.astype(np.uint8), "u8"
    r16 = r_sorted.astype(np.float16)
    if np.array_equal(r16.astype(np.float32), r_sorted):
        return r16, "f16"
    return r_sorted, "f32"


def _sort_edges_by_user(user_idx, item_idx, rating, n_edges, U_pad,
                        counts_u):
    """(user, item)-sorted item/rating columns: native two-pass sort
    (counting sort by user + per-adjacency stable item sort) with a numpy
    lexsort fallback. Item-sorted adjacencies are what make the delta
    item wire dense AND improve factor-gather locality on device; ALS
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
            # wholesale. Training is order-invariant so this is safe,
            # but the delta wire then won't apply (negative gaps →
            # planes fallback) — say so instead of silently diverging
            # from the numpy lexsort path.
            log.warning(
                "within-user item sort skipped (an entity exceeds "
                "2^24 edges); item wire falls back to planes"
            )
    else:
        order = np.lexsort((item_idx, user_idx))
        i_sorted = np.ascontiguousarray(item_idx[order])
        r_sorted = np.ascontiguousarray(rating[order])
    return i_sorted, r_sorted


def _choose_item_wire(i_sorted, counts_u, I_pad, n_edges):
    """Pick the denser lossless item wire: uint16/24/32 planes vs 12-bit
    deltas over the (user, item)-sorted adjacency, sized by a count-only
    pass (PIO_TPU_ALS_ITEM_WIRE overrides: auto/delta12/planes).
    Returns (item_wire, n_ovf, edge_item_bytes)."""
    item_env = knobs.knob_str("PIO_TPU_ALS_ITEM_WIRE")
    plane_width = 2 if I_pad < 65536 else (3 if I_pad < 2 ** 24 else 4)
    n_ovf = None
    delta_bytes = None
    if I_pad < 65536 and item_env in ("auto", "delta12"):
        sized = _delta_wire_size(i_sorted, counts_u)
        if sized is not None:
            delta_bytes, n_ovf = sized
            if item_env == "delta12" or delta_bytes < 2 * n_edges:
                return "delta12", n_ovf, delta_bytes
    return "planes", n_ovf, plane_width * n_edges


def _run_mesh_compact(config, mesh, axis, n_shards, user_idx, item_idx,
                      rating, n_edges, U_pad, I_pad, w_user, w_item,
                      counts_layout, trainer, seed, stats, capture):
    """Multi-shard training over the COMPACT edge wire.

    The host link (PCIe on a TPU VM) is the slow hop and ICI the fast
    one, so the wire crosses the host link exactly once:
    every edge-indexed array ships SHARDED over the mesh axis (each
    device receives 1/n of ~2 B/edge), and the jitted trainer
    re-replicates them with an all-gather that rides ICI before the
    on-device dual blocked-layout construction (``device_pack``). The
    constructed block arrays come out sharded by block index — the
    layout the shard_map half-steps consume — so block CONTENT never
    needed host-side shard routing at all (the round-3 design note in
    docs/parallelism.md). Bit-identical to the host-packed blocked-f32
    path by the device_pack parity guarantee."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    t0 = monotonic_s()
    counts_u, chunk_user, S_u = counts_layout(user_idx, w_user, U_pad)
    counts_i, chunk_item, S_i = counts_layout(item_idx, w_item, I_pad)
    if S_u * w_user >= 2 ** 31 or S_i * w_item >= 2 ** 31:
        raise ValueError(
            "edge set too large for int32 block addressing; raise "
            "block width or shard the edge set first"
        )
    counts_u = np.ascontiguousarray(counts_u, np.int64)
    i_sorted, r_sorted = _sort_edges_by_user(
        user_idx, item_idx, rating, n_edges, U_pad, counts_u
    )
    r_ship, rating_wire = _encode_ratings(r_sorted)
    item_wire, n_ovf, item_bytes = _choose_item_wire(
        i_sorted, counts_u, I_pad, n_edges
    )
    if item_wire == "delta12":
        i_ship, i_hi, ovf_idx, ovf_val, _ = _encode_items_delta(
            i_sorted, counts_u, n_ovf=n_ovf
        )
    else:
        i_ship, i_hi = _planes(i_sorted, I_pad)
        ovf_idx = np.zeros(0, np.int32)
        ovf_val = np.zeros(0, np.uint8)
    # chunked shipment (the single-device stream discipline applied to
    # the sharded puts): slice each ENCODED array into ≤8 spans so the
    # per-device transfers of span k+1 pipeline behind span k instead of
    # one monolithic put per array serializing the whole h2d. Slicing
    # happens after encoding, so the wire BYTES are unchanged — the
    # trainer splices the trimmed spans back together before decoding.
    edge_bytes = item_bytes + r_ship.nbytes
    n_stream = _n_stream_chunks(edge_bytes, "PIO_TPU_ALS_STREAM_MB")

    def spans_of(a):
        if n_stream == 1 or len(a) == 0:
            return [a]
        bounds = [len(a) * c // n_stream for c in range(n_stream + 1)]
        return [a[s:e] for s, e in zip(bounds[:-1], bounds[1:]) if e > s]

    lo_spans = spans_of(i_ship)
    hi_spans = spans_of(i_hi)
    r_spans = spans_of(r_ship)

    if stats is not None:
        stats["pack_s"] = monotonic_s() - t0
        stats["wire_bytes"] = (
            item_bytes + r_ship.nbytes + 4 * (U_pad + I_pad)
        )
        stats["encoding"] = f"{rating_wire}+{item_wire}"
        stats["n_stream"] = max(len(lo_spans), len(r_spans))

    run = trainer(
        chunk_user, chunk_item, (S_u, w_user, S_i, w_item),
        rating_wire, item_wire,
        mesh_wire_lens=(
            tuple(len(s) for s in lo_spans),
            tuple(len(s) for s in hi_spans),
            tuple(len(s) for s in r_spans),
        ),
    )
    shard1 = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def pad_to_shards(a):
        p = (-len(a)) % n_shards
        return np.concatenate([a, np.zeros(p, a.dtype)]) if p else a

    t0 = monotonic_s()
    small = (
        jax.device_put(counts_u.astype(np.int32), repl),
        jax.device_put(np.ascontiguousarray(counts_i, np.int32), repl),
        jax.device_put(ovf_idx, repl),
        jax.device_put(ovf_val, repl),
    )
    # interleave the arrays' spans so early spans of every array are in
    # flight together; per-span timings land in stats on profiled runs
    lo_dev: list = []
    hi_dev: list = []
    r_dev: list = []
    chunk_ts = []
    for parts in itertools.zip_longest(lo_spans, hi_spans, r_spans):
        tc = monotonic_s()
        group = []
        for part, dev in zip(parts, (lo_dev, hi_dev, r_dev)):
            if part is not None:
                dev.append(jax.device_put(pad_to_shards(part), shard1))
                group.append(dev[-1])
        if stats is not None:
            jax.block_until_ready(group)
            chunk_ts.append(round(monotonic_s() - tc, 3))
    args = (*small[:2], tuple(lo_dev), tuple(hi_dev), *small[2:],
            tuple(r_dev))
    if stats is not None:
        jax.block_until_ready(args)
        stats["h2d_s"] = monotonic_s() - t0
        stats["h2d_chunk_s"] = chunk_ts
        P_f, Q_f = _profiled_run(run, (*args, seed), stats, capture)
    else:
        P_f, Q_f = run(*args, seed)
    return P_f, Q_f


def _profiled_run(run, args, stats: dict, capture: ScopeCapture):
    """The device phase of a ``stats`` call: dispatch, block, time it as
    ``device_s``, all inside the scope capture."""
    import jax

    with capture:
        t0 = monotonic_s()
        out = run(*args)
        jax.block_until_ready(out)
        stats["device_s"] = monotonic_s() - t0
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

    ``stats``, when a dict, is filled with a per-phase breakdown —
    ``{pack_s, wire_bytes, encoding, n_stream, h2d_s, device_s}`` and
    ``solve_impl`` (``{"user", "item"}``: which solver each side's batch
    gets, :func:`_solve_impl`) and ``gather_impl`` (which table layout each
    half-step gathers the other side's rows from, :func:`_gather_impl`) — by
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
    ``als.sort``, the feed's ``stream.*`` and ``als.readback``; they tile
    and never nest, and no span encloses the call.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

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
    if stats is not None:
        stats["solve_impl"] = solve_impl
        stats["gather_impl"] = gather_impl

    def _counts_layout(ent, width, n_entities):
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
        chunk = min(config.blocks_per_chunk, _round_up(per_shard, 8))
        pad_to = n_shards * chunk
        # single home for the padded block count — the numpy packer is
        # handed S directly so both paths cannot drift apart
        S = max(pad_to, _round_up(max(n_blocks, 1), pad_to))
        return counts, chunk, S

    def _layout(ent, other, rat, width, n_entities):
        """Host-packed blocks (the multi-shard path; single-device packs
        on device instead — see _build_trainer's COO variant)."""
        native = _native_packer()
        counts, chunk, S = _counts_layout(ent, width, n_entities)
        if native is not None:
            block_ent = np.empty(S, np.int32)
            block_other = np.empty(S * width, np.int32)
            block_rating = np.empty(S * width, np.float32)
            native.als_pack_fill(
                _i32p(ent), _i32p(other), _f32p(rat), len(ent),
                n_entities, width, _i64p(counts), S,
                _i32p(block_ent), _i32p(block_other), _f32p(block_rating),
            )
            blocks = (
                block_ent,
                block_other.reshape(S, width),
                block_rating.reshape(S, width),
            )
        else:
            blocks = _pack_blocks(
                ent, other, rat, n_entities, width, S, counts=counts
            )
            assert blocks[0].shape[0] == S
        return blocks, chunk

    seed = np.uint32(config.seed)

    def _trainer(chunk_user, chunk_item, packed_shapes, rating_wire="f32",
                 item_wire="planes", mesh_wire_lens=None):
        # one call site for the long positional signature so the mesh and
        # single-device branches can never drift apart
        return _build_trainer(
            mesh, axis, config.iterations, float(config.reg),
            bool(config.implicit), float(config.alpha),
            chunk_user, chunk_item,
            _resolve_matmul_dtype(str(config.matmul_dtype)), str(config.solver),
            packed_shapes, K, U_pad, I_pad, rating_wire, item_wire,
            mesh_wire_lens,
        )

    if n_shards > 1:
        # wire policy: "compact" (default) ships the single-device delta/
        # plane+code wire — each device receives 1/n of it over the host
        # link (PCIe/DCN, the slow hop) and the jitted trainer re-
        # replicates it over ICI (fast) before the on-device dual blocked-
        # layout construction, whose sharded outputs feed the shard_map
        # half-steps. "blocked" keeps the host-packed f32 block shipment
        # (~16× the bytes/edge) — retained as the equality reference.
        mesh_wire = knobs.knob_str("PIO_TPU_ALS_MESH_WIRE")
        if mesh_wire in ("auto", "compact"):
            P_f, Q_f = _run_mesh_compact(
                config, mesh, axis, n_shards, user_idx, item_idx, rating,
                n_edges, U_pad, I_pad, w_user, w_item, _counts_layout,
                _trainer, seed, stats, capture,
            )
        else:
            t0 = monotonic_s()
            # canonical (user, item) edge order BEFORE packing: block
            # content becomes input-order-invariant and bit-identical to
            # the compact path's on-device construction (which composes
            # through a stable sort of the same canonical stream)
            cu0 = np.ascontiguousarray(
                np.bincount(user_idx, minlength=U_pad), np.int64
            )
            i_srt, r_srt = _sort_edges_by_user(
                user_idx, item_idx, rating, n_edges, U_pad, cu0
            )
            u_srt = np.repeat(
                np.arange(U_pad, dtype=np.int32), cu0
            )
            by_user, chunk_user = _layout(
                u_srt, i_srt, r_srt, w_user, U_pad
            )
            by_item, chunk_item = _layout(
                i_srt, u_srt, r_srt, w_item, I_pad
            )
            run = _trainer(chunk_user, chunk_item, None)
            blk = NamedSharding(mesh, P(axis))
            blk2 = NamedSharding(mesh, P(axis, None))
            put_blocks = lambda t: (
                jax.device_put(t[0], blk),
                jax.device_put(t[1], blk2),
                jax.device_put(t[2], blk2),
            )
            if stats is not None:
                stats["pack_s"] = monotonic_s() - t0
                stats["wire_bytes"] = sum(
                    a.nbytes for t in (by_user, by_item) for a in t
                )
                stats["encoding"] = "blocked-f32"
                stats["n_stream"] = 1
                t0 = monotonic_s()
                u_dev, i_dev = put_blocks(by_user), put_blocks(by_item)
                jax.block_until_ready((u_dev, i_dev))
                stats["h2d_s"] = monotonic_s() - t0
                P_f, Q_f = _profiled_run(
                    run, (u_dev, i_dev, seed), stats, capture)
            else:
                P_f, Q_f = run(
                    put_blocks(by_user), put_blocks(by_item), seed
                )
    else:
        # Single-device path: ship the COO edges pre-sorted by user (see
        # _build_trainer's COO variant for the wire format) and let the
        # jitted trainer build both blocked layouts on device, which
        # matters where the device link is slow or shares a core with
        # the process. Above a wire-size threshold the
        # shipment is STREAMED in chunks overlapped with the chunk packs +
        # iteration-1 accumulation (_build_stream_trainer).
        t0 = monotonic_s()
        with active_span("als.sort"):
            counts_u, chunk_user, S_u = _counts_layout(
                user_idx, w_user, U_pad)
            counts_i, chunk_item, S_i = _counts_layout(
                item_idx, w_item, I_pad)
            if S_u * w_user >= 2 ** 31 or S_i * w_item >= 2 ** 31:
                raise ValueError(
                    "edge set too large for int32 block addressing; "
                    "use a multi-device mesh"
                )

            counts_u = np.ascontiguousarray(counts_u, np.int64)
            i_sorted, r_sorted = _sort_edges_by_user(
                user_idx, item_idx, rating, n_edges, U_pad, counts_u
            )
            r_ship, rating_wire = _encode_ratings(r_sorted)
            # item wire sized by a count-only pass so nothing is
            # materialized before the stream/monolithic split
            item_wire, n_ovf, item_bytes = _choose_item_wire(
                i_sorted, counts_u, I_pad, n_edges
            )
        use_delta = item_wire == "delta12"
        edge_bytes = item_bytes + r_ship.nbytes
        if stats is not None:
            stats["pack_s"] = monotonic_s() - t0
            stats["wire_bytes"] = (
                edge_bytes + 4 * (U_pad + I_pad)  # + the two count arrays
            )
            stats["encoding"] = f"{rating_wire}+{item_wire}"

        # stream threshold: chunked double-buffered shipment once the edge
        # wire exceeds ~one chunk (default 8 MiB); tiny runs keep the
        # single-dispatch path. <= 0 disables streaming entirely.
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
                counts_u, counts_i, i_sorted, r_ship, rating_wire,
                item_wire, n_stream, seed, stats, capture,
            )
        else:
            if use_delta:
                i_ship, i_hi, ovf_idx, ovf_val, _ = _encode_items_delta(
                    i_sorted, counts_u, n_ovf=n_ovf
                )
            else:
                i_ship, i_hi = _planes(i_sorted, I_pad)
                ovf_idx = np.zeros(0, np.int32)
                ovf_val = np.zeros(0, np.uint8)
            run = _trainer(
                chunk_user, chunk_item, (S_u, w_user, S_i, w_item),
                rating_wire, item_wire,
            )
            args = (
                counts_u.astype(np.int32),
                np.ascontiguousarray(counts_i, np.int32),
                i_ship, i_hi, ovf_idx, ovf_val, r_ship,
            )
            if stats is not None:
                t0 = monotonic_s()
                args = tuple(jax.device_put(a) for a in args)
                jax.block_until_ready(args)
                stats["h2d_s"] = monotonic_s() - t0
                P_f, Q_f = _profiled_run(run, (*args, seed), stats, capture)
            else:
                P_f, Q_f = run(*args, seed)

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
