"""Device-resident factor scoring for serving — SURVEY.md §7 hard part (d).

The reference's query server scores on the driver JVM per request
(``CreateServer`` → ``predictBase``, reference core/.../workflow/
CreateServer.scala — UNVERIFIED path; see SURVEY.md). The TPU-first serving
story instead uploads the factor/embedding matrices to the accelerator ONCE
at deploy (the ``Engine.prepareDeploy`` analog — see
``Algorithm.prepare_for_serving``) and jits score + top-k, so each request
is one device dispatch of a ``[B, K] @ [K, N]`` MXU matmul and only integer
codes + top-N results cross the host link.

**Adaptive routing.** What dominates per-request cost is the host↔device
round trip, not the math. The scorer therefore probes BOTH costs once at
deploy — one tiny transfer round trip, one host-scored row — and routes
each call by batch size: ``B ≥ RTT / host_row_cost`` goes to the
accelerator (the RTT amortizes across the batch), smaller batches use the
host mirror of the factors (which exists anyway — it is the serialized
model state). ``PIO_TPU_SERVE_DEVICE=1|0`` forces device/host for all
calls. Which route answered is never implicit: every public call bumps a
per-route counter, and :meth:`DeviceTopNScorer.route_info` reports the
counts with the probe's measurements (``/stats.json`` ``topnScorers``).

Shape discipline: jit specializes per shape, so both the batch dimension
and the top-k width are bucketed to powers of two (a handful of
compilations total, each cached by jax). Padding rows use code 0 and are
sliced off on the way out; excluded item slots use the sentinel index
``n_cols``, which ``.at[].set(mode="drop")`` discards as out-of-bounds.
"""

from __future__ import annotations

import ctypes
import functools
import threading
from pio_tpu.utils import knobs
from pio_tpu.obs import monotonic_s
from typing import Optional, Tuple

import numpy as np

#: largest per-dispatch batch bucket; bigger batches loop in chunks of this
_MAX_BATCH_BUCKET = 512

#: ctypes pointer types for the native host scorer (hoisted off the
#: per-request path)
_F32P = ctypes.POINTER(ctypes.c_float)
_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)


def _bucket(n: int, cap: int) -> int:
    """Smallest power of two ≥ n, capped."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


@functools.lru_cache(maxsize=None)
def _topn_fn(k: int, with_exclude: bool, n_valid: Optional[int] = None):
    """Jitted [B,K]@[K,N] + top-k (cached per static k / exclusion arity).

    ``n_valid``: static count of real columns when the col table is padded
    to a mesh multiple — pad columns are masked to -inf before top-k so a
    zero-vector pad row can never outrank a real negative score.
    """
    import jax
    import jax.numpy as jnp

    def _mask_pad(scores):
        if n_valid is None:
            return scores
        keep = jnp.arange(scores.shape[1]) < n_valid
        return jnp.where(keep[None, :], scores, -jnp.inf)

    if with_exclude:

        def fn(rows, cols, codes, excl):
            q = rows[codes]
            scores = jnp.einsum(
                "bk,nk->bn", q, cols, preferred_element_type=jnp.float32
            )
            scores = _mask_pad(scores)
            b = jnp.arange(codes.shape[0])[:, None]
            # sentinel index n_cols is out of bounds → dropped, not wrapped
            scores = scores.at[b, excl].set(-jnp.inf, mode="drop")
            return jax.lax.top_k(scores, k)

    else:

        def fn(rows, cols, codes):
            q = rows[codes]
            scores = jnp.einsum(
                "bk,nk->bn", q, cols, preferred_element_type=jnp.float32
            )
            return jax.lax.top_k(_mask_pad(scores), k)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _scores_fn():
    import jax
    import jax.numpy as jnp

    def fn(rows, cols, codes):
        return jnp.einsum(
            "bk,nk->bn", rows[codes], cols,
            preferred_element_type=jnp.float32,
        )

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _pairs_fn():
    import jax
    import jax.numpy as jnp

    def fn(rows, cols, rcodes, ccodes):
        return jnp.einsum(
            "bk,bk->b", rows[rcodes], cols[ccodes],
            preferred_element_type=jnp.float32,
        )

    return jax.jit(fn)


def _env_mode() -> str:
    env = knobs.knob_str("PIO_TPU_SERVE_DEVICE").lower()
    if env in ("1", "true", "yes", "device"):
        return "device"
    if env in ("0", "false", "no", "host"):
        return "host"
    return "auto"


@functools.lru_cache(maxsize=1)
def _probe_link_rtt_s() -> float:
    """One-time cost of a minimal host→device→host round trip (measures the
    link, not the math — 4 bytes each way)."""
    import jax

    x = np.ones(1, np.float32)
    jax.device_get(jax.device_put(x))  # warm the path
    best = float("inf")
    for _ in range(3):
        t0 = monotonic_s()
        jax.device_get(jax.device_put(x))
        best = min(best, monotonic_s() - t0)
    return best


class DeviceTopNScorer:
    """Row-factors × col-factors top-N scorer, resident on the accelerator.

    ``rows`` is the query-side table (user factors / user tower output),
    ``cols`` the scored-item table. All methods accept/return host numpy —
    only integer codes and the top-N results cross the link.

    ``prefer_device``: True/False pins every call to the device/host path;
    None consults ``PIO_TPU_SERVE_DEVICE`` and defaults to adaptive
    batch-size routing (see module docstring). ``link_rtt_s`` overrides the
    probed link round-trip (tests inject synthetic link speeds).

    ``mesh``: a multi-device mesh to shard the factor tables over. Both
    tables row-shard on the mesh's entity axis (``data``), padded up to a
    shard multiple — each chip holds 1/n of the model, and the jitted
    score + top-k runs GSPMD-sharded with stable input shardings (no
    steady-state retraces). The per-device footprint is enforced against
    ``PIO_TPU_DEVICE_BUDGET_BYTES`` when set.
    """

    def __init__(
        self,
        row_factors: np.ndarray,
        col_factors: np.ndarray,
        prefer_device: Optional[bool] = None,
        warmup: bool = False,
        link_rtt_s: Optional[float] = None,
        mesh=None,
    ):
        rows = np.ascontiguousarray(row_factors, dtype=np.float32)
        cols = np.ascontiguousarray(col_factors, dtype=np.float32)
        if rows.shape[1] != cols.shape[1]:
            raise ValueError(
                f"rank mismatch: rows {rows.shape} vs cols {cols.shape}"
            )
        self.n_rows, self.rank = rows.shape
        self.n_cols = cols.shape[0]
        self._rows_np = rows
        self._cols_np = cols
        self._rows_dev = self._cols_dev = None
        self._cols_t = None  # lazy transposed mirror (native host path)
        if mesh is not None and int(np.prod(mesh.devices.shape)) <= 1:
            mesh = None  # a 1-chip mesh is the plain device path
        self._mesh = mesh
        self._ncols_pad = self.n_cols
        #: calls answered per route — the serving path can answer every
        #: query from the host mirror, so which one did is on the record
        self._route_lock = threading.Lock()
        self._route_counts = {"device": 0, "host": 0}
        #: what the adaptive probe measured (None: forced mode, no probe)
        self.link_rtt_s: Optional[float] = None
        self.host_row_s: Optional[float] = None
        self.mode = "host"

        if self.n_rows == 0 or self.n_cols == 0:
            # degenerate factor tables cannot be probed (the host-row
            # probe would index row 0) and have nothing to score on the
            # accelerator; every call takes the host path, whose public
            # methods handle the empty dimensions explicitly
            self.min_device_batch = float("inf")
            self.min_pair_batch = float("inf")
            return

        if prefer_device is True:
            mode = "device"
        elif prefer_device is False:
            mode = "host"
        else:
            mode = _env_mode()
        self.mode = mode
        if mode == "host":
            self.min_device_batch = float("inf")
            self.min_pair_batch = float("inf")
        else:
            import jax

            from pio_tpu.parallel.partition import assert_device_budget

            # the single upload of the deploy lifetime
            if self._mesh is not None:
                n_dev = int(np.prod(self._mesh.devices.shape))
                assert_device_budget(
                    rows.nbytes + cols.nbytes, n_dev, "topn mesh placement"
                )
                self._rows_dev, self._cols_dev, self._ncols_pad = (
                    self._place_sharded(rows, cols)
                )
            else:
                assert_device_budget(
                    rows.nbytes + cols.nbytes, 1, "topn device placement"
                )
                self._rows_dev = jax.device_put(rows)
                self._cols_dev = jax.device_put(cols)
            if mode == "device":
                self.min_device_batch = 1
                self.min_pair_batch = 1
            else:  # adaptive: break-even batch sizes from measured costs.
                # A pair query is a rank-length dot (~n_cols× cheaper on
                # host than a full score row), so its break-even batch is
                # correspondingly larger — per-item queries essentially
                # always stay on the host mirror.
                rtt = link_rtt_s if link_rtt_s is not None \
                    else _probe_link_rtt_s()
                host_row = self._probe_host_row_s()
                self.link_rtt_s, self.host_row_s = rtt, host_row
                host_pair = max(host_row / self.n_cols, 1e-9)
                self.min_device_batch = max(1, int(np.ceil(rtt / host_row)))
                self.min_pair_batch = max(1, int(np.ceil(rtt / host_pair)))
            if warmup and self.min_device_batch <= 1:
                # pre-compile the single-query buckets (the first live
                # request must not pay the ~seconds-scale XLA compile)
                self.top_n_batch(np.zeros(1, np.int32), 16)
                if self.min_pair_batch <= 1:
                    self.score_pairs(
                        np.zeros(1, np.int32), np.zeros(1, np.int32)
                    )
        if warmup and self.min_device_batch > 1:
            # small batches will route to the host mirror: pay the
            # native-library g++ build and the transposed-table copy at
            # DEPLOY time, not inside the first live request
            self.top_n_batch(np.zeros(1, np.int32), 1)

    def _place_sharded(self, rows, cols):
        """Row-shard both tables over the mesh entity axis (padded to a
        shard multiple; pad rows are zero and masked out of top-k)."""
        import jax

        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh
        axis = "data" if "data" in mesh.axis_names else mesh.axis_names[0]
        size = int(mesh.shape[axis])
        sharding = NamedSharding(mesh, P(axis, None))

        def pad_rows(a):
            n = -(-a.shape[0] // size) * size
            if n == a.shape[0]:
                return a
            out = np.zeros((n, a.shape[1]), a.dtype)
            out[: a.shape[0]] = a
            return out

        rows_dev = jax.device_put(pad_rows(rows), sharding)
        cols_p = pad_rows(cols)
        return rows_dev, jax.device_put(cols_p, sharding), cols_p.shape[0]

    @property
    def mesh_sharded(self) -> bool:
        """True when the factor tables are sharded over a serving mesh."""
        return self._mesh is not None and self.on_device

    def sharding_info(self) -> Optional[dict]:
        """Placement summary for /stats.json; None when unsharded."""
        if not self.mesh_sharded:
            return None
        mesh = self._mesh
        n_dev = int(np.prod(mesh.devices.shape))
        total = self._rows_np.nbytes + self._cols_np.nbytes
        return {
            "meshShape": {
                k: int(v) for k, v in mesh.shape.items() if int(v) > 1
            } or {"data": 1},
            "nDevices": n_dev,
            "rows": [int(self.n_rows), int(self.rank)],
            "cols": [int(self.n_cols), int(self.rank)],
            "colsPadded": int(self._ncols_pad),
            "bytesPerDevice": -(-total // n_dev),
            "totalBytes": int(total),
        }

    @property
    def on_device(self) -> bool:
        """True when at least some batch sizes route to the accelerator."""
        return self._rows_dev is not None

    def _probe_host_row_s(self) -> float:
        best = float("inf")
        for _ in range(3):
            t0 = monotonic_s()
            self._rows_np[0] @ self._cols_np.T
            best = min(best, monotonic_s() - t0)
        return max(best, 1e-7)

    def _route_to_device(self, batch: int) -> bool:
        return self.on_device and batch >= self.min_device_batch

    def _count_route(self, device: bool) -> None:
        with self._route_lock:
            self._route_counts["device" if device else "host"] += 1

    # pio: endpoint=/stats.json
    def route_info(self) -> dict:
        """Routing mode, the probe's measurements and the calls answered
        per route so far (``/stats.json`` ``topnScorers``)."""
        with self._route_lock:
            counts = dict(self._route_counts)

        def finite(v):
            return None if v == float("inf") else int(v)

        return {
            "mode": self.mode,
            "onDevice": self.on_device,
            "linkRttS": self.link_rtt_s,
            "hostRowS": self.host_row_s,
            "minDeviceBatch": finite(self.min_device_batch),
            "minPairBatch": finite(self.min_pair_batch),
            "routes": counts,
        }

    # ----------------------------------------------------------- device path
    def _top_n_device(self, codes, n, exclude):
        import jax

        B = codes.shape[0]
        k = _bucket(n, self.n_cols) if n < self.n_cols else self.n_cols
        padded_cols = self._ncols_pad != self.n_cols
        n_valid = self.n_cols if padded_cols else None
        idx_out = np.empty((B, k), np.int64)
        val_out = np.empty((B, k), np.float32)
        for lo in range(0, B, _MAX_BATCH_BUCKET):
            chunk = codes[lo:lo + _MAX_BATCH_BUCKET]
            bb = _bucket(chunk.shape[0], _MAX_BATCH_BUCKET)
            pad = bb - chunk.shape[0]
            cp = np.pad(chunk, (0, pad))
            if exclude is not None:
                # bucket the exclusion width too — every distinct raw E
                # would otherwise trigger a fresh XLA compile per request
                E = exclude.shape[1]
                ep = np.pad(
                    exclude[lo:lo + _MAX_BATCH_BUCKET],
                    ((0, pad), (0, _bucket(max(E, 1), 1 << 30) - E)),
                    constant_values=self._ncols_pad,  # OOB → dropped
                )
                vals, idx = _topn_fn(k, True, n_valid)(
                    self._rows_dev, self._cols_dev, cp, ep
                )
            else:
                vals, idx = _topn_fn(k, False, n_valid)(
                    self._rows_dev, self._cols_dev, cp
                )
            vals, idx = jax.device_get((vals, idx))
            m = chunk.shape[0]
            idx_out[lo:lo + m] = idx[:m]
            val_out[lo:lo + m] = vals[:m]
        if padded_cols:
            # a fully-masked row could surface a pad index at -inf; pin
            # such slots to col 0 so callers never see an OOB item code
            idx_out = np.where(np.isfinite(val_out), idx_out, 0)
        return idx_out[:, :n], val_out[:, :n]

    #: native host scorer is a SINGLE-CORE fused loop targeting the
    #: per-request serving path; larger batches keep the multithreaded
    #: BLAS GEMM + argpartition (batch_predict on many-core hosts)
    _NATIVE_HOST_MAX_BATCH = 8

    # ------------------------------------------------------------- host path
    def _top_n_host(self, codes, n, exclude):
        if exclude is None and codes.shape[0] <= self._NATIVE_HOST_MAX_BATCH:
            got = self._top_n_host_native(codes, n)
            if got is not None:
                return got
        B = codes.shape[0]
        # chunk rows so the [chunk, N] score + key planes stay ~100 MB
        # regardless of batch size (batch_predict can send thousands)
        chunk = max(1, (8 << 20) // max(1, self.n_cols))
        idx_out = np.empty((B, n), np.int64)
        val_out = np.empty((B, n), np.float32)
        for lo in range(0, B, chunk):
            hi = min(B, lo + chunk)
            ex = exclude[lo:hi] if exclude is not None else None
            idx_out[lo:hi], val_out[lo:hi] = self._top_n_host_chunk(
                codes[lo:hi], n, ex
            )
        return idx_out, val_out

    def _top_n_host_chunk(self, codes, n, exclude):
        scores = self._rows_np[codes] @ self._cols_np.T  # [B, N]
        if exclude is not None:
            b = np.arange(scores.shape[0])[:, None]
            keep = exclude < self.n_cols  # sentinel slots stay untouched
            scores[
                np.broadcast_to(b, exclude.shape)[keep],
                exclude[keep],
            ] = -np.inf
        # composite u64 keys encode (-score, index): selection and order
        # become DETERMINISTIC under score ties — the same (-score, idx)
        # contract the native serving path implements, so predict and
        # batch_predict agree on tied items (exactly, up to summation
        # rounding differences between the two dot-product loops). NaN
        # (diverged factors) maps to -inf in BOTH paths: ranks tied-last,
        # surfaces as -inf. `+ 0.0` canonicalizes -0.0 to +0.0 so the
        # bit transform ties them like the native float compare does.
        scores += np.float32(0.0)
        np.copyto(scores, -np.inf, where=np.isnan(scores))
        bits = scores.view(np.uint32)
        ordered = np.where(
            (bits >> np.uint32(31)).astype(bool),
            ~bits, bits | np.uint32(0x80000000),
        )
        keys = (
            ((np.uint32(0xFFFFFFFF) - ordered).astype(np.uint64)
             << np.uint64(32))
            | np.arange(self.n_cols, dtype=np.uint64)[None, :]
        )
        if n < self.n_cols:
            part = np.argpartition(keys, n - 1, axis=1)[:, :n]
        else:
            part = np.argsort(keys, axis=1)
        pk = np.take_along_axis(keys, part, axis=1)
        order = np.argsort(pk, axis=1)
        idx = np.take_along_axis(part, order, axis=1).astype(np.int64)
        return idx, np.take_along_axis(scores, idx, axis=1)

    def _top_n_host_native(self, codes, n):
        """Fused native blocked scan-and-select (no [B, N] score array):
        stride-1 FMA over a transposed [K, N] table in L1-sized blocks,
        heap selection while each block is cache-hot. None → caller uses
        the numpy path (library unavailable, or exclusions requested)."""
        from pio_tpu.native import NativeUnavailable, topn_host_lib

        try:
            lib = topn_host_lib()
        except (NativeUnavailable, OSError):  # no toolchain / unloadable
            self._top_n_host_native = lambda codes, n: None
            return None
        if self._cols_t is None:
            # one-time transposed mirror (the kernel's layout); built
            # lazily so scorers that never take the host path skip it
            self._cols_t = np.ascontiguousarray(self._cols_np.T)
        B = codes.shape[0]
        out_idx = np.empty((B, n), np.int64)
        out_val = np.empty((B, n), np.float32)
        rc = lib.topn_host_f32(
            self._rows_np.ctypes.data_as(_F32P),
            self._cols_t.ctypes.data_as(_F32P),
            self.n_rows, self.n_cols, self.rank,
            np.ascontiguousarray(codes).ctypes.data_as(_I32P),
            B, n,
            out_idx.ctypes.data_as(_I64P),
            out_val.ctypes.data_as(_F32P),
        )
        if rc != 0:
            return None  # out-of-range code: numpy path raises the error
        return out_idx, out_val

    # -------------------------------------------------------------- public
    def top_n_batch(
        self,
        codes: np.ndarray,
        n: int,
        exclude: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-n col indices + scores for each row code.

        ``exclude``: optional ``[B, E]`` int array of col codes to mask out
        per row; pad unused slots with any value ≥ ``n_cols``.
        """
        codes = np.asarray(codes, np.int32)
        if codes.ndim != 1:
            raise ValueError("codes must be 1-D")
        n = max(1, min(n, self.n_cols))
        if exclude is not None:
            exclude = np.asarray(exclude, np.int32)
            if exclude.ndim != 2 or exclude.shape[0] != codes.shape[0]:
                raise ValueError("exclude must be [B, E]")
        if codes.shape[0] == 0 or self.n_cols == 0:
            b = codes.shape[0]
            n = 0 if self.n_cols == 0 else n
            return (np.empty((b, n), np.int64), np.empty((b, n), np.float32))
        device = self._route_to_device(codes.shape[0])
        self._count_route(device)
        if device:
            return self._top_n_device(codes, n, exclude)
        return self._top_n_host(codes, n, exclude)

    def scores_batch(self, codes: np.ndarray) -> np.ndarray:
        """Full ``[B, n_cols]`` score matrix (host numpy out).

        Unlike top-N, the result is B × n_cols floats back over the link —
        that payload, not the matmul, can dominate, so the device route is
        taken only when the link probe found a round trip no dearer than
        one host row (min_device_batch == 1, or forced mode).
        """
        import jax

        codes = np.asarray(codes, np.int32)
        B = codes.shape[0]
        device = B > 0 and self.on_device and self.min_device_batch <= 1
        if B:
            self._count_route(device)
        if not device:
            return self._rows_np[codes] @ self._cols_np.T
        out = np.empty((B, self.n_cols), np.float32)
        for lo in range(0, B, _MAX_BATCH_BUCKET):
            chunk = codes[lo:lo + _MAX_BATCH_BUCKET]
            bb = _bucket(chunk.shape[0], _MAX_BATCH_BUCKET)
            cp = np.pad(chunk, (0, bb - chunk.shape[0]))
            s = jax.device_get(
                _scores_fn()(self._rows_dev, self._cols_dev, cp)
            )
            # sharded placement pads the col table; trim pad columns
            out[lo:lo + chunk.shape[0]] = s[: chunk.shape[0], : self.n_cols]
        return out

    def score_pairs(
        self, row_codes: np.ndarray, col_codes: np.ndarray
    ) -> np.ndarray:
        """Per-pair dot products ``rows[rc] · cols[cc]`` → ``[B]``."""
        rc = np.asarray(row_codes, np.int32)
        cc = np.asarray(col_codes, np.int32)
        B = rc.shape[0]
        device = B > 0 and self.on_device and B >= self.min_pair_batch
        if B:
            self._count_route(device)
        if not device:
            return np.einsum(
                "bk,bk->b", self._rows_np[rc], self._cols_np[cc]
            )
        import jax

        chunk_cap = 1 << 20
        out = np.empty(B, np.float32)
        for lo in range(0, B, chunk_cap):
            rcc, ccc = rc[lo:lo + chunk_cap], cc[lo:lo + chunk_cap]
            bb = _bucket(rcc.shape[0], chunk_cap)
            pad = bb - rcc.shape[0]
            got = jax.device_get(_pairs_fn()(
                self._rows_dev, self._cols_dev,
                np.pad(rcc, (0, pad)), np.pad(ccc, (0, pad)),
            ))
            out[lo:lo + rcc.shape[0]] = got[: rcc.shape[0]]
        return out
