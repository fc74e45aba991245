"""Device-resident serving scorers with donated dispatch buffers.

The warmed serving path (bucket cache + micro-batcher + batch lane,
ISSUE 7) still pays two host-side taxes per dispatch: the model's
parameters are re-fed from the host mirror into every ``predict`` call,
and the query features cross the link as float32. This module makes the
hot path device-resident and (near-)zero-copy:

* **Resident params** — a :class:`ResidentLinearScorer` places the
  template's serving parameters on the device ONCE at deploy/hot-swap
  (``jax.device_put`` behind the query server's swap lock) as jax
  arrays; every dispatch passes the same device buffers to a shared
  jitted program instead of re-uploading a host mirror. Hot-swap
  :meth:`retire`\\ s the old generation — a retired scorer refuses to
  serve, so stale weights can never answer a live query.

* **Donated output buffers** — the jitted scorer takes a pre-allocated
  per-bucket logits buffer with ``donate_argnums=(0,)`` and returns the
  refreshed buffer: steady state ping-pongs ONE device allocation per
  bucket instead of alloc/free per call. The buffer rides inside a
  :class:`DonatedBuffer` guard — a donated buffer must never be re-read
  (on backends that honor donation the memory now holds the new logits)
  and the guard makes a re-read raise instead of returning garbage.
  Donation accounting: a dispatch that recycled an existing bucket
  buffer is a **hit**; a cold shape that had to allocate fresh is a
  **miss** (first dispatch per bucket per generation — flat in steady
  state). Backends that additionally reclaim the donated input's memory
  (TPU/GPU; CPU ignores donation) are counted as ``backend_reclaims``.

* **int8 feature wire** — with ``wire="int8"`` the query features are
  quantized at request decode with the TRAINING-side per-column scales
  (``x_q = clip(rint(x / s), -127, 127)``) and the scales fold into the
  resident weights (``X @ W = X_q @ (s ⊙ W)`` — the identity the
  training wire already uses, see ``models/logreg.py``), so per-request
  H2D drops to one byte per feature and the device math is unchanged.

Env knobs (see docs/operations.md):

* ``PIO_TPU_DEVICE_RESIDENT`` — ``1`` force-on, ``0`` force-off,
  unset/``auto``: resident only on a real accelerator backend (CPU
  serving keeps the host-numpy path that every existing deploy runs).
* ``PIO_TPU_SERVE_WIRE`` — ``int8`` / ``float32`` / unset ``auto``
  (int8 whenever the model carries training scales, else float32).
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from pio_tpu.utils import knobs
from pio_tpu.faults import failpoint
from pio_tpu.obs import devicewatch

log = logging.getLogger("pio_tpu.residency")

WIRE_INT8 = "int8"
WIRE_FLOAT32 = "float32"


def enabled() -> bool:
    """Is device-resident serving on for this process?

    ``PIO_TPU_DEVICE_RESIDENT=1`` forces on (tests, CPU smoke),
    ``=0`` forces off; the ``auto`` default enables residency only on a
    real accelerator backend — on CPU the host-numpy predict path is
    already resident by definition and existing deploys keep it."""
    flag = knobs.knob_str("PIO_TPU_DEVICE_RESIDENT").strip().lower()
    if flag in ("0", "off", "false"):
        return False
    if flag in ("1", "on", "true"):
        return True
    import jax

    return jax.default_backend() != "cpu"


def wire_mode(has_scales: bool) -> str:
    """Resolve the serving feature wire: the ``PIO_TPU_SERVE_WIRE``
    override, else int8 whenever training scales exist to fold."""
    raw = knobs.knob_str("PIO_TPU_SERVE_WIRE").strip().lower()
    if raw == WIRE_INT8:
        return WIRE_INT8 if has_scales else WIRE_FLOAT32
    if raw == WIRE_FLOAT32:
        return WIRE_FLOAT32
    return WIRE_INT8 if has_scales else WIRE_FLOAT32


class DonatedBuffer:
    """Single-use handle around a device buffer headed into a
    ``donate_argnums`` call.

    Donation transfers ownership of the buffer's memory to the compiled
    program — after the call the old array may alias the OUTPUT, so any
    further read through the old reference is a correctness bug (jax
    only faults on backends that honor donation; CPU silently returns
    stale bytes). The guard makes the contract enforceable everywhere:
    :meth:`take` hands the raw buffer out exactly once, and every later
    ``take``/``array`` raises loudly."""

    __slots__ = ("_buf", "_taken")

    def __init__(self, buf):
        self._buf = buf
        self._taken = False

    def take(self):
        """Hand the raw device buffer to the donating call. One shot."""
        if self._taken:
            raise RuntimeError(
                "donated device buffer re-used: this buffer was already "
                "handed to a donate_argnums dispatch and its memory may "
                "now hold that dispatch's output"
            )
        self._taken = True
        buf, self._buf = self._buf, None
        return buf

    def array(self) -> np.ndarray:
        """Host copy of the buffer — raises once donated."""
        if self._taken or self._buf is None:
            raise RuntimeError(
                "donated device buffer re-read after donation"
            )
        return np.asarray(self._buf)

    @property
    def donated(self) -> bool:
        return self._taken


@functools.lru_cache(maxsize=1)
def _scorer_fn():
    """The ONE jitted linear scorer shared by every resident model and
    bucket: params and the donated logits buffer are arguments, so jax's
    shape-keyed dispatch cache gives each (bucket, D, C, wire-dtype)
    combination its own executable under a single wrapper — hot-swap
    generations and multiple engines reuse compiles, and the warmup
    sweep in the query server is what populates the cache."""
    import jax
    import jax.numpy as jnp

    # keep_unused: the donated buffer contributes MEMORY, not values —
    # without it jit would DCE the argument and the input/output alias
    # match (same [B, C] f32 aval as the returned logits) never forms
    @functools.partial(jax.jit, donate_argnums=(0,), keep_unused=True)
    def score(logits_buf, x, w, b):
        # int8 codes (or raw f32 features) against the resident weights;
        # the scales are pre-folded into w, so both wires share one
        # program shape-for-shape. logits has logits_buf's aval exactly,
        # which is what lets XLA alias the donated buffer's memory.
        logits = (
            jnp.dot(x.astype(jnp.float32), w,
                    preferred_element_type=jnp.float32)
            + b
        )
        del logits_buf  # consumed via donation (memory, not values)
        codes = jnp.argmax(logits, axis=1).astype(jnp.int32)
        return logits, codes

    return score


class ResidentLinearScorer:
    """Device-resident ``argmax(X @ W + b)`` scorer for the linear
    classifier templates (logreg weights, multinomial-NB log-thetas).

    Built by ``Algorithm.resident_scorer`` at deploy/hot-swap; the query
    server places it before the swap is visible, binds the metric sinks,
    and retires the previous generation when the swap lands.
    """

    def __init__(
        self,
        weights: np.ndarray,
        bias: np.ndarray,
        scales: Optional[np.ndarray] = None,
        name: str = "",
        query_factory: Optional[Callable[[np.ndarray], object]] = None,
        result_factory: Optional[Callable[[int], object]] = None,
        mesh=None,
    ):
        import jax
        import jax.numpy as jnp

        W = np.asarray(weights, np.float32)  # [D, C]
        b = np.asarray(bias, np.float32)  # [C]
        if W.ndim != 2 or b.shape != (W.shape[1],):
            raise ValueError(
                f"weights [D,C] / bias [C] expected, got {W.shape} {b.shape}"
            )
        self.name = name
        self.in_dim = int(W.shape[0])
        self.n_classes = int(W.shape[1])
        self.scales = (
            np.asarray(scales, np.float32).reshape(self.in_dim)
            if scales is not None else None
        )
        self.wire = wire_mode(self.scales is not None)
        #: mints the template's Query from a dequantized feature row —
        #: lets the lane drainer turn a packed int8 payload back into a
        #: servable query (see batchlane.PackedQuery)
        self.query_factory = query_factory
        #: maps one argmax class code straight to the template's result
        #: object. Attaching it is the template's declaration that a
        #: wire-codes dispatch is result-equivalent to its full
        #: supplement → predict path, which lets the packed query wire
        #: skip the dequantize → Query → re-quantize round trip
        self.result_factory = result_factory
        if self.wire == WIRE_INT8:
            # fold the training scales into the resident weights once:
            # X @ W == (X/s·s) @ W == X_q @ (s ⊙ W) up to quantization
            w_eff = self.scales[:, None] * W
        else:
            w_eff = W
        # the one-time placement: these device arrays ARE the serving
        # params for this generation; no per-dispatch host re-feed.
        # With a multi-chip mesh the weights row-shard on the contraction
        # dim (each chip holds D/n rows; the jitted matmul closes with a
        # psum and the logits come back replicated, so the donated
        # buffers keep their single-buffer aval and aliasing). A D that
        # doesn't divide the axis falls back to replicated placement
        # (``mesh_fallback`` — the service counts it).
        self._mesh = None
        self._x_sharding = None
        self.mesh_fallback = False
        if mesh is not None and int(np.prod(mesh.devices.shape)) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from pio_tpu.parallel.partition import assert_device_budget

            axis = (
                "data" if "data" in mesh.axis_names else mesh.axis_names[0]
            )
            if self.in_dim % int(mesh.shape[axis]) == 0:
                n_dev = int(np.prod(mesh.devices.shape))
                assert_device_budget(
                    w_eff.nbytes + b.nbytes, n_dev,
                    f"resident scorer {name!r} mesh placement",
                )
                self._mesh = mesh
                self._w_dev = jax.device_put(
                    jnp.asarray(w_eff), NamedSharding(mesh, P(axis, None))
                )
                self._x_sharding = NamedSharding(mesh, P())
                self._b_dev = jax.device_put(
                    jnp.asarray(b), self._x_sharding
                )
            else:
                self.mesh_fallback = True
        if self._mesh is None:
            from pio_tpu.parallel.partition import assert_device_budget

            assert_device_budget(
                w_eff.nbytes + b.nbytes, 1,
                f"resident scorer {name!r} placement",
            )
            self._w_dev = jax.device_put(jnp.asarray(w_eff))
            self._b_dev = jax.device_put(jnp.asarray(b))
        self.placed_bytes = int(w_eff.nbytes + b.nbytes)
        #: per-bucket donated logits buffers, keyed by batch size; the
        #: value cycles: donated into the dispatch, replaced by the
        #: returned (aliased) buffer
        self._out_bufs: Dict[int, DonatedBuffer] = {}
        self._lock = threading.Lock()
        self.retired = False
        # accounting (host ints; the service mirrors them into counters
        # via the bound sinks)
        self.h2d_bytes = 0
        self.dispatches = 0
        self.donation_hits = 0
        self.donation_misses = 0
        self.backend_reclaims = 0
        self._on_h2d: Optional[Callable[[int], None]] = None
        self._on_donation: Optional[Callable[[str], None]] = None
        # device ledger (ISSUE 17): book the placement with the active
        # watch; retire() releases it. Per-scorer compile attribution
        # keys off the bucket sizes this instance has dispatched.
        self._dw_key = f"{name}#{id(self):x}"
        devicewatch.ledger_place(
            "resident", self._dw_key, self.placed_bytes, name=name
        )

    # -- service wiring ----------------------------------------------------
    def bind(self, on_h2d=None, on_donation=None) -> "ResidentLinearScorer":
        """Attach the query server's metric sinks (h2d bytes counter,
        donation outcome counter)."""
        self._on_h2d = on_h2d
        self._on_donation = on_donation
        return self

    def prealloc(self, buckets) -> None:
        """Pre-allocate the per-bucket output buffers for the serving
        ladder so even each bucket's FIRST hot dispatch recycles instead
        of allocating (the warmup sweep then compiles against the same
        buffers)."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            for b in buckets:
                if b not in self._out_bufs:
                    z = jnp.zeros((int(b), self.n_classes), jnp.float32)
                    self._out_bufs[b] = DonatedBuffer(
                        jax.device_put(z, self._x_sharding)
                        if self._x_sharding is not None
                        else jax.device_put(z)
                    )
            donated = sum(
                int(b) * self.n_classes * 4 for b in self._out_bufs
            )
        devicewatch.ledger_place(
            "donated", self._dw_key, donated,
            name=f"{self.name} logits buffers",
        )

    def retire(self) -> None:
        """Hot-swap eviction: drop the device params and refuse further
        dispatches. The old generation's buffers free with the refs."""
        with self._lock:
            self.retired = True
            self._w_dev = None
            self._b_dev = None
            self._out_bufs.clear()
        devicewatch.ledger_release("resident", self._dw_key)
        devicewatch.ledger_release("donated", self._dw_key)

    # -- wire encode -------------------------------------------------------
    def quantize(self, X: np.ndarray) -> np.ndarray:
        """Host-side int8 wire encode of raw float features with the
        training scales (exact inverse of the fold in the weights)."""
        if self.scales is None:
            raise ValueError(f"scorer {self.name!r} has no feature scales")
        return np.clip(
            np.rint(np.asarray(X, np.float32) / self.scales), -127, 127
        ).astype(np.int8)

    def dequantize(self, codes: np.ndarray) -> np.ndarray:
        """int8 wire codes back to (approximate) float features —
        re-quantizing the result yields the identical codes, which is
        what makes the packed lane path round-trip exactly."""
        if self.scales is None:
            raise ValueError(f"scorer {self.name!r} has no feature scales")
        return codes.astype(np.float32) * self.scales

    def encode(self, X: np.ndarray) -> np.ndarray:
        """Features → wire form (int8 codes or float32 passthrough)."""
        if self.wire == WIRE_INT8:
            return self.quantize(X)
        return np.ascontiguousarray(X, np.float32)

    # -- dispatch ----------------------------------------------------------
    def score_codes(self, X: np.ndarray) -> np.ndarray:
        """Argmax class codes for a [B, D] float feature batch through
        the resident params (wire encode on host, one h2d, one compiled
        dispatch)."""
        return self.score_wire(self.encode(X))

    def score_wire(self, wire: np.ndarray) -> np.ndarray:
        """Dispatch an already wire-encoded [B, D] batch (the packed
        lane path lands here without re-quantizing)."""
        import jax

        if self.retired:
            raise RuntimeError(
                f"resident scorer {self.name!r} is retired (model was "
                f"hot-swapped); refusing to serve stale weights"
            )
        if wire.ndim != 2 or wire.shape[1] != self.in_dim:
            raise ValueError(
                f"wire batch [B,{self.in_dim}] expected, got {wire.shape}"
            )
        n = wire.shape[0]
        failpoint("scorer.h2d.ship")
        if self._x_sharding is not None:
            x_dev = jax.device_put(wire, self._x_sharding)
        else:
            # let the jitted call ship the host array itself: the
            # runtime's C++ transfer path is several times cheaper than
            # an explicit device_put for the per-request single-query
            # dispatch (the bytes crossing host→device are identical)
            x_dev = np.ascontiguousarray(wire)
        nbytes = int(wire.nbytes)
        self.h2d_bytes += nbytes
        if self._on_h2d is not None:
            self._on_h2d(nbytes)
        # per-bucket donated buffer: recycle the standing allocation
        # (hit) or mint one for a cold shape (miss — once per bucket per
        # generation; the prealloc'd ladder never misses)
        failpoint("scorer.donate.dispatch")
        with self._lock:
            if self.retired:
                raise RuntimeError(
                    f"resident scorer {self.name!r} retired mid-dispatch"
                )
            guard = self._out_bufs.pop(n, None)
        outcome = "hit" if guard is not None else "miss"
        if guard is None:
            import jax.numpy as jnp

            z = jnp.zeros((n, self.n_classes), jnp.float32)
            guard = DonatedBuffer(
                jax.device_put(z, self._x_sharding)
                if self._x_sharding is not None
                else jax.device_put(z)
            )
        raw = guard.take()
        # compile attribution: the first dispatch at this program shape
        # (batch n × this model's dims) is the trace+compile entry.
        # Keyed on the WATCH, not the scorer instance: _scorer_fn's jit
        # cache is process-global, so a hot-swapped replacement scorer
        # re-dispatching a warmed shape compiles nothing and must not
        # be recounted. Steady buckets add one set-membership test to
        # the hot path, nothing more.
        with devicewatch.compile_span(
            "resident_scorer", key=(n, self.in_dim, self.n_classes)
        ):
            new_logits, codes = _scorer_fn()(
                raw, x_dev, self._w_dev, self._b_dev
            )
        # the old buffer object is dead either way; count the backends
        # that actually reclaimed its memory (CPU ignores donation)
        try:
            if raw.is_deleted():
                self.backend_reclaims += 1
        except AttributeError:
            pass
        with self._lock:
            if not self.retired:
                self._out_bufs[n] = DonatedBuffer(new_logits)
        self.dispatches += 1
        if outcome == "hit":
            self.donation_hits += 1
        else:
            self.donation_misses += 1
        if self._on_donation is not None:
            self._on_donation(outcome)
        return np.asarray(codes)

    # -- introspection -----------------------------------------------------
    # pio: endpoint=/stats.json
    def to_dict(self) -> dict:
        total = self.donation_hits + self.donation_misses
        return {
            "name": self.name,
            "wire": self.wire,
            "inDim": self.in_dim,
            "nClasses": self.n_classes,
            "paramBytes": self.placed_bytes,
            "sharded": self._mesh is not None,
            "retired": self.retired,
            "dispatches": self.dispatches,
            "h2dBytes": self.h2d_bytes,
            "donation": {
                "hits": self.donation_hits,
                "misses": self.donation_misses,
                "hitRate": (
                    round(self.donation_hits / total, 4) if total else None
                ),
                "backendReclaims": self.backend_reclaims,
            },
        }
