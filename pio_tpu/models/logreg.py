"""Softmax / logistic regression — TPU-native classification trainer.

Rebuild of the reference classification template's training substrate:
MLlib's ``LogisticRegressionWithLBFGS`` / ``NaiveBayes``
(``examples/scala-parallel-classification``, UNVERIFIED paths; SURVEY.md
§2.6) runs full-batch gradient aggregation via Spark ``treeAggregate`` over
executor partitions.

TPU-first formulation: examples are sharded over the mesh ``data`` axis
(NamedSharding); parameters stay replicated. The per-device partial gradient
reduction that ``treeAggregate`` did over netty becomes the ``psum`` XLA
inserts over ICI when a mean over the sharded batch dimension flows into
replicated outputs — no hand-written collectives. The whole optimization
loop is a single compiled program (``lax.scan`` over iterations), so HBM
never round-trips to host between steps.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

from pio_tpu.utils.numutil import n_stream_chunks


@functools.lru_cache(maxsize=32)
def _jitted_fit(mesh, axis: str, n_parts: int, iterations: int,
                learning_rate: float, reg: float):
    """Build (once per static config) the jitted full-batch trainer.

    Cached so repeat trains — production retrains, benchmark repeats —
    reuse the compiled program instead of paying a fresh trace+XLA
    compile per call (the scan over ``iterations`` is the expensive
    compile). Everything run-dependent (params, feature chunks, labels,
    mask, quantization scales) is an ARGUMENT, never a baked constant;
    jax's own dispatch cache handles shape/dtype/backend variation
    under the one wrapper.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    tx = optax.adam(learning_rate)

    def loss_fn(params, Xs, ys, ms, scales):
        w = params["w"]
        if scales is not None:
            # X ≈ X_q·s  ⇒  X@W = X_q@(s⊙W): a [D,C] elementwise per
            # step instead of a dequantized [N,D] HBM copy
            w = w * scales[:, None]
        if Xs.dtype == jnp.int8:
            Xs = Xs.astype(jnp.bfloat16)
        logits = (
            jnp.dot(Xs, w.astype(Xs.dtype),
                    preferred_element_type=jnp.float32)
            + params["b"]
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, ys)
        # mean over real rows only; over sharded inputs this contraction
        # is where XLA inserts the cross-device psum (≙ treeAggregate)
        data_loss = jnp.sum(ce * ms) / jnp.sum(ms)
        return data_loss + reg * jnp.sum(params["w"] ** 2)

    def fit(params, X_parts, ys, ms, scales):
        # chunked wire arrives as row spans: assembled once here
        # (device-side copy at HBM rate), OUTSIDE the scan
        Xs = X_parts[0] if len(X_parts) == 1 else jnp.concatenate(X_parts)
        opt_state = tx.init(params)

        def step(carry, _):
            params, opt_state = carry
            grads = jax.grad(loss_fn)(params, Xs, ys, ms, scales)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), None

        (params, _), _ = jax.lax.scan(
            step, (params, opt_state), None, length=iterations
        )
        return params

    if mesh is not None:
        shard = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())
        return jax.jit(
            fit,
            in_shardings=(repl, (shard,) * n_parts, shard, shard, repl),
            out_shardings=repl,
        )
    return jax.jit(fit)


@dataclasses.dataclass(frozen=True)
class LogRegConfig:
    iterations: int = 100
    learning_rate: float = 0.1
    reg: float = 0.0  # L2 on weights (not bias)
    seed: int = 0
    #: feature wire + matmul dtype. "float32" (default) keeps exact
    #: full-precision numerics, matching the reference's MLlib path.
    #: Opt into "bfloat16" to halve the host→device feature shipment —
    #: the dominant cost of a full-batch train on a slow link — and run
    #: the logits matmul at the MXU's native rate. Opt into "int8" to
    #: quarter it: features ship as symmetric per-column int8 codes and
    #: the [D] float32 scales fold into the WEIGHTS on device
    #: (X ≈ X_q·s, so X@W = X_q@(s⊙W) — one tiny [D,C] elementwise per
    #: step, no dequantized [N,D] copy), so the learned weights still
    #: apply to raw float features at serving time. Gradients, optimizer
    #: state, and the loss stay float32 in every mode.
    input_dtype: str = "float32"


@dataclasses.dataclass
class LogRegModel:
    """weights [D, C] float32, bias [C] float32, plus class count.

    ``feature_scales`` [D] float32 are the per-column symmetric
    quantization scales observed on the TRAINING features (None on
    models persisted before they were recorded): the serving-side int8
    wire folds them into device-resident weights so query features can
    ship as one byte per column (see ``pio_tpu/server/residency.py``).
    """

    weights: np.ndarray
    bias: np.ndarray
    n_classes: int
    feature_scales: Optional[np.ndarray] = None

    def logits(self, X: np.ndarray) -> np.ndarray:
        return X.astype(np.float32) @ self.weights + self.bias

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Argmax class codes for a [B, D] feature matrix."""
        return np.argmax(self.logits(X), axis=1).astype(np.int32)

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        z = self.logits(X)
        z = z - z.max(axis=1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=1, keepdims=True)


def train_logreg(
    ctx,
    X: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    config: LogRegConfig = LogRegConfig(),
    stats: Optional[dict] = None,
) -> LogRegModel:
    """Full-batch softmax regression with Adam, data-parallel over the mesh.

    Args:
        ctx: ComputeContext (mesh + batch axis); mesh=None → single device.
        X: [N, D] features (host numpy).
        y: [N] int class codes.
        n_classes: C.
        stats: optional dict that receives a phase decomposition of the
            run — pack_s (host encode), h2d_s (wire drain), device_s,
            d2h_s — with the h2d/compute overlap serialized so the
            phases are measurable (stats runs are slightly slower than
            plain runs, exactly like ``train_als``'s profiled mode).
    """
    import jax
    import jax.numpy as jnp

    if config.input_dtype not in ("bfloat16", "float32", "int8"):
        raise ValueError(
            f"input_dtype must be bfloat16/float32/int8, "
            f"got {config.input_dtype!r}"
        )
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.int32)
    n, d = X.shape

    mesh = ctx.mesh if ctx is not None else None
    axis = ctx.batch_axis if ctx is not None else "data"
    n_dev = ctx.num_devices if ctx is not None else 1

    # pad batch to a multiple of the device count; padded rows carry 0 weight
    n_pad = (-n) % max(n_dev, 1)
    if n_pad:
        X = np.concatenate([X, np.zeros((n_pad, d), np.float32)])
        y = np.concatenate([y, np.zeros(n_pad, np.int32)])
    mask = np.concatenate(
        [np.ones(n, np.float32), np.zeros(n_pad, np.float32)]
    )

    w_key = jax.random.PRNGKey(config.seed)
    params = {
        # small seeded init: breaks symmetry and makes `seed` a live knob
        "w": 0.01 * jax.random.normal(w_key, (d, n_classes), jnp.float32),
        "b": jnp.zeros((n_classes,), jnp.float32),
    }

    # per-column symmetric quantization scales: the int8 TRAINING wire
    # folds them into the weights on device so the learned W applies to
    # RAW floats; they also persist on the model (every mode — the pass
    # is one reduction) so the SERVING int8 wire can quantize query
    # features with the same training-side scales
    s = np.abs(X).max(axis=0)
    feature_scales = np.where(s == 0.0, 1.0, s / 127.0).astype(np.float32)
    scales = feature_scales if config.input_dtype == "int8" else None

    def _prep(chunk: np.ndarray) -> np.ndarray:
        """Host-side wire encoding of a row span (the per-chunk work the
        streamed path overlaps with the previous chunk's transfer)."""
        if config.input_dtype == "bfloat16":
            # cast on the HOST (ml_dtypes ships with jax) so only
            # 2 B/feature cross the link; a device-side cast would ship
            # f32 first
            import ml_dtypes

            return chunk.astype(ml_dtypes.bfloat16)
        if config.input_dtype == "int8":
            return np.clip(
                np.rint(chunk / scales), -127, 127
            ).astype(np.int8)
        return chunk

    # chunked double-buffered shipment (single-device path): encode span
    # k+1 on host while span k is still crossing the link (device_put is
    # async). Multi-device runs keep one put per device shard — chunking
    # WITHIN shards is the mesh-wire streaming discipline (als.py).
    itemsize = {"bfloat16": 2, "int8": 1}.get(config.input_dtype, 4)
    wire_bytes = X.shape[0] * d * itemsize
    n_stream = 1
    if mesh is None or n_dev == 1:
        n_stream = n_stream_chunks(wire_bytes, "PIO_TPU_LOGREG_STREAM_MB")
    bounds = np.linspace(0, X.shape[0], n_stream + 1, dtype=int)
    spans = [(a, b) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]

    fit = _jitted_fit(mesh, axis, len(spans), config.iterations,
                      config.learning_rate, config.reg)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())
        put_x = lambda a: jax.device_put(a, shard)
        put_r = lambda a: jax.device_put(a, repl)
    else:
        put_x = put_r = jax.device_put
    from pio_tpu.obs import monotonic_s

    scales_dev = put_r(jnp.asarray(scales)) if scales is not None else None
    ys_dev = put_x(y)
    ms_dev = put_x(mask)
    params_dev = put_r(params)
    if stats is not None:
        # serialize pack vs drain: encode every span first (pack_s),
        # then let the transfers drain (h2d_s) — overlap off, like
        # train_als's profiled mode
        t0 = monotonic_s()
        encoded = [_prep(X[a:b]) for a, b in spans]
        stats["pack_s"] = monotonic_s() - t0
        t0 = monotonic_s()
        X_parts = tuple(put_x(e) for e in encoded)
        jax.block_until_ready((X_parts, ys_dev, ms_dev, params_dev))
        stats["h2d_s"] = monotonic_s() - t0
        stats["wire_bytes"] = int(
            wire_bytes + y.nbytes + mask.nbytes
        )
        stats["n_stream"] = len(spans)
        t0 = monotonic_s()
    else:
        X_parts = tuple(put_x(_prep(X[a:b])) for a, b in spans)
    fitted = fit(params_dev, X_parts, ys_dev, ms_dev, scales_dev)
    if stats is not None:
        jax.block_until_ready(fitted)
        stats["device_s"] = monotonic_s() - t0
        t0 = monotonic_s()
    # one fused pull: separate np.asarray calls pay the link RTT twice
    weights, bias = jax.device_get((fitted["w"], fitted["b"]))
    weights, bias = np.asarray(weights), np.asarray(bias)
    if stats is not None:
        stats["d2h_s"] = monotonic_s() - t0

    return LogRegModel(
        weights=weights, bias=bias, n_classes=n_classes,
        feature_scales=feature_scales,
    )
