"""Sequence recommender — causal transformer over user event histories.

The reference has no sequential model (nearest concepts: MarkovChain in e2,
ALS over an interaction matrix — SURVEY.md §2.5); this model family makes
the framework's long-context support real: next-item prediction over a
user's **entire event history**, SASRec-style.

One jitted train step composes every parallelism axis in the mesh
(pio_tpu/parallel/mesh.py):

- **dp**    — batch rows shard over ``data``; the loss mean psums there.
- **sp**    — the sequence shards over ``seq``; attention is exact ring
  attention (pio_tpu/parallel/ring.py), K/V blocks rotating by ppermute.
- **tp**    — attention heads and FFN hidden shard over ``model``
  (Megatron split: column-parallel in, row-parallel out + psum).
- **ep**    — the item-embedding table shards by vocab rows over ``model``;
  logits use *vocab-parallel* cross-entropy (local partial logits, pmax /
  psum assembled log-softmax) so the ``[B, T, V]`` tensor never exists
  unsharded.
- **pp**    — transformer blocks stack over ``pipe`` and microbatches flow
  through :func:`pio_tpu.parallel.pipeline.pipeline_apply`.

Everything is differentiated through ``shard_map``; JAX transposes the
collectives (psum↔broadcast, ppermute↔reverse ppermute, gather↔scatter).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

from pio_tpu.parallel.mesh import mesh_axis_size
from pio_tpu.parallel.vocab import (
    vocab_parallel_lookup,
    vocab_parallel_target_gather,
)
from pio_tpu.utils.numutil import round_up as _round_up


@dataclasses.dataclass(frozen=True)
class SeqRecConfig:
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    ffn: int = 128
    max_len: int = 64
    dropout: float = 0.0  # reserved; deterministic v1
    learning_rate: float = 1e-3
    steps: int = 200
    #: sequence-parallel attention mode: "ring" (ppermute K/V rotation,
    #: O(T/n) memory — longest contexts) or "ulysses" (two all-to-alls,
    #: full-T for H/n heads — fewer collective hops; needs the local head
    #: count divisible by the seq-axis size). See pio_tpu/parallel/.
    attention: str = "ring"
    seed: int = 0
    #: rows per optimizer step. 0 = full-batch (every step consumes the
    #: whole dataset — the historical path); > 0 = minibatch SGD over
    #: wrapped contiguous row blocks, which is what lets the epoch
    #: STREAM through the mesh instead of staging on device.
    batch_size: int = 0
    #: epoch feed for the minibatch path: "off" stages the full epoch
    #: on device, "on" streams row spans through parallel/stream.py,
    #: "auto" streams only when staging would exceed
    #: PIO_TPU_DEVICE_BUDGET_BYTES. Streamed and staged runs with the
    #: same seed/config produce identical params.
    stream: str = "auto"


@dataclasses.dataclass
class SeqRecModel:
    """Trained transformer; host copies of params for persistence/serving."""

    params: dict  # layer-stacked pytree (host numpy)
    n_items: int
    config: SeqRecConfig
    _serve_cache: Optional[tuple] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_serve_cache"] = None
        return state

    def next_item_scores(self, histories: np.ndarray) -> np.ndarray:
        """[B, T] padded histories (0 = pad) → [B, V] next-item scores.

        Single-device serving path; jitted + device-cached like
        MLPModel (pio_tpu/models/mlp.py).
        """
        import jax
        import jax.numpy as jnp

        if self._serve_cache is None:
            params = jax.tree.map(jnp.asarray, self.params)

            @jax.jit
            def fwd(params, seqs):
                h = _trunk(params, seqs, self.config, None, None, None)
                # score from the last real position of each row
                lengths = (seqs > 0).sum(axis=1)
                last = jnp.take_along_axis(
                    h,
                    jnp.maximum(lengths - 1, 0)[:, None, None],
                    axis=1,
                )[:, 0]
                return jnp.dot(
                    last,
                    params["emb"].T,
                    preferred_element_type=jnp.float32,
                )

            self._serve_cache = (fwd, params)
        fwd, params = self._serve_cache
        return np.asarray(fwd(params, jnp.asarray(histories, jnp.int32)))


def init_params(vocab: int, cfg: SeqRecConfig):
    """Layer-stacked parameter pytree (leading dim = n_layers)."""
    import jax

    k = jax.random.PRNGKey(cfg.seed)
    keys = jax.random.split(k, 8)
    D, F, L = cfg.d_model, cfg.ffn, cfg.n_layers
    s = D ** -0.5

    def nrm(key, shape, scale):
        return jax.random.normal(key, shape) * scale

    return {
        "emb": nrm(keys[0], (vocab, D), s),
        "pos": nrm(keys[1], (cfg.max_len, D), s),
        "blocks": {
            "ln1_g": np.ones((L, D), np.float32),
            "ln1_b": np.zeros((L, D), np.float32),
            "wq": nrm(keys[2], (L, D, D), s),
            "wk": nrm(keys[6], (L, D, D), s),
            "wv": nrm(keys[7], (L, D, D), s),
            "wo": nrm(keys[3], (L, D, D), s),
            "ln2_g": np.ones((L, D), np.float32),
            "ln2_b": np.zeros((L, D), np.float32),
            "w1": nrm(keys[4], (L, D, F), s),
            "b1": np.zeros((L, F), np.float32),
            "w2": nrm(keys[5], (L, F, D), F ** -0.5),
            "b2": np.zeros((L, D), np.float32),
        },
        "lnf_g": np.ones((D,), np.float32),
        "lnf_b": np.zeros((D,), np.float32),
    }


def param_specs(cfg: SeqRecConfig):
    """PartitionSpecs: ep for emb, tp for heads/ffn, pp over the stack —
    derived from the partition-rule registry (``rules_for("seqrec")``)."""
    from pio_tpu.parallel.partition import match_partition_rules, rules_for

    block_keys = (
        "ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
        "ln2_g", "ln2_b", "w1", "b1", "w2", "b2",
    )
    skeleton = {
        "emb": np.empty(0),
        "pos": np.empty(0),
        "blocks": {k: np.empty(0) for k in block_keys},
        "lnf_g": np.empty(0),
        "lnf_b": np.empty(0),
    }
    return match_partition_rules(
        rules_for("seqrec"), skeleton, on_unmatched="error"
    )


def _ln(x, g, b):
    import jax

    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * g + b


def _block(blk, h, cfg, m_axis, s_axis):
    """One pre-LN transformer block on the local [mb, T_loc, D] slice.

    ``blk`` leaves have NO layer dim (already sliced). Heads/FFN hidden are
    local tp shards; attention rides the ring over ``s_axis``.
    """
    import jax
    import jax.numpy as jnp

    from jax.lax import axis_size
    from pio_tpu.parallel.ring import ring_attention
    from pio_tpu.parallel.ulysses import ulysses_attention

    mb, t_loc, D = h.shape
    n_model = 1 if m_axis is None else axis_size(m_axis)
    heads_loc = cfg.n_heads // n_model
    hd = cfg.d_model // cfg.n_heads
    if cfg.attention == "ring":
        attn_fn = ring_attention
    elif cfg.attention == "ulysses":
        attn_fn = ulysses_attention
    else:
        raise ValueError(
            f"unknown attention mode {cfg.attention!r}; use ring/ulysses"
        )

    x = _ln(h, blk["ln1_g"], blk["ln1_b"])
    # separate projections: a fused [D, 3D] column shard would split at
    # arbitrary offsets and scramble the q/k/v boundaries across devices
    q = jnp.dot(x, blk["wq"], preferred_element_type=jnp.float32)
    k = jnp.dot(x, blk["wk"], preferred_element_type=jnp.float32)
    v = jnp.dot(x, blk["wv"], preferred_element_type=jnp.float32)

    def split_heads(a):
        return a.reshape(mb, t_loc, heads_loc, hd)

    attn = attn_fn(
        split_heads(q), split_heads(k), split_heads(v),
        axis=s_axis, causal=True,
    ).reshape(mb, t_loc, heads_loc * hd)
    out = jnp.dot(attn, blk["wo"], preferred_element_type=jnp.float32)
    if m_axis is not None:
        out = jax.lax.psum(out, m_axis)  # close row-parallel wo (tp)
    h = h + out

    x = _ln(h, blk["ln2_g"], blk["ln2_b"])
    f = jnp.maximum(
        jnp.dot(x, blk["w1"], preferred_element_type=jnp.float32)
        + blk["b1"],
        0.0,
    )
    f = jnp.dot(f, blk["w2"], preferred_element_type=jnp.float32)
    if m_axis is not None:
        f = jax.lax.psum(f, m_axis)
    return h + f + blk["b2"]


def _embed(params, seqs, cfg, m_axis, s_axis):
    """Vocab-parallel embedding + global-position encoding → [mb, T_loc, D]."""
    import jax
    import jax.numpy as jnp

    x = vocab_parallel_lookup(params["emb"], seqs, m_axis)
    t_loc = seqs.shape[1]
    t_off = 0 if s_axis is None else jax.lax.axis_index(s_axis) * t_loc
    pos = jax.lax.dynamic_slice_in_dim(params["pos"], t_off, t_loc)
    return x + pos[None]


def _trunk(params, seqs, cfg, m_axis, s_axis, p_axis):
    """Embed + all transformer blocks + final LN → [mb, T_loc, D].

    With a pipe axis the blocks run through pipeline_apply (the whole local
    batch as ONE microbatch per tick slot — callers microbatch upstream);
    otherwise a scan over the layer stack.
    """
    import jax
    import jax.numpy as jnp

    h = _embed(params, seqs, cfg, m_axis, s_axis)
    blocks = params["blocks"]

    def apply_stack(h, stack):
        def body(h, blk):
            return _block(blk, h, cfg, m_axis, s_axis), None

        h, _ = jax.lax.scan(body, h, stack)
        return h

    if p_axis is None:
        h = apply_stack(h, blocks)
    else:
        from jax.lax import axis_size
        from pio_tpu.parallel.pipeline import pipeline_apply

        # Microbatch so the pipe stays busy: with one microbatch every
        # stage computes discarded garbage for (n_pipe-1)/n_pipe of the
        # ticks. n_pipe microbatches ≈ 50% steady-state utilization.
        n_pipe = axis_size(p_axis)
        mb = h.shape[0]
        m = n_pipe if mb % n_pipe == 0 else 1
        hm = h.reshape(m, mb // m, *h.shape[1:])
        h = pipeline_apply(
            blocks, hm, lambda stack, x: apply_stack(x, stack),
            axis=p_axis,
        ).reshape(h.shape)
    return _ln(h, params["lnf_g"], params["lnf_b"])


def _vocab_parallel_ce(h, emb, targets, mask, m_axis):
    """CE over the vocab-sharded logits; [mb, T_loc] masked mean parts.

    Returns (sum_ce, sum_mask) — caller psums over data/seq axes.
    """
    import jax
    import jax.numpy as jnp

    logits = jnp.einsum(
        "btd,vd->btv", h, emb, preferred_element_type=jnp.float32
    )  # local vocab shard
    if m_axis is None:
        z = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
    else:
        rows = emb.shape[0]
        offset = jax.lax.axis_index(m_axis) * rows
        # The stability shift carries no gradient (it cancels in
        # logsumexp), and pmax has no differentiation rule — so detach the
        # local max and reduce it with the (linear, differentiable)
        # all_gather instead.
        gmax = jax.lax.all_gather(
            jax.lax.stop_gradient(logits.max(axis=-1)), m_axis
        ).max(axis=0)
        z = gmax + jnp.log(
            jax.lax.psum(
                jnp.exp(logits - gmax[..., None]).sum(axis=-1), m_axis
            )
        )
        tgt = vocab_parallel_target_gather(logits, targets, m_axis)
    ce = (z - tgt) * mask
    return ce.sum(), mask.sum()


def train_seqrec(
    mesh,
    sequences: np.ndarray,
    n_items: int,
    config: SeqRecConfig = SeqRecConfig(),
    checkpoint=None,
    checkpoint_every: int = 0,
    stats=None,
) -> SeqRecModel:
    """Next-item training over padded histories.

    Args:
        mesh: build_mesh() mesh — data/seq/model/pipe all honored; None →
            single-device.
        sequences: [n, T] int32, item ids ≥ 1, 0 = pad (right-padded).
        n_items: vocabulary size (ids are 1..n_items; row 0 = pad).
        checkpoint/checkpoint_every: optional
            pio_tpu.workflow.checkpoint.CheckpointManager + snapshot
            interval in steps; resumes from the newest snapshot on restart.
        stats: optional dict — streamed runs report the executor phases
            (h2d_s/device_s/h2d_bytes/encode_s) plus n_stream; all runs
            report place_s/steps_s (profiling only: phases serialize).

    Raises:
        DeviceBudgetExceeded: the params can't fit (single-chip or even
            sharded), or the staged epoch can't fit next to them and
            ``batch_size`` is 0 so the feed cannot stream (full-batch
            steps need the whole dataset resident).
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = config
    n_data = mesh_axis_size(mesh, "data")
    n_seq = mesh_axis_size(mesh, "seq")
    n_model = mesh_axis_size(mesh, "model")
    n_pipe = mesh_axis_size(mesh, "pipe")
    m_axis = "model" if mesh is not None else None
    s_axis = "seq" if mesh is not None else None
    p_axis = "pipe" if (mesh is not None and n_pipe > 1) else None

    if cfg.stream not in ("auto", "on", "off"):
        raise ValueError(
            f"stream must be auto/on/off, got {cfg.stream!r}"
        )
    if cfg.stream == "on" and cfg.batch_size <= 0:
        raise ValueError(
            "stream='on' needs batch_size > 0 (full-batch steps consume "
            "the whole dataset every step — nothing to stream)"
        )
    if cfg.n_heads % n_model:
        raise ValueError("n_heads must divide by the model axis")
    if cfg.n_layers % max(n_pipe, 1):
        raise ValueError("n_layers must divide by the pipe axis")
    if cfg.attention not in ("ring", "ulysses"):
        raise ValueError(
            f"unknown attention mode {cfg.attention!r}; use ring/ulysses"
        )
    if cfg.attention == "ulysses" and (cfg.n_heads // max(n_model, 1)) % max(
        n_seq, 1
    ):
        raise ValueError(
            "ulysses attention needs the per-device head count "
            f"(n_heads {cfg.n_heads} / model axis {n_model} = "
            f"{cfg.n_heads // max(n_model, 1)}) divisible by the seq axis "
            f"({n_seq}); use ring attention or adjust n_heads"
        )

    seqs = np.asarray(sequences, np.int32)
    n, t = seqs.shape
    t_pad = _round_up(min(t, cfg.max_len), n_seq)
    if t_pad > cfg.max_len:
        raise ValueError(
            f"max_len {cfg.max_len} not a multiple of seq axis {n_seq}"
        )
    buf = np.zeros((_round_up(n, n_data), t_pad), np.int32)
    if t <= t_pad:
        buf[:n, :t] = seqs
    else:
        # keep each row's NEWEST t_pad events: serving scores the tail of
        # the history (next_item_scores on codes[-max_len:]), so training
        # on the head would skew heavy users onto stale behavior
        for r in range(n):
            codes = seqs[r][seqs[r] > 0][-t_pad:]
            buf[r, : len(codes)] = codes
    seqs = buf

    if cfg.batch_size > 0:
        # minibatch SGD: contiguous row blocks with wraparound so every
        # scan step slices a full batch (the two_tower discipline)
        B = _round_up(min(cfg.batch_size, max(n, 1)), n_data)
        reps = _round_up(max(n, B), B)
        seqs = np.resize(seqs[:max(n, 1)], (reps, t_pad))
        n_batches = reps // B
    else:
        B, n_batches = seqs.shape[0], 1

    # next-item targets: target[t] = seq[t+1]; last position unsupervised
    targets = np.zeros_like(seqs)
    targets[:, :-1] = seqs[:, 1:]
    mask = (targets > 0) & (seqs > 0)

    vocab = _round_up(n_items + 1, n_model)  # +1 for the pad row
    tx = optax.adam(cfg.learning_rate)
    specs = param_specs(cfg)

    # placement accounting BEFORE anything lands on device (the
    # two_tower discipline): sharded params must fit the per-chip
    # budget, and the staged epoch must fit NEXT TO them or the feed
    # streams row spans instead
    from pio_tpu.parallel.partition import (
        DeviceBudgetExceeded,
        assert_device_budget,
        device_budget_bytes,
        per_device_nbytes,
    )

    def _skeleton():
        D, F, L, T = cfg.d_model, cfg.ffn, cfg.n_layers, cfg.max_len
        z = np.zeros((), np.float32)

        def bt(*shape):
            return np.broadcast_to(z, shape)

        return {
            "emb": bt(vocab, D),
            "pos": bt(T, D),
            "blocks": {
                "ln1_g": bt(L, D), "ln1_b": bt(L, D),
                "wq": bt(L, D, D), "wk": bt(L, D, D), "wv": bt(L, D, D),
                "wo": bt(L, D, D), "ln2_g": bt(L, D), "ln2_b": bt(L, D),
                "w1": bt(L, D, F), "b1": bt(L, F),
                "w2": bt(L, F, D), "b2": bt(L, D),
            },
            "lnf_g": bt(D), "lnf_b": bt(D),
        }

    skeleton = _skeleton()
    params_nbytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(skeleton)
    )
    if mesh is None:
        assert_device_budget(
            params_nbytes, 1, "seqrec params (single-chip placement)"
        )
        params_pd = params_nbytes
    else:
        params_pd = per_device_nbytes(mesh, skeleton, specs)
        assert_device_budget(params_pd, 1, "seqrec sharded params")
    # seqs + targets (int32) + mask (float32), sharded over data × seq
    staged_pd = -(-12 * seqs.shape[0] * t_pad // (n_data * n_seq))
    budget = device_budget_bytes()
    over = budget > 0 and params_pd + staged_pd > budget
    streamed = cfg.batch_size > 0 and (
        cfg.stream == "on" or (cfg.stream == "auto" and over)
    )
    if over and cfg.batch_size <= 0 and cfg.stream != "off":
        raise DeviceBudgetExceeded(
            f"seqrec staged epoch ({staged_pd} B/device) does not fit "
            f"beside the params ({params_pd} B/device) under "
            f"PIO_TPU_DEVICE_BUDGET_BYTES={budget}; set batch_size > 0 "
            f"so the feed can stream row spans"
        )
    n_stream = 0
    if streamed:
        from pio_tpu.parallel.stream import n_stream_chunks

        n_stream = max(
            2,
            n_stream_chunks(12 * seqs.shape[0] * t_pad,
                            "PIO_TPU_TRAIN_STREAM_MB", cap=256),
        )
        if budget > params_pd:
            n_stream = max(n_stream, -(-staged_pd // (budget - params_pd)))
        n_stream = min(n_batches, n_stream)
    if stats is not None:
        stats["n_stream"] = n_stream

    def global_loss(params, seqs, targets, mask):
        if mesh is None:
            h = _trunk(params, seqs, cfg, None, None, None)
            ce, denom = _vocab_parallel_ce(
                h, params["emb"], targets, mask, None
            )
            return ce / jnp.maximum(denom, 1.0)

        def inner(params, seqs, targets, mask):
            h = _trunk(params, seqs, cfg, m_axis, s_axis, p_axis)
            ce, denom = _vocab_parallel_ce(
                h, params["emb"], targets, mask, m_axis
            )
            ce = jax.lax.psum(ce, ("data", "seq"))
            denom = jax.lax.psum(denom, ("data", "seq"))
            return ce / jnp.maximum(denom, 1.0)

        dspec = P("data", "seq")
        return shard_map(
            inner,
            mesh=mesh,
            in_specs=(specs, dspec, dspec, dspec),
            out_specs=P(),
            check_vma=False,
        )(params, seqs, targets, mask)

    mask = mask.astype(np.float32)

    def _init_all():
        p = init_params(vocab, cfg)
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)

    from pio_tpu.obs import monotonic_s

    t0 = monotonic_s()
    dsh = None
    if mesh is not None:
        psh = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )
        # each device materializes only its shard — the vocab-sharded
        # table never exists unsharded on any chip
        params = jax.jit(_init_all, out_shardings=psh)()
        dsh = NamedSharding(mesh, P("data", "seq"))
    else:
        params = jax.jit(_init_all)()

    def _put_epoch(s_np, t_np, m_np):
        if mesh is None:
            return jnp.asarray(s_np), jnp.asarray(t_np), jnp.asarray(m_np)
        return tuple(
            jax.device_put(jnp.asarray(a), dsh) for a in (s_np, t_np, m_np)
        )

    seqs_d = targets_d = mask_d = None
    if not streamed:
        seqs_d, targets_d, mask_d = _put_epoch(seqs, targets, mask)
    if stats is not None:
        jax.block_until_ready((params, seqs_d, targets_d, mask_d))
        stats["place_s"] = monotonic_s() - t0

    def _scan_steps(state, n, batch_fn):
        step0, params, opt_state = state

        def step(carry, i):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(global_loss)(
                params, *batch_fn(i, step0)
            )
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), jnp.arange(n)
        )
        # per-step losses ride along for the telemetry plane; callers
        # that don't want them drop the array undereferenced (no sync)
        return (step0 + n, params, opt_state), losses

    @functools.partial(jax.jit, static_argnums=1)
    def chunk_full(state, n):
        return _scan_steps(
            state, n, lambda i, step0: (seqs_d, targets_d, mask_d)
        )

    @functools.partial(jax.jit, static_argnums=1)
    def chunk_staged(state, n):
        def batch_fn(i, step0):
            start = ((step0 + i) % n_batches) * B
            return tuple(
                jax.lax.dynamic_slice_in_dim(a, start, B)
                for a in (seqs_d, targets_d, mask_d)
            )

        return _scan_steps(state, n, batch_fn)

    @functools.partial(jax.jit, static_argnums=4)
    def chunk_span(state, s_span, t_span, m_span, n):
        def batch_fn(i, step0):
            return tuple(
                jax.lax.dynamic_slice_in_dim(a, i * B, B)
                for a in (s_span, t_span, m_span)
            )

        return _scan_steps(state, n, batch_fn)

    from pio_tpu.obs import devicewatch, trainwatch

    trainwatch.begin_algo(
        "seqrec", total_steps=cfg.steps, n_batches=n_batches,
        streamed=streamed, n_stream=n_stream,
        per_device_bytes=params_pd,
    )
    # lagged loss drain (the two_tower discipline): per-step losses come
    # back as device arrays and are fetched one chunk behind the
    # dispatch frontier; no recorder → dropped undereferenced.
    _pending: list = []
    _last_drain = [monotonic_s()]

    def _drain(keep: int = 0):
        while len(_pending) > keep:
            n_s, dev = _pending.pop(0)
            vals = np.asarray(jax.device_get(dev), np.float32)
            now = monotonic_s()
            trainwatch.record_steps(
                int(n_s), losses=[float(v) for v in vals],
                examples=int(n_s) * B, dur_s=now - _last_drain[0],
            )
            _last_drain[0] = now

    def _note_chunk(n_s, losses_dev, keep: int):
        if trainwatch.active_recorder() is None:
            return
        _pending.append((n_s, losses_dev))
        _drain(keep)

    if streamed:
        from pio_tpu.parallel.stream import (
            epoch_spans,
            span_bounds,
            stream_feed,
        )

        bounds = span_bounds(n_batches, n_stream)

        def chunk_fn(state, n):
            _drain()
            step0 = int(jax.device_get(state[0]))
            work = epoch_spans(step0, n, n_batches, bounds)

            def encode(span):
                b0, b1 = span
                return tuple(
                    np.ascontiguousarray(a[b0 * B:b1 * B])
                    for a in (seqs, targets, mask)
                )

            def dispatch(st, dev, i):
                b0, b1 = work[i]
                st, losses = chunk_span(st, *dev, b1 - b0)
                _note_chunk(b1 - b0, losses, keep=2)
                return st

            return stream_feed(
                work,
                encode=encode,
                put=lambda host, _i: _put_epoch(*host),
                init_carry=lambda: state,
                dispatch=dispatch,
                lookahead=2,
                stats=stats,
            )

    elif cfg.batch_size > 0:
        def chunk_fn(state, n):
            _drain()
            # compile attribution: n is static in the jitted chunk, so
            # each distinct chunk length is its own trainer program
            with devicewatch.compile_span(
                "train_step", key=("seqrec", "staged", B, int(n))
            ):
                state, losses = chunk_staged(state, n)
            _note_chunk(n, losses, keep=1)
            return state
    else:
        def chunk_fn(state, n):
            _drain()
            with devicewatch.compile_span(
                "train_step", key=("seqrec", "full", int(n))
            ):
                state, losses = chunk_full(state, n)
            _note_chunk(n, losses, keep=1)
            return state

    from pio_tpu.workflow.checkpoint import (
        run_chunked_steps,
        state_fingerprint,
    )

    # steps excluded: resume with a different total must still match.
    # stream normalized: streamed and staged feeds walk the SAME batch
    # schedule, so their snapshots are interchangeable
    fingerprint = state_fingerprint(
        "seqrec", dataclasses.replace(cfg, steps=0, stream="auto"),
        n_items, seqs.shape, int(seqs.sum()),
    )
    state = (jnp.int32(0), params, jax.jit(tx.init)(params))
    state = run_chunked_steps(
        state, cfg.steps, chunk_fn,
        checkpoint=checkpoint, checkpoint_every=checkpoint_every,
        fingerprint=fingerprint,
    )
    _drain()  # flush the telemetry tail (no-op without a recorder)
    fitted = state[1]

    # ONE fused pull (device_get returns host numpy): per-leaf
    # np.asarray paid a host link round trip per parameter tensor
    host = jax.device_get(fitted)
    host["emb"] = host["emb"][: n_items + 1]
    return SeqRecModel(params=host, n_items=n_items, config=cfg)
