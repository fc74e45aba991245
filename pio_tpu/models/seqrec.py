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

import contextlib
import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

from pio_tpu.models.seq_layers import (
    SELECTION,
    TOKEN_CHUNK,
    attn_impls,
    check_block,
    dense_layer,
    describe_params,
    expert_layer,
    experts_impl,
    group_norms,
    has_experts,
    init_from,
    is_moe,
    layer_kind,
    mixer_layer,
    mm,
    period_kinds,
    rms_norm,
    ssm_impl,
    unflatten,
    update_router_bias,
)
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
    # -- the block, by data (pio_tpu/models/seq_layers.py). The defaults
    # -- are the SASRec block; the widths below are read only by the kinds
    # -- that have them.
    #: "mha" (learned positions, full heads of d_model / n_heads), "mla"
    #: (multi-head latent attention, RoPE on a shared rope key) or "gqa"
    #: (grouped queries over ``kv_heads``, layers of the kinds in
    #: ``layer_pattern``, a sigmoid gate a query head where ``attn_gate``)
    attention_kind: str = "mha"
    #: "relu" (one biased two-matmul FFN of width ``ffn``) or "moe"
    #: (``dense_layers`` SwiGLU layers of width ``ffn``, then expert layers)
    ffn_kind: str = "relu"
    dense_layers: int = 0
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    #: the router's width, which of its experts this program holds, and
    #: how many a token selects
    n_experts: int = 64
    experts_first: int = 0
    experts_held: int = 64
    experts_per_token: int = 4
    expert_ffn: int = 1536
    shared_experts: int = 1
    routed_scale: float = 1.8
    #: step of the selection bias towards the mean load, after every step
    bias_update_rate: float = 1e-3
    #: multi-token-prediction modules (0 or 1) and their loss weight
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    #: matmul operand dtype of the mla/moe and gqa/moe blocks (float32
    #: accumulation, float32 master weights and Adam)
    compute_dtype: str = "bfloat16"
    #: how the router scores: "sigmoid_bias" (top-k of sigmoid + a moving
    #: selection bias) or "softmax" (top-k of the probabilities, no bias)
    router_kind: str = "sigmoid_bias"
    # -- the gqa block. Layer ``i`` is of kind ``layer_pattern[i mod its
    # -- length]``: "full" (causal; ``heads_full`` query heads; RoPE of
    # -- ``rope_theta`` on the first ``rotary_dim`` dims of a head, 0 = all,
    # -- with YaRN's frequencies and factor where ``yarn_factor`` > 1),
    # -- "window" (the last ``window`` keys, the query's own included;
    # -- ``heads_window`` query heads; RoPE of ``window_rope_theta`` on the
    # -- whole head) or "sparse" (a full layer's heads and RoPE over the keys
    # -- its indexer selects, below). ``kv_heads`` and both head counts are
    # -- the heads held here: with attention divided over chips by KV head, a
    # -- chip's share.
    layer_pattern: Tuple[str, ...] = ("full",)
    head_dim: int = 128
    kv_heads: int = 8
    heads_full: int = 48
    heads_window: int = 48
    window: int = 512
    window_rope_theta: float = 1e4
    rotary_dim: int = 0
    yarn_factor: float = 1.0
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    #: the gqa layers rotate ``q`` and ``k`` by position / scale every query
    #: head's output by its own sigmoid gate; false = no position encoding /
    #: no gate (and no ``g_proj``)
    attn_rope: bool = True
    attn_gate: bool = True
    #: per-head RMSNorm (a gain of ``head_dim``, ``norm_eps``) on ``q`` and
    #: ``k`` before RoPE, in every gqa layer
    attn_qk_norm: bool = False
    # -- a "sparse" layer of the gqa block (DeepSeek Sparse Attention's
    # -- form): a lightning indexer of ``index_heads`` heads of
    # -- ``index_head_dim`` over one shared key scores every earlier key;
    # -- each query attends to the ``index_topk`` keys it scores highest
    # -- (ties to the earlier key), the selection shared by every head; the
    # -- indexer learns from its own KL loss alone (``INDEX_LOSS_WEIGHT``),
    # -- against the main attention's head-summed distribution over the
    # -- selection.
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    #: the experts and the shared expert: "swiglu" (three matrices,
    #: ``W_down(silu(W_gate x) * W_up x)``) or "relu2" (two,
    #: ``W_down relu(W_up x)^2``)
    expert_act: str = "swiglu"
    #: the routed experts' grouped matmul: "ragged_dot" (XLA's) or "gmm"
    #: (the Pallas TPU kernel, whose time follows the rows routed here;
    #: off a TPU it is "ragged_dot" all the same)
    expert_matmul: str = "ragged_dot"
    # -- a layer that is one mixer alone, ``h + mixer(norm(h))``. Layer ``i``
    # -- is ``mixer_pattern[i]`` (``n_layers`` of them): "mamba" (a Mamba-2 mixer of
    # -- ``ssm_heads`` heads of ``ssm_head_dim`` channels over ``ssm_groups``
    # -- groups of state ``ssm_state``, a causal depthwise convolution of
    # -- ``ssm_conv`` taps, the recurrence by chunks of ``ssm_chunk``), "moe"
    # -- (the expert feed-forward), "attn" (gqa with ``heads_full`` query
    # -- heads) or "mlp" (the dense SwiGLU of width ``ffn`` with a norm of its
    # -- own); it need hold no "moe". Empty = the layers are attention and a
    # -- feed-forward part.
    # -- ``ssm_dt_*`` draw a mamba layer's initial step sizes (log-uniform
    # -- in [min, max], floored).
    mixer_pattern: Tuple[str, ...] = ()
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 1e-3
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # -- four scalars of the model and its table, for any moe block, each
    # -- applied only where set: ``embed_scale`` multiplies the looked-up
    # -- rows, ``residual_scale`` what every layer (every mixer) adds to the
    # -- stream, ``attn_scale`` replaces the scores' head width ** -0.5 (0 =
    # -- that), ``logit_scale`` multiplies the logits before the log-sum-exp
    # -- (and the served scores); ``tied_head`` reads the logits from the
    # -- embedding table: no ``head``, one gradient (the sum of both uses)
    # -- and one Adam state, the table drawn as a head is.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logit_scale: float = 1.0
    tied_head: bool = False

    def __post_init__(self):
        # engine.json gives a list; the config keys the kept programs
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "mixer_pattern", tuple(self.mixer_pattern))


@dataclasses.dataclass
class SeqRecModel:
    """Trained transformer; host copies of params for persistence/serving."""

    params: dict  # layer-stacked pytree (host numpy)
    n_items: int
    config: SeqRecConfig
    #: what the training call saw, per optimizer step (the moe blocks only):
    #: ``l_main``/``l_mtp`` [steps], ``grad_norm`` [steps, parameter groups
    #: of ``seq_layers.groups_of``], and per expert layer (the MTP module's
    #: last) ``pairs`` (token, held expert) routed here, ``dropped`` (those
    #: of them the grouped matmuls were not given: 0 for a dropless layer),
    #: ``passes`` (of the grouped matmuls that ran: more than one where the
    #: held pairs passed the first's rows) and the rows they ``staged``,
    #: ``load_max_over_mean`` over all experts, ``bias_max`` (a router with
    #: a selection bias), ``window_tiles``/``causal_tiles`` (the gqa block:
    #: score tiles its window layers visited, and what causal layers of
    #: their length visit), ``l_index``, ``l_select`` and ``DSA_COUNTERS``
    #: (sparse layers: the indexers' loss, the selected keys' positions
    #: summed over the layers and queries, a checksum of the selection that
    #: the benchmark compares, the (query, key) pairs selected, the key blocks
    #: the attention's loops ran and those causal loops run, the rows tied
    #: at the ``index_topk``-th score), ``ssm_chunks``/``ssm_head_blocks``/
    #: ``ssm_state_absmax`` (mamba layers: the chunks their carrying loops
    #: ran, the turns of their scans' maps, the largest carried state). A
    #: block without an expert layer holds the expert columns ``[steps, 0]``
    trace: Optional[dict] = None
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

            cfg = self.config

            @jax.jit
            def fwd(params, seqs):
                # score from the last real position of each row
                lengths = (seqs > 0).sum(axis=1)
                at = jnp.maximum(lengths - 1, 0)[:, None, None]
                if is_moe(cfg):
                    # serving runs no MTP module and keeps no counters
                    h, _ = _latent_trunk(params, seqs, cfg, None, None)
                    last = rms_norm(
                        jnp.take_along_axis(h, at, axis=1)[:, 0],
                        params["lnf_g"], cfg.norm_eps)
                    scores = mm(last, _head_table(params, cfg).T,
                                jnp.dtype(cfg.compute_dtype))
                    return (scores if cfg.logit_scale == 1.0
                            else scores * cfg.logit_scale)
                h = _trunk(params, seqs, cfg, None, None, None)
                last = jnp.take_along_axis(h, at, axis=1)[:, 0]
                return jnp.dot(
                    last,
                    params["emb"].T,
                    preferred_element_type=jnp.float32,
                )

            self._serve_cache = (fwd, params)
        fwd, params = self._serve_cache
        return np.asarray(fwd(params, jnp.asarray(histories, jnp.int32)))


def init_params(vocab: int, cfg: SeqRecConfig):
    """Layer-stacked parameter pytree (leading dim = layers of the group),
    drawn as :func:`seq_layers.describe_params` says."""
    return init_from(describe_params(vocab, cfg), cfg.seed)


def param_specs(cfg: SeqRecConfig):
    """PartitionSpecs: ep for emb/head and the experts, tp for the SASRec
    block's heads/ffn, pp over its stack — derived from the partition-rule
    registry (``rules_for("seqrec")``) over the described tree."""
    from pio_tpu.parallel.partition import match_partition_rules, rules_for

    skeleton = unflatten(
        {path: np.empty(0) for path in describe_params(1, cfg)})
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


#: the MTP module's own leaves; its other leaves are one expert layer's
_MTP_OWN = ("eh_proj", "h_norm", "e_norm", "lnf_g")


def _embedded(params, ids, cfg, m_axis):
    """The rows of the (vocab-parallel) table, times ``embed_scale``."""
    rows = vocab_parallel_lookup(params["emb"], ids, m_axis)
    return rows if cfg.embed_scale == 1.0 else rows * cfg.embed_scale


def _head_table(params, cfg):
    """The table the logits read: the embedding's under ``tied_head``."""
    return params["emb"] if cfg.tied_head else params["head"]


def _latent_trunk(params, seqs, cfg, m_axis, s_axis):
    """Embed + the dense layers + the expert layers of a moe block, one
    ``jax.checkpoint`` a layer -> ``(h [mb, T_loc, D] float32 before the
    final norm, counters stacked over the expert layers)``. The expert
    layers are scanned a period at a time; the period is data
    (``period_kinds``): each of its layers takes the next slice of its
    kind's stack (``window/*`` and ``full/*`` have unlike shapes; the
    mla/moe block's period is one layer of ``blocks``). Layers that are one
    mixer alone (``mixer_pattern``) go through :func:`_mixer_layers`."""
    import jax
    import jax.numpy as jnp

    h = _embedded(params, seqs, cfg, m_axis)

    # the barrier keeps a layer's float32 -> compute-dtype weight casts
    # inside the layer: hoisted out of the scan they stand for every layer
    # at once (1.1 GB of the v5e's 16 at the published widths)
    # (inside the checkpoint, so that the backward pass's recomputation is
    # held the same way)
    # a sparse layer's selection (32 MB of bits a layer at 16k) is kept for
    # the backward pass rather than found again: its top-k is no gradient's
    def layer(fn, kind):
        policy = (jax.checkpoint_policies.save_only_these_names(SELECTION)
                  if kind == "sparse" else None)
        return jax.checkpoint(lambda blk, h: fn(
            jax.lax.optimization_barrier(blk), h, cfg, m_axis, s_axis, kind),
            policy=policy)

    if cfg.mixer_pattern:
        return _mixer_layers(params, h, cfg, layer)
    dense = {}
    if "dense" in params:
        h, dense = jax.lax.scan(
            lambda h, blk: layer(dense_layer, layer_kind(cfg, 0))(blk, h),
            h, params["dense"])
    kinds = period_kinds(cfg)

    def period(h, blks):
        taken, counters = dict.fromkeys(blks, 0), []
        for kind in kinds:
            blk = jax.tree.map(lambda a: a[taken[kind]], blks[kind])
            taken[kind] += 1
            h, c = layer(expert_layer, kind)(blk, h)
            counters.append(c)
        return h, jax.tree.map(lambda *a: jnp.stack(a), *counters)

    if len(kinds) == 1:
        # a period of one layer scans its stack as it stands: through the
        # reshape below XLA converts the whole stack's weights before the
        # scan, barrier or not (0.6 GB at the mla/moe cell, whose compiled
        # step this keeps instruction for instruction)
        h, counters = jax.lax.scan(
            lambda h, blk: layer(expert_layer, kinds[0])(blk, h),
            h, params[kinds[0]])
    else:
        stacks = {  # [layers of the kind, ..] -> [periods, its layers in one, ..]
            kind: jax.tree.map(
                lambda a: a.reshape(-1, kinds.count(kind), *a.shape[1:]),
                params[kind])
            for kind in set(kinds)
        }
        h, counters = jax.lax.scan(period, h, stacks)
        counters = jax.tree.map(  # [periods, layers a period, ..] -> [layers, ..]
            lambda a: a.reshape(-1, *a.shape[2:]), counters)
    for name in ("tiles", "dsa", "index_kl", "l_select"):
        if name in counters:  # one sum over every layer, the dense ones too
            counters[name] = sum(
                c[name].sum(axis=0) for c in (dense, counters) if name in c)
    return h, counters


def _mixer_layers(params, h, cfg, layer):
    """The layers of a block of single mixers -> ``(h, counters)``: layer
    after layer as ``mixer_pattern`` names them, each taking the next slice
    of its kind's stack (``mamba/*``, ``moe/*``, ``attn/*``, ``mlp/*``).
    Unlike kinds of layer share no counters, so they are stacked by kind:
    the ``moe`` layers' as the expert layers' of the other blocks (empty
    columns where the pattern has none), the ``mamba`` layers' summed
    (``ssm_chunks``, ``ssm_head_blocks``) and maximised
    (``ssm_state_absmax``)."""
    import jax
    import jax.numpy as jnp

    taken = dict.fromkeys(cfg.mixer_pattern, 0)
    by_kind = {kind: [] for kind in taken}
    for kind in cfg.mixer_pattern:
        blk = jax.tree.map(lambda a: a[taken[kind]], params[kind])
        taken[kind] += 1
        h, c = layer(mixer_layer, kind)(blk, h)
        by_kind[kind].append(c)
    if "moe" in by_kind:
        counters = jax.tree.map(lambda *a: jnp.stack(a), *by_kind["moe"])
    else:  # what ``seq_layers.moe`` counts, over no layer
        counters = {name: jnp.zeros((0,), jnp.float32) for name in (
            "pairs", "dropped", "passes", "staged")}
        counters["load"] = jnp.zeros((0, cfg.n_experts), jnp.float32)
    if "mamba" in by_kind:
        for name in ("ssm_chunks", "ssm_head_blocks"):
            counters[name] = sum(c[name] for c in by_kind["mamba"])
        counters["ssm_state_absmax"] = jnp.stack(
            [c["ssm_state_absmax"] for c in by_kind["mamba"]]).max()
    return h, counters


def _mtp_hidden(params, h, next_ids, cfg, m_axis, s_axis):
    """The depth-1 MTP module (DeepSeek-V3 report, 2.2): ``W_eh [norm(h_t)
    ; norm(Emb(x_{t+1}))]`` through one more expert layer. The embedding
    and the head are the main model's."""
    import jax
    import jax.numpy as jnp

    mtp = params["mtp"]
    e = _embedded(params, next_ids, cfg, m_axis)
    both = jnp.concatenate(
        [rms_norm(h, mtp["h_norm"], cfg.norm_eps),
         rms_norm(e, mtp["e_norm"], cfg.norm_eps)], axis=-1)
    h2 = mm(both, mtp["eh_proj"], jnp.dtype(cfg.compute_dtype))
    blk = {k: v[0] for k, v in mtp.items() if k not in _MTP_OWN}
    return jax.checkpoint(
        lambda blk, h: expert_layer(blk, h, cfg, m_axis, s_axis, "mtp")
    )(blk, h2)


def _chunked_ce(h, norm_g, head, targets, mask, cfg, m_axis):
    """Final RMSNorm, the head (the model's own, or its embedding table
    where tied: :func:`_head_table`), ``logit_scale`` and the cross-entropy,
    ``TOKEN_CHUNK`` tokens at a time under ``jax.checkpoint``: ``[B, T, V]``
    in float32 never stands whole, forward or backward. Returns ``(sum_ce,
    sum_mask)`` like :func:`_vocab_parallel_ce`."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import pick_block

    with jax.named_scope("seq.head"):
        cd = jnp.dtype(cfg.compute_dtype)
        B, T, D = h.shape
        chunk = pick_block(B * T, TOKEN_CHUNK)
        head = head.astype(cd)

        @jax.checkpoint
        def one(hc, tc, mc):
            x = rms_norm(hc, norm_g, cfg.norm_eps).astype(cd)
            return _vocab_parallel_ce(x[None], head, tc[None], mc[None],
                                      m_axis, cfg.logit_scale)

        def body(acc, xs):
            ce, den = one(*xs)
            return (acc[0] + ce, acc[1] + den), None

        (ce, den), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)),
            (h.reshape(-1, chunk, D), targets.reshape(-1, chunk),
             mask.reshape(-1, chunk)),
        )
        return ce, den


def _latent_loss_sums(params, batch, cfg, m_axis, s_axis):
    """Local sums of one batch through the mla/moe model: ``{"ce", "den",
    "ce2", "den2"}`` (``"kl", "kl_den"``: the sparse layers' indexer loss
    and the positions it is summed over) and the expert layers' counters
    (the MTP module's layer last). Callers psum over data/seq and
    divide."""
    import jax
    import jax.numpy as jnp

    seqs, targets, mask, targets2, mask2 = batch
    h, counters = _latent_trunk(params, seqs, cfg, m_axis, s_axis)
    ce, den = _chunked_ce(
        h, params["lnf_g"], _head_table(params, cfg), targets, mask, cfg,
        m_axis)
    sums = {"ce": ce, "den": den}
    if "index_kl" in counters:  # the sparse layers' indexers, every position
        sums["kl"] = counters.pop("index_kl")
        sums["kl_den"] = jnp.float32(seqs.size)
    if cfg.mtp_depth:
        with jax.named_scope("seq.mtp"):
            h2, c2 = _mtp_hidden(params, h, targets, cfg, m_axis, s_axis)
            sums["ce2"], sums["den2"] = _chunked_ce(
                h2, params["mtp"]["lnf_g"], _head_table(params, cfg), targets2,
                mask2, cfg, m_axis)
        counters = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]]), counters, c2)
    return sums, counters


def _latent_loss(sums, counters, cfg):
    """``(loss, aux)`` from the global sums."""
    import jax.numpy as jnp

    l_main = sums["ce"] / jnp.maximum(sums["den"], 1.0)
    aux = dict(counters, l_main=l_main)
    loss = l_main
    if cfg.mtp_depth:
        aux["l_mtp"] = sums["ce2"] / jnp.maximum(sums["den2"], 1.0)
        loss = loss + cfg.mtp_weight * aux["l_mtp"]
    if "kl" in sums:  # summed over the layers, averaged over the positions
        aux["l_index"] = sums["kl"] / jnp.maximum(sums["kl_den"], 1.0)
        loss = loss + INDEX_LOSS_WEIGHT * aux["l_index"]
    return loss, aux


def _vocab_parallel_ce(h, emb, targets, mask, m_axis, scale: float = 1.0):
    """CE over the vocab-sharded logits (times ``scale`` where it is not 1);
    [mb, T_loc] masked mean parts.

    Returns (sum_ce, sum_mask) — caller psums over data/seq axes.
    """
    import jax
    import jax.numpy as jnp

    logits = jnp.einsum(
        "btd,vd->btv", h, emb, preferred_element_type=jnp.float32
    )  # local vocab shard
    if scale != 1.0:
        logits = logits * scale
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


class _Programs(NamedTuple):
    """The jitted programs of one (block, mesh, sizes)."""

    init: object  # (seed) -> params, placed as ``param_specs`` says
    opt_init: object  # (params) -> Adam's state
    #: (state, epoch, n) / (state, epoch, n) / (state, span, n) ->
    #: (state, losses [n], aux); ``n`` static; the first two donate ``state``
    chunk_full: object
    chunk_staged: object
    chunk_span: object


@functools.lru_cache(maxsize=8)
def _programs(cfg: SeqRecConfig, mesh, vocab: int, B: int,
              n_batches: int) -> _Programs:
    """Built once and kept: ``train_seqrec`` defines no jitted function of
    its own, so a second call with the same block, mesh and sizes traces
    and compiles nothing. ``cfg`` comes with ``seed`` and ``steps`` zeroed:
    the seed is ``init``'s argument and the step count the steppers'."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    latent = is_moe(cfg)
    m_axis = "model" if mesh is not None else None
    s_axis = "seq" if mesh is not None else None
    p_axis = "pipe" if mesh_axis_size(mesh, "pipe") > 1 else None
    tx = optax.adam(cfg.learning_rate)
    specs = param_specs(cfg)

    def local_loss(params, batch, m_axis, s_axis, p_axis, psum):
        """``(loss, aux)`` from one device's slice; ``psum`` closes the
        sums over data and seq."""
        if latent:
            sums, counters = _latent_loss_sums(
                params, batch, cfg, m_axis, s_axis)
            absmax = counters.pop("ssm_state_absmax", None)
            sums, counters = psum((sums, counters))
            if absmax is not None:  # a maximum over the rows, not a sum
                counters["ssm_state_absmax"] = absmax if mesh is None else (
                    jax.lax.all_gather(absmax, ("data", "seq")).max())
            return _latent_loss(sums, counters, cfg)
        seqs, targets, mask = batch
        h = _trunk(params, seqs, cfg, m_axis, s_axis, p_axis)
        ce, denom = psum(_vocab_parallel_ce(
            h, params["emb"], targets, mask, m_axis))
        return ce / jnp.maximum(denom, 1.0), {}

    def global_loss(params, batch):
        if mesh is None:
            return local_loss(params, batch, None, None, None, lambda x: x)
        dspec = P("data", "seq")
        return shard_map(
            lambda params, batch: local_loss(
                params, batch, m_axis, s_axis, p_axis,
                lambda x: jax.lax.psum(x, ("data", "seq"))),
            mesh=mesh,
            in_specs=(specs, (dspec,) * len(batch)),
            out_specs=P(),
            check_vma=False,
        )(params, batch)

    def init_all(seed):
        p = init_from(describe_params(vocab, cfg), seed)
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)

    if mesh is None:
        init = jax.jit(init_all)
    else:
        init = jax.jit(init_all, out_shardings=jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        ))

    def scan_steps(state, n, batch_fn):
        step0, params, opt_state = state

        def step(carry, i):
            params, opt_state = carry
            (loss, aux), grads = jax.value_and_grad(
                global_loss, has_aux=True)(params, batch_fn(i, step0))
            with jax.named_scope("seq.opt"):
                if latent:
                    aux["grad_norm"] = group_norms(grads, cfg)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                if latent:
                    params, aux = _after_step(params, aux, cfg)
            return (params, opt_state), (loss, aux)

        (params, opt_state), (losses, aux) = jax.lax.scan(
            step, (params, opt_state), jnp.arange(n)
        )
        # per-step losses ride along for the telemetry plane; callers
        # that don't want them drop the array undereferenced (no sync)
        return (step0 + n, params, opt_state), losses, aux

    # the state is donated: beside 16 B a parameter there is no room for a
    # second copy of the weights and Adam's moments
    @functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
    def chunk_full(state, epoch, n):
        return scan_steps(state, n, lambda i, step0: epoch)

    @functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
    def chunk_staged(state, epoch, n):
        def batch_fn(i, step0):
            start = ((step0 + i) % n_batches) * B
            return tuple(
                jax.lax.dynamic_slice_in_dim(a, start, B) for a in epoch
            )

        return scan_steps(state, n, batch_fn)

    # not donated: the feed keeps each span's carry to wait on it
    @functools.partial(jax.jit, static_argnums=2)
    def chunk_span(state, span, n):
        def batch_fn(i, step0):
            return tuple(
                jax.lax.dynamic_slice_in_dim(a, i * B, B) for a in span
            )

        return scan_steps(state, n, batch_fn)

    return _Programs(init, jax.jit(tx.init), chunk_full, chunk_staged,
                     chunk_span)


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
            single-device. The mla/moe block takes data, seq (ring over
            the same blocked attention) and model (experts and vocabulary
            shard, tokens stay replicated); it has no pipe split yet.
        sequences: [n, T] int32, item ids ≥ 1, 0 = pad (right-padded).
        n_items: vocabulary size (ids are 1..n_items; row 0 = pad).
        checkpoint/checkpoint_every: optional
            pio_tpu.workflow.checkpoint.CheckpointManager + snapshot
            interval in steps; resumes from the newest snapshot on restart.
        stats: optional dict — streamed runs report the executor phases
            (h2d_s/device_s/h2d_bytes/encode_s) plus n_stream; all runs
            report pack_s/place_s/steps_s/readback_s (profiling only:
            phases serialize). On a TPU the steps of a ``stats`` call are
            also traced and reduced to the program's ``seq.*`` scopes
            (``device_scope_s``, ``device_unscoped_s``, ``device_busy_s``,
            ``device_program_s``, and ``device_renamed_s``: the part of
            the unscoped seconds in operations XLA renamed, as the grouped
            expert matmuls' ``ragged-dot-none``, whose ``seq.moe/experts``
            scope the TPU compiler drops: pio_tpu/obs/profile.py), ``xla`` holds
            the compile counts with ``in_call``, and ``counters`` the
            moe blocks' routed pairs, load ratio and dropped pairs, the
            passes of their grouped matmuls that ran and the rows those
            staged (``moe_passes``, ``moe_staged_rows``), the
            largest selection bias (a router that has one), the gqa
            block's ``window_tiles`` and ``causal_tiles``, its sparse
            layers' ``selected_pairs``, ``sparse_key_blocks``,
            ``causal_key_blocks`` and ``topk_boundary_ties``, and the mamba
            layers' ``ssm_chunks``, ``ssm_head_blocks`` (the turns of
            their scans' maps) and ``ssm_state_absmax`` (the same counters
            stand in ``/train.json``); a moe block
            also says which grouped matmul its routed experts ran
            (``experts_impl``: ``seq_layers.experts_impl``; ``none`` for a
            block of single mixers without an expert layer) and what ran
            the attention tiles of each kind of its attention layers
            (``attn_impl``: ``{"mla"}`` or ``{"full", "window",
            "sparse"}`` ->
            ``pallas`` / ``xla``, ``ring.attention_impl``; a sparse block
            adds ``index`` where its indexer's scores run on their kernels,
            ``seq_layers.index_impl``) and the chunks of
            its Mamba-2 mixers (``ssm_impl``: ``pallas`` / ``xla``,
            ``seq_layers.ssd_impl``; ``none`` without a mamba layer); both
            in ``/train.json`` and the run record too.

    Raises:
        DeviceBudgetExceeded: the params can't fit (single-chip or even
            sharded), or the staged epoch can't fit next to them and
            ``batch_size`` is 0 so the feed cannot stream (full-batch
            steps need the whole dataset resident). The check counts the
            parameters alone (4 B each): not Adam's two moments, the
            gradients or the activations, which the compiler places.
    """
    from pio_tpu.obs.tracing import PROCESS

    with PROCESS.train_call(stats):
        return _train_seqrec(mesh, sequences, n_items, config, checkpoint,
                             checkpoint_every, stats)


def _train_seqrec(mesh, sequences, n_items, config, checkpoint,
                  checkpoint_every, stats) -> SeqRecModel:
    """The call itself, inside :meth:`ProcessTimeline.train_call`. Its host
    work stands in leaf spans that tile it (:func:`pio_tpu.obs.active_span`):
    ``seq.pack``, ``seq.build`` (the placement accounting and the steppers,
    looked up or made), ``seq.init`` (the parameters and the epoch placed;
    again for Adam's state), ``seq.steps`` (the chunks' dispatch: on a
    first call JAX's trace, lowering and compile or cache load of the
    stepper; in a ``stats`` call also the wait for the device and the
    scope capture) and ``seq.readback`` (the wait for the device, but for
    a ``stats`` call, and the transfer). ``stats``' phase seconds are
    those spans'."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pio_tpu.obs import active_span, devicewatch, monotonic_s, trainwatch
    from pio_tpu.obs.profile import ScopeCapture, device_stats

    cfg = config
    latent = is_moe(cfg)
    n_data = mesh_axis_size(mesh, "data")
    n_seq = mesh_axis_size(mesh, "seq")
    n_model = mesh_axis_size(mesh, "model")
    n_pipe = mesh_axis_size(mesh, "pipe")
    p_axis = "pipe" if (mesh is not None and n_pipe > 1) else None

    check_block(cfg, n_seq)
    if cfg.stream not in ("auto", "on", "off"):
        raise ValueError(
            f"stream must be auto/on/off, got {cfg.stream!r}"
        )
    if cfg.stream == "on" and cfg.batch_size <= 0:
        raise ValueError(
            "stream='on' needs batch_size > 0 (full-batch steps consume "
            "the whole dataset every step — nothing to stream)"
        )
    if cfg.attention not in ("ring", "ulysses"):
        raise ValueError(
            f"unknown attention mode {cfg.attention!r}; use ring/ulysses"
        )
    if latent:
        if p_axis is not None:
            raise ValueError(
                "the moe blocks have no pipe split: their stages differ "
                "(dense, expert, MTP) and pipeline_apply takes like stages"
            )
        if cfg.attention != "ring":
            raise ValueError("the moe blocks ride ring attention")
        if has_experts(cfg) and cfg.experts_held % n_model:
            raise ValueError("experts_held must divide by the model axis")
    else:
        if cfg.n_heads % n_model:
            raise ValueError("n_heads must divide by the model axis")
        if cfg.n_layers % max(n_pipe, 1):
            raise ValueError("n_layers must divide by the pipe axis")
    if cfg.attention == "ulysses" and (cfg.n_heads // max(n_model, 1)) % max(
        n_seq, 1
    ):
        raise ValueError(
            "ulysses attention needs the per-device head count "
            f"(n_heads {cfg.n_heads} / model axis {n_model} = "
            f"{cfg.n_heads // max(n_model, 1)}) divisible by the seq axis "
            f"({n_seq}); use ring attention or adjust n_heads"
        )

    with active_span("seq.pack") as packed:
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
            # keep each row's NEWEST t_pad events: serving scores the tail
            # of the history (next_item_scores on codes[-max_len:]), so
            # training on the head would skew heavy users onto stale
            # behavior
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

        # next-item targets: target[t] = seq[t+1]; last position
        # unsupervised
        targets = np.zeros_like(seqs)
        targets[:, :-1] = seqs[:, 1:]
        mask = (targets > 0) & (seqs > 0)
        epoch = [seqs, targets, mask.astype(np.float32)]
        if latent:
            # the MTP module's targets, one more event ahead
            targets2 = np.zeros_like(seqs)
            targets2[:, :-2] = seqs[:, 2:]
            epoch += [targets2, (mask & (targets2 > 0)).astype(np.float32)]
        epoch = tuple(epoch)
    if stats is not None:
        stats["pack_s"] = packed.seconds

    with active_span("seq.build"):
        vocab = _round_up(n_items + 1, n_model)  # +1 for the pad row
        specs = param_specs(cfg)

        # placement accounting BEFORE anything lands on device (the
        # two_tower discipline): sharded params must fit the per-chip
        # budget, and the staged epoch must fit NEXT TO them or the feed
        # streams row spans instead. What is counted is the parameters, 4 B
        # each, as they shard; Adam's moments (8 B more) are not.
        from pio_tpu.parallel.partition import (
            DeviceBudgetExceeded,
            assert_device_budget,
            device_budget_bytes,
            per_device_nbytes,
        )

        z = np.zeros((), np.float32)
        skeleton = unflatten({
            path: np.broadcast_to(z, leaf.shape)
            for path, leaf in describe_params(vocab, cfg).items()
        })
        params_nbytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(skeleton)
        )
        if mesh is None:
            assert_device_budget(
                params_nbytes, 1,
                "seqrec params alone, no optimizer state (single-chip placement)"
            )
            params_pd = params_nbytes
        else:
            params_pd = per_device_nbytes(mesh, skeleton, specs)
            assert_device_budget(
                params_pd, 1, "seqrec sharded params alone, no optimizer state")
        # seqs + targets (int32) + masks (float32), sharded over data × seq
        row_bytes = 4 * len(epoch)
        staged_pd = -(-row_bytes * seqs.shape[0] * t_pad // (n_data * n_seq))
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
                n_stream_chunks(row_bytes * seqs.shape[0] * t_pad,
                                "PIO_TPU_TRAIN_STREAM_MB", cap=256),
            )
            if budget > params_pd:
                n_stream = max(n_stream, -(-staged_pd // (budget - params_pd)))
            n_stream = min(n_batches, n_stream)
        if stats is not None:
            stats["n_stream"] = n_stream
            if latent:
                stats["experts_impl"] = experts_impl(jax.default_backend(), cfg)

        # the programs are built once for these sizes and kept: a second call
        # traces and compiles nothing
        prog = _programs(dataclasses.replace(cfg, seed=0, steps=0), mesh, vocab,
                         B, n_batches)
    dsh = None if mesh is None else NamedSharding(mesh, P("data", "seq"))

    def _put_epoch(*arrays):
        if mesh is None:
            return tuple(jnp.asarray(a) for a in arrays)
        return tuple(jax.device_put(jnp.asarray(a), dsh) for a in arrays)

    with active_span("seq.init") as placed:
        # with a mesh each device materializes only its shard — the
        # vocab-sharded table never exists unsharded on any chip
        params = prog.init(jnp.int32(cfg.seed))
        epoch_d = None
        if not streamed:
            epoch_d = _put_epoch(*epoch)
        if stats is not None:
            jax.block_until_ready((params, epoch_d))
    if stats is not None:
        stats["place_s"] = placed.seconds

    trainwatch.begin_algo(
        "seqrec", total_steps=cfg.steps, n_batches=n_batches,
        streamed=streamed, n_stream=n_stream,
        per_device_bytes=params_pd,
    )
    if latent:
        attn_impl = attn_impls(jax.default_backend(), cfg, t_pad // n_seq)
        trainwatch.set_attn_impl(attn_impl)
        ssm = ssm_impl(jax.default_backend(), cfg, t_pad // n_seq)
        trainwatch.set_ssm_impl(ssm)
        if stats is not None:
            stats["attn_impl"] = attn_impl
            stats["ssm_impl"] = ssm
    # lagged loss drain (the two_tower discipline): per-step losses come
    # back as device arrays and are fetched one chunk behind the
    # dispatch frontier; no recorder → dropped undereferenced.
    _pending: list = []
    _last_drain = [monotonic_s()]
    _aux: list = []  # the mla/moe block's per-step counters, on device

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

    def _note_chunk(n_s, losses_dev, aux_dev, keep: int):
        if latent:
            _aux.append(aux_dev)
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
                    np.ascontiguousarray(a[b0 * B:b1 * B]) for a in epoch
                )

            def dispatch(st, dev, i):
                b0, b1 = work[i]
                st, losses, aux = prog.chunk_span(st, dev, b1 - b0)
                _note_chunk(b1 - b0, losses, aux, keep=2)
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
                state, losses, aux = prog.chunk_staged(state, epoch_d, n)
            _note_chunk(n, losses, aux, keep=1)
            return state
    else:
        def chunk_fn(state, n):
            _drain()
            with devicewatch.compile_span(
                "train_step", key=("seqrec", "full", int(n))
            ):
                state, losses, aux = prog.chunk_full(state, epoch_d, n)
            _note_chunk(n, losses, aux, keep=1)
            return state

    with active_span("seq.init"):  # Adam's state, and what a snapshot keys on
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
        state = (jnp.int32(0), params, prog.opt_init(params))
    xla_before = devicewatch.xla_totals() if stats is not None else None
    # a ``stats`` call waits for the device on both sides of its steps and
    # traces them; a plain call's span ends with the last chunk's dispatch
    capture = ScopeCapture("seq.") if stats is not None else None
    with active_span("seq.steps") as stepped:
        if stats is not None:
            jax.block_until_ready(state)
        with capture or contextlib.nullcontext():
            state = run_chunked_steps(
                state, cfg.steps, chunk_fn,
                checkpoint=checkpoint, checkpoint_every=checkpoint_every,
                fingerprint=fingerprint,
            )
            if stats is not None:
                jax.block_until_ready(state)
    if stats is not None:
        stats["steps_s"] = stepped.seconds

    with active_span("seq.readback") as read:
        _drain()  # flush the telemetry tail (no-op without a recorder)
        # ONE fused pull (device_get returns host numpy): per-leaf
        # np.asarray paid a host link round trip per parameter tensor
        host, aux = jax.device_get((state[1], _aux))
        for table in ("emb", "head"):
            if table in host:
                host[table] = host[table][: n_items + 1]
        trace = None
        if aux:
            trace = {
                k: np.concatenate([np.asarray(a[k]) for a in aux])
                for k in aux[0] if k != "load"
            }
            counters = _counters(trace)
            trainwatch.set_counters(counters)  # /train.json
    if stats is not None:
        stats["readback_s"] = read.seconds
        stats.update(device_stats(capture.result))
        xla = devicewatch.xla_totals()
        if xla is not None and xla_before is not None:
            stats["xla"] = dict(
                xla, in_call={k: xla[k] - xla_before[k] for k in xla})
        if trace is not None:
            stats["counters"] = counters
    return SeqRecModel(params=host, n_items=n_items, config=cfg, trace=trace)


def _counters(trace: dict) -> dict:
    """A call's counters from its per-step trace: sums over steps and
    layers, and the largest of what is a maximum (left out where no layer
    reported one: a block without an expert layer has no load ratio)."""
    out = {
        "pairs_held": float(trace["pairs"].sum()),
        "dropped_pairs": float(trace["dropped"].sum()),
        "moe_passes": float(trace["passes"].sum()),
        "moe_staged_rows": float(trace["staged"].sum()),
    }
    for name in ("window_tiles", "causal_tiles", "ssm_chunks",
                 "ssm_head_blocks") + DSA_COUNTERS:
        if name in trace:
            out[name] = float(trace[name].sum())
    for name in ("load_max_over_mean", "bias_max", "ssm_state_absmax"):
        if name in trace and trace[name].size:
            out[name] = float(trace[name].max())
    return out


#: the indexer loss's weight in the step's loss (DeepSeek-V3.2-Exp's sparse
#: stage: the two losses share no parameter, so it scales the indexer's
#: gradient alone)
INDEX_LOSS_WEIGHT = 1.0
#: a sparse layer's counters (``seq_layers.dsa``), summed over its layers
DSA_COUNTERS = ("selected_pairs", "sparse_key_blocks", "causal_key_blocks",
                "topk_boundary_ties")


def _after_step(params, aux, cfg):
    """What follows the optimizer in a step of a moe block: the per-expert
    loads reduce to the step's counters, a router with a selection bias
    moves every expert layer's bias towards the mean load, and the gqa
    block's tile counters take their names. A block without an expert
    layer has no bias to move and reduces no load: its columns are empty."""
    import jax.numpy as jnp

    load = aux["load"]  # [expert layers (+ the MTP module's), n_experts]
    aux = dict(aux)
    if "tiles" in aux:
        aux["window_tiles"], aux["causal_tiles"] = aux.pop("tiles")
    if "dsa" in aux:
        aux.update(zip(DSA_COUNTERS, aux.pop("dsa")))
    main = "moe" if cfg.mixer_pattern else "blocks"
    if cfg.router_kind == "sigmoid_bias" and main in params:
        n_main = params[main]["router_b"].shape[0]
        params = dict(params)
        groups = [(main, load[:n_main])]
        if cfg.mtp_depth:
            groups.append(("mtp", load[n_main:]))
        biases = []
        for group, group_load in groups:
            b = update_router_bias(
                params[group]["router_b"], group_load, cfg.bias_update_rate)
            params[group] = dict(params[group], router_b=b)
            biases.append(jnp.abs(b).max())
        aux["bias_max"] = jnp.stack(biases).max()
    aux["load_max_over_mean"] = load.max(axis=-1) / jnp.maximum(
        load.mean(axis=-1), 1.0)
    return params, aux
