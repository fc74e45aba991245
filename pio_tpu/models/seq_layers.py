"""The sequence model's blocks, described by data.

Four blocks share ``models/seqrec.py``'s trainer and server. A layer is
built in one of three ways: the SASRec block's attention and biased FFN, the
moe blocks' "attention, then a feed-forward part" (dense or experts), or
**one mixer alone** (``mixer_pattern``):

- ``attention_kind="mha", ffn_kind="relu"`` — the SASRec block (pre-LN,
  learned positions, tied head); its math lives in ``seqrec._block``.
- ``attention_kind="mla", ffn_kind="moe"`` — the block of the
  DeepSeek-V3 / ``glm4_moe_lite`` family: RMSNorm, multi-head latent
  attention with RoPE on a shared rope key, ``dense_layers`` leading SwiGLU
  layers, then expert layers (a sigmoid ``noaux_tc`` router over all
  ``n_experts``, the ``experts_held`` experts from ``experts_first`` computed
  here, one always-on shared expert), an untied head and ``mtp_depth``
  multi-token-prediction modules. This module holds that block's layers.
- ``attention_kind="gqa", ffn_kind="moe"`` — grouped-query attention whose
  layers come in kinds (``layer_pattern``: ``"full"`` causal layers and
  ``"window"`` layers that see the last ``window`` keys), each kind with
  its own count of query heads over the same ``kv_heads`` and its own RoPE
  (a rotated slice of the head, YaRN's interpolated frequencies and factor
  on a full layer), a sigmoid gate a query head on the attention's output,
  and the same dense and expert layers behind a ``router_kind="softmax"``
  router; or ``"sparse"`` layers (DeepSeek Sparse Attention's form,
  :func:`dsa`): a full layer's heads over the keys a lightning indexer
  selects a query, the indexer trained by its own KL loss (its scores on a
  TPU in two Pallas kernels, :func:`index_impl`). Per-head q/k
  RMSNorm where ``attn_qk_norm``; no shared expert where ``shared_experts``
  is 0. Its stacks are ``dense/*``, ``window/*``, ``full/*`` and
  ``sparse/*``: the kinds' projections have unlike shapes. ``heads_full``,
  ``heads_window`` and ``kv_heads`` count the heads held here, as
  ``experts_held`` counts the experts.
- the same two kinds with ``mixer_pattern`` set (the ``nemotron_h`` family):
  layer ``i`` is ``h + mixer(norm(h))`` with the one mixer
  ``mixer_pattern[i]`` names: ``"mamba"`` (a Mamba-2
  state-space mixer, :func:`mamba`: one wide input projection, a causal
  depthwise convolution, the selective recurrence over ``ssm_heads`` heads
  computed by chunks, :func:`ssd_scan` or on a TPU its two Pallas kernels
  (:func:`ssd_impl`), a gated group RMSNorm and an output
  projection), ``"moe"`` (the expert feed-forward alone, :func:`moe`, here
  with ``expert_act="relu2"`` experts of two matrices behind either router;
  ``expert_matmul="gmm"`` hands any moe block's routed rows to the Pallas
  grouped matmul on a TPU, :func:`grouped_matmul`),
  ``"attn"`` (:func:`gqa` with ``heads_full`` query heads, no position
  encoding where ``attn_rope`` is false and no gate where ``attn_gate`` is)
  or ``"mlp"`` (the dense SwiGLU of width ``ffn``, :func:`swiglu`, as a
  layer of its own with its own norm: the ``granitemoehybrid`` family's
  layer, a mixer *and* an MLP, is two entries of the pattern).
  Its stacks are ``mamba/*``, ``moe/*``, ``attn/*`` and ``mlp/*``. The
  pattern may hold no ``moe`` layer at all: the step's trace then carries
  the expert counters as empty columns. A ``mamba`` layer's
  recurrent state is not passed from shard to shard: a mesh with a ``seq``
  axis is refused.

Four scalars of the model and one switch apply to every moe block, each only
where set: ``embed_scale`` multiplies the looked-up rows, ``residual_scale``
what a layer adds to the stream, ``attn_scale`` replaces the scores'
``head width ** -0.5``, ``logit_scale`` multiplies the logits before the
log-sum-exp, and ``tied_head`` reads the logits from the embedding table (no
``head`` parameter: one gradient, the sum of both uses; drawn as a head is).

:func:`describe_params` is the one place a block's parameter shapes are
written: ``init_params``, ``param_specs`` and the placement check of
``train_seqrec`` all derive from it, for both blocks.

Compute policy of the new block (``compute_dtype``, bfloat16 as published):
master weights, Adam and the residual stream are float32; matmul operands
are cast to ``compute_dtype`` and accumulate in float32; norms, softmax,
the router's scores and every reduction are float32. In a ``mamba`` layer
the step sizes, ``A``, the cumulative sums, every ``exp``, the carried state
and the gated norm are float32 too.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, NamedTuple, Tuple

from pio_tpu.utils.numutil import round_up

BLOCK_KINDS = {("mha", "relu"), ("mla", "moe"), ("gqa", "moe")}
LAYER_KINDS = ("full", "window", "sparse")
MIXER_KINDS = ("mamba", "moe", "attn", "mlp")
ROUTER_KINDS = ("sigmoid_bias", "softmax")
EXPERT_ACTS = ("swiglu", "relu2")
EXPERT_MATMULS = ("ragged_dot", "gmm")

#: How the mla/moe block's parameters are drawn (a norm's gain is 1): every
#: matrix and the head; the embedding rows; the router's selection bias.
#: Unit embedding rows keep the residual stream the token's own: with rows of
#: 0.02 the stream after the first layer is one vector common to every
#: position and every token selects the same experts (PERF.md section 6).
INIT_STD, EMBED_INIT_STD, BIAS_INIT_STD = 0.02, 1.0, 0.02
#: Edge of the attention tiles, and tokens to a chunk of the cross-entropy and
#: of the dense SwiGLU (each clamped to a divisor of what it cuts).
ATTN_BLOCK, TOKEN_CHUNK = 512, 2048
#: Queries of a block of the indexer's scores, a block after another: XLA's
#: einsum holds the block's ``[rows, index heads, keys]`` float32 products at
#: once (16 heads of 16,384 keys: 128 MB at 128 rows), the kernels of
#: :mod:`pio_tpu.models.index_kernel` a chunk of keys of them in VMEM.
INDEX_BLOCK = 128
#: The name a sparse layer's selection carries (``checkpoint_name``): the
#: layer's recomputation in the backward pass keeps it instead of running
#: the indexer's scores and the top-k again.
SELECTION = "seq.dsa.selection"
#: The name a sparse layer's indexer gradient carries (``checkpoint_name``):
#: its loss's gradient in the indexer's own parameters, taken in the forward
#: pass (:func:`index_loss`) and kept, so that neither the backward pass nor
#: the layer's recomputation walks the queries again.
INDEX_GRAD = "seq.dsa.index_grad"
#: Heads of a Mamba-2 group to a turn of the XLA chunked scan's map
#: (:func:`ssd_scan`, which the TPU's kernels replace where :func:`ssd_impl`
#: admits them; clamped to a divisor of the group's heads, so a group of
#: 8 heads is one turn). A turn's float32 ``[chunk, chunk]`` weights stand a
#: head: at chunks of 256 a group of 64 heads whole would hold 0.5 GB three
#: times over. Of 8, 16 and 32 on a v5e at 64 heads in one group, chunks of
#: 256, nine layers of 8,192 events, 16 read the scan's fewest seconds (1.023,
#: 0.972 and 1.028 s a call) on one process a setting, one seed, not repeated;
#: a whole call moved by half a percent, inside the calls' own scatter, so the
#: choice is not resolved at the call's level (PERF.md section 6, PR 39).
SSM_HEAD_BLOCK = 16
#: Rows the first pass of the grouped matmuls stages, over the pairs a
#: balanced router sends the held experts (:func:`pass_widths`). Everything
#: around the matmuls (gathers, selects, the combine) costs by the row staged,
#: so the pass is sized to the pairs held; twice their mean leaves room for
#: the uneven loads one pass should still take, and a hot held expert costs
#: further passes, never pairs.
MOE_PASS_OVER_HELD = 2
#: Passes a layer unrolls at most. A pass is three grouped matmuls, their
#: recomputation and their backward in the compiled step whether it runs or
#: not, so the count is held to one more than the four that a quarter of all
#: pairs to a pass gave.
MOE_MAX_PASSES = 5
#: Share of all pairs the grouped matmuls are given at once at most (but by
#: the first pass and one like it), and that the unrolled passes cover
#: together; the last pass takes the rest a chunk of this share at a time. A
#: pass's float32 rows live in HBM together: with half the pairs to one the
#: 16k cells' steps do not fit a v5e.
MOE_CHUNK_SHARE = 0.25
#: Row, contraction and column tile of the Pallas grouped matmul
#: (``expert_matmul="gmm"``), each clamped to the size it cuts: of the
#: product, of its rows' gradient and of its weights' gradient. The least
#: of fifteen an operation timed on a v5e at 2,688 x 1,856 and back, 4,000
#: and 8,000 rows in 8 groups (PERF.md section 6, PR 35).
GMM_TILES = {"out": (128, 2688, 512), "d_rows": (256, 2688, 512),
             "d_weights": (512, 896, 1024)}


#: parameter groups the trainer reports gradient norms for, in this order
GROUPS = ("embedding", "head", "mla", "router", "routed_experts",
          "shared_expert", "dense_mlp", "mtp")


#: the gqa/moe block's: the attention of each kind of layer and the gate
#: apart, every norm's gain together
GQA_GROUPS = ("embedding", "head", "attn_window", "attn_full", "gate",
              "router", "routed_experts", "shared_expert", "dense_mlp",
              "norms")


#: a block of sparse layers: attention's projections and q/k norms' gains
#: together, the indexer (trained by its own loss alone) apart; the shared
#: expert and the dense MLP only where the block has them
DSA_GROUPS = ("embedding", "head", "attn", "indexer", "router",
              "routed_experts", "shared_expert", "dense_mlp", "norms")


#: a block of single mixers: a mamba layer's two projections apart from what
#: its recurrence reads (the convolution, ``A_log``, ``D``, ``dt_bias`` and
#: the gated norm's gain), the attention layers' four projections together
MIXER_GROUPS = ("embedding", "head", "ssm_proj", "ssm_scan", "attn", "router",
                "routed_experts", "shared_expert", "norms")


def groups_of(cfg) -> Tuple[str, ...]:
    """The groups a block's gradient norms are reported for, in order. A
    block of single mixers with a dense ``mlp`` mixer reports the groups it
    has parameters in, ``dense_mlp`` among them (no ``head`` under a tied
    table, no router or experts without a ``moe`` layer); one without keeps
    ``MIXER_GROUPS`` whole, whatever its pattern: the comparison of a
    trained call reads the columns by position."""
    pattern = cfg.mixer_pattern
    if "mlp" in pattern:
        has = {"head": not cfg.tied_head, "ssm_proj": "mamba" in pattern,
               "ssm_scan": "mamba" in pattern, "attn": "attn" in pattern,
               "router": "moe" in pattern, "routed_experts": "moe" in pattern,
               "shared_expert": "moe" in pattern}
        return tuple(g for g in (*MIXER_GROUPS[:-1], "dense_mlp", "norms")
                     if has.get(g, True))
    if pattern:
        return MIXER_GROUPS
    if is_sparse(cfg):
        has = {"shared_expert": cfg.shared_experts > 0,
               "dense_mlp": cfg.dense_layers > 0}
        return tuple(g for g in DSA_GROUPS if has.get(g, True))
    return GQA_GROUPS if cfg.attention_kind == "gqa" else GROUPS


def group_of(path: str, cfg=None) -> str:
    """Which of ``groups_of(cfg)`` the parameter at ``group/name`` belongs
    to. The mla/moe block (and ``cfg=None``): the MTP module whole; the two
    tables; an expert layer's router, routed experts and shared expert; the
    dense layers' MLP; and attention with every norm under ``mla``. The
    gqa/moe block: the four projections under the attention of their
    layer's kind (a dense layer's kind is the pattern's), the gate's map,
    and the norms' gains on their own; a block of sparse layers: the
    projections under ``attn``, the indexer's ``idx_*`` under ``indexer``.
    A block of single mixers: see
    ``MIXER_GROUPS``; an ``mlp`` mixer's three matrices are ``dense_mlp``."""
    group, _, name = path.rpartition("/")
    if cfg is not None and cfg.mixer_pattern:
        if name.endswith("norm") or name == "lnf_g":
            return "norms"
        if group == "mamba":
            return "ssm_proj" if name.endswith("_proj") else "ssm_scan"
        if group == "attn":
            return "attn"
    elif cfg is not None and cfg.attention_kind == "gqa":
        if is_sparse(cfg) and name.startswith("idx_"):
            return "indexer"
        if is_sparse(cfg) and name.endswith("_proj"):
            return "attn"
        if name.endswith("norm") or name == "lnf_g":
            return "norms"
        if name == "g_proj":
            return "gate"
        if name.endswith("_proj"):
            return "attn_" + (layer_kind(cfg, 0) if group == "dense" else group)
    if group == "mtp":
        return "mtp"
    by_name = {"emb": "embedding", "head": "head", "router_w": "router",
               "router_b": "router"}
    if name in by_name:
        return by_name[name]
    for prefix, kind in (("e_", "routed_experts"), ("s_", "shared_expert"),
                         ("w_", "dense_mlp")):
        if name.startswith(prefix):
            return kind
    return "mla"


def group_norms(grads: dict, cfg=None):
    """``[len(groups_of(cfg))]`` Frobenius norms of a two-deep gradient
    tree."""
    import jax.numpy as jnp

    groups = GROUPS if cfg is None else groups_of(cfg)
    total = dict.fromkeys(groups, jnp.float32(0.0))
    for group, value in grads.items():
        leaves = value.items() if isinstance(value, dict) else [(None, value)]
        for name, g in leaves:
            kind = group_of(f"{group}/{name}" if name else group, cfg)
            total[kind] = total[kind] + jnp.sum(jnp.square(g))
    return jnp.sqrt(jnp.stack([total[k] for k in groups]))


class Leaf(NamedTuple):
    """One parameter: its shape and how it starts. ``init`` is ``"ones"``,
    ``"zeros"``, ``("split", i, scale)`` (the SASRec block's historical
    draw: key ``i`` of ``split(PRNGKey(seed), 8)``) or ``("named", std)``
    (normal of that std under ``fold_in(PRNGKey(seed), crc32(path))``, the
    rule a plain reference can follow by name), or under the same key
    ``("uniform", lo, hi)``, ``("log_uniform", lo, hi)`` (the log of a
    uniform draw) and ``("dt_bias", lo, hi, floor)`` (the inverse softplus
    of a log-uniform step size in ``[lo, hi]``, floored)."""

    shape: Tuple[int, ...]
    init: object


def is_moe(cfg) -> bool:
    """Whether the block is one of the two with dense and expert layers
    (mla or gqa attention), which share the layer-stack trunk, the untied
    head and the per-step trace."""
    return cfg.ffn_kind == "moe"


def has_experts(cfg) -> bool:
    """Whether a moe block has an expert layer: all but a block of single
    mixers whose pattern names no ``moe``."""
    return not cfg.mixer_pattern or "moe" in cfg.mixer_pattern


def is_sparse(cfg) -> bool:
    """Whether the gqa block's layers are ``"sparse"`` (:func:`dsa`)."""
    return cfg.attention_kind == "gqa" and "sparse" in cfg.layer_pattern


def layer_kind(cfg, layer: int) -> str:
    """The gqa/moe block's kind of layer ``layer``: the pattern repeats."""
    return cfg.layer_pattern[layer % len(cfg.layer_pattern)]


def period_kinds(cfg) -> Tuple[str, ...]:
    """The stacks that the expert layers of one period draw from, in the
    layers' order: the gqa/moe block's kinds from the first layer behind
    the dense ones; the mla/moe block's layers are alike, a period is one
    layer of ``blocks``."""
    if cfg.attention_kind != "gqa":
        return ("blocks",)
    n = len(cfg.layer_pattern)
    return tuple(layer_kind(cfg, cfg.dense_layers + i) for i in range(n))


def heads_of(cfg, kind: str) -> int:
    """Query heads of a layer of ``kind``: a sparse layer's are a full
    layer's."""
    return cfg.heads_window if kind == "window" else cfg.heads_full


def check_block(cfg, n_seq: int = 1) -> None:
    """Refuses a block that cannot be built; ``n_seq`` is the size of the
    mesh's ``seq`` axis the block is to run under."""
    if (cfg.attention_kind, cfg.ffn_kind) not in BLOCK_KINDS:
        raise ValueError(
            f"unsupported block: attention_kind={cfg.attention_kind!r} with "
            f"ffn_kind={cfg.ffn_kind!r}; have {sorted(BLOCK_KINDS)}"
        )
    if not is_moe(cfg):
        if cfg.mixer_pattern:
            raise ValueError("mixer_pattern needs ffn_kind='moe': the mha/relu "
                             "block's layers are attention and FFN together")
        return
    if cfg.router_kind not in ROUTER_KINDS:
        raise ValueError(f"router_kind is one of {ROUTER_KINDS}")
    if cfg.expert_act not in EXPERT_ACTS:
        raise ValueError(f"expert_act is one of {EXPERT_ACTS}")
    if cfg.expert_matmul not in EXPERT_MATMULS:
        raise ValueError(f"expert_matmul is one of {EXPERT_MATMULS}")
    if cfg.mixer_pattern:
        _check_mixers(cfg, n_seq)
    elif cfg.attention_kind == "gqa":
        _check_gqa(cfg, n_seq)
    if not 0 <= cfg.dense_layers < cfg.n_layers:
        raise ValueError("dense_layers must leave at least one expert layer")
    if not (0 <= cfg.experts_first
            and cfg.experts_first + cfg.experts_held <= cfg.n_experts
            and cfg.experts_held >= 1):
        raise ValueError(
            f"held experts [{cfg.experts_first}, "
            f"{cfg.experts_first + cfg.experts_held}) are not among the "
            f"router's {cfg.n_experts}"
        )
    if cfg.mtp_depth not in (0, 1):
        raise ValueError("mtp_depth is 0 or 1")
    if cfg.attn_scale < 0 or min(cfg.embed_scale, cfg.residual_scale,
                                 cfg.logit_scale) <= 0:
        raise ValueError("embed_scale, residual_scale and logit_scale are "
                         "positive, attn_scale positive or 0 (head width "
                         "** -0.5)")
    if cfg.attention_kind == "mla" and cfg.qk_rope_dim % 2:
        raise ValueError("qk_rope_dim must be even")


def _check_mixers(cfg, n_seq: int) -> None:
    pattern = cfg.mixer_pattern
    if any(k not in MIXER_KINDS for k in pattern):
        raise ValueError(f"mixer_pattern holds kinds of {MIXER_KINDS}")
    if cfg.attention_kind != "gqa":
        raise ValueError("a block of single mixers has gqa attention layers: "
                         "attention_kind='gqa'")
    if cfg.n_layers != len(pattern):
        raise ValueError(f"mixer_pattern names every layer: {len(pattern)} "
                         f"kinds for n_layers {cfg.n_layers}")
    if cfg.dense_layers or cfg.mtp_depth:
        raise ValueError("a block of single mixers has no dense layers and "
                         "no MTP module: a layer is one mixer alone")
    if "attn" in pattern:
        _check_heads(cfg, ("full",))
    if "mamba" in pattern:
        if n_seq > 1:
            raise ValueError(
                f"a mamba layer cannot run under a seq axis of {n_seq}: its "
                "recurrent state (and the convolution's last inputs) would "
                "have to pass from one shard of the sequence to the next, "
                "which is not built, and a state that silently restarts at "
                "a shard's edge is another model")
        if cfg.ssm_heads % cfg.ssm_groups or min(
                cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk) < 1:
            raise ValueError(
                f"ssm_heads {cfg.ssm_heads} must be a multiple of "
                f"ssm_groups {cfg.ssm_groups}, every ssm size at least 1")
        if not 0 < cfg.ssm_dt_min <= cfg.ssm_dt_max:
            raise ValueError("0 < ssm_dt_min <= ssm_dt_max")


def _check_gqa(cfg, n_seq: int) -> None:
    pattern = cfg.layer_pattern
    if not pattern or any(k not in LAYER_KINDS for k in pattern):
        raise ValueError(f"layer_pattern holds kinds of {LAYER_KINDS}")
    if (cfg.n_layers - cfg.dense_layers) % len(pattern):
        raise ValueError(
            "the expert layers must be whole periods of layer_pattern")
    if len({layer_kind(cfg, i) for i in range(cfg.dense_layers)}) > 1:
        raise ValueError("the dense layers must be of one kind: they stack")
    _check_heads(cfg, set(pattern))
    if "window" in pattern and cfg.window < 1:
        raise ValueError("window layers need window >= 1")
    if "sparse" in pattern:
        if set(pattern) != {"sparse"}:
            raise ValueError("layer_pattern holds sparse layers alone: the "
                             "indexer's loss and group are the block's")
        if n_seq > 1:
            raise ValueError(
                f"a sparse layer cannot run under a seq axis of {n_seq}: "
                "its indexer scores every earlier key of the row, and the "
                "selection across shards of the sequence is not built")
        if (min(cfg.index_heads, cfg.index_head_dim, cfg.index_topk) < 1
                or cfg.index_head_dim % 4):
            raise ValueError("index_heads, index_topk >= 1; index_head_dim "
                             "a multiple of 4 (its first half is rotated)")
        if cfg.yarn_factor > 1.0:
            raise ValueError("a sparse layer's RoPE is plain (no YaRN)")
    if cfg.router_kind != "softmax" or cfg.mtp_depth:
        raise ValueError("the gqa/moe block has a softmax router and no "
                         "MTP module")


def _check_heads(cfg, kinds) -> None:
    for kind in kinds:
        if heads_of(cfg, kind) % cfg.kv_heads or heads_of(cfg, kind) < 1:
            raise ValueError(
                f"the {kind} layers' query heads must be a multiple of "
                f"kv_heads {cfg.kv_heads}")
    rotary = cfg.rotary_dim or cfg.head_dim
    if cfg.attn_rope and (cfg.head_dim % 2 or rotary % 2
                          or rotary > cfg.head_dim):
        raise ValueError("head_dim and rotary_dim are even, rotary_dim at "
                         "most head_dim")


def _mla_leaves(L: int, cfg) -> Dict[str, Leaf]:
    D, H, std = cfg.d_model, cfg.n_heads, ("named", INIT_STD)
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "attn_norm": Leaf((L, D), "ones"),
        "q_a": Leaf((L, D, cfg.q_lora_rank), std),
        "q_norm": Leaf((L, cfg.q_lora_rank), "ones"),
        "q_b": Leaf((L, cfg.q_lora_rank, H * qk), std),
        "kv_a": Leaf((L, D, cfg.kv_lora_rank + cfg.qk_rope_dim), std),
        "kv_norm": Leaf((L, cfg.kv_lora_rank), "ones"),
        "kv_b": Leaf((L, cfg.kv_lora_rank,
                      H * (cfg.qk_nope_dim + cfg.v_head_dim)), std),
        "o_proj": Leaf((L, H * cfg.v_head_dim, D), std),
        "ffn_norm": Leaf((L, D), "ones"),
    }


def _gqa_leaves(L: int, cfg, kind: str) -> Dict[str, Leaf]:
    D, H, std = cfg.d_model, heads_of(cfg, kind), ("named", INIT_STD)
    d, Hkv = cfg.head_dim, cfg.kv_heads
    out = {
        "attn_norm": Leaf((L, D), "ones"),
        "q_proj": Leaf((L, D, H * d), std),
        "k_proj": Leaf((L, D, Hkv * d), std),
        "v_proj": Leaf((L, D, Hkv * d), std),
        "g_proj": Leaf((L, D, H), std),
        "o_proj": Leaf((L, H * d, D), std),
        "ffn_norm": Leaf((L, D), "ones"),
    }
    if not cfg.attn_gate:
        del out["g_proj"]
    if cfg.attn_qk_norm:
        out["q_norm"] = Leaf((L, d), "ones")
        out["k_norm"] = Leaf((L, d), "ones")
    if kind == "sparse":  # the lightning indexer
        Hi, di = cfg.index_heads, cfg.index_head_dim
        out.update({
            "idx_q": Leaf((L, D, Hi * di), std),
            "idx_k": Leaf((L, D, di), std),
            "idx_k_norm_g": Leaf((L, di), "ones"),
            "idx_k_norm_b": Leaf((L, di), "zeros"),
            "idx_w": Leaf((L, D, Hi), std),
        })
    return out


def _moe_leaves(L: int, cfg) -> Dict[str, Leaf]:
    """An expert layer's feed-forward: router, held experts, shared expert."""
    D, Fe, std = cfg.d_model, cfg.expert_ffn, ("named", INIT_STD)
    Eh, Fs = cfg.experts_held, cfg.expert_ffn * cfg.shared_experts
    out = {
        "router_w": Leaf((L, D, cfg.n_experts), std),
        "e_gate": Leaf((L, Eh, D, Fe), std),
        "e_up": Leaf((L, Eh, D, Fe), std),
        "e_down": Leaf((L, Eh, Fe, D), std),
        "s_gate": Leaf((L, D, Fs), std),
        "s_up": Leaf((L, D, Fs), std),
        "s_down": Leaf((L, Fs, D), std),
    }
    if cfg.expert_act == "relu2":  # two matrices an expert: no gate
        del out["e_gate"], out["s_gate"]
    if not cfg.shared_experts:
        for name in ("s_gate", "s_up", "s_down"):
            out.pop(name, None)
    return out


def ssm_widths(cfg) -> Tuple[int, int]:
    """``(inner, convolved)`` widths of a mamba layer: the heads' channels
    ``z`` and ``x`` have, and ``x | B | C`` together."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    return inner, inner + 2 * cfg.ssm_groups * cfg.ssm_state


def _mamba_leaves(L: int, cfg) -> Dict[str, Leaf]:
    """A mamba layer: ``in_proj``'s columns are ``z | x B C | dt``;
    ``conv_w[j]`` multiplies the input ``ssm_conv - 1 - j`` steps back."""
    D, H, std = cfg.d_model, cfg.ssm_heads, ("named", INIT_STD)
    inner, conv = ssm_widths(cfg)
    bound = cfg.ssm_conv ** -0.5
    return {
        "norm": Leaf((L, D), "ones"),
        "in_proj": Leaf((L, D, inner + conv + H), std),
        "conv_w": Leaf((L, cfg.ssm_conv, conv), ("uniform", -bound, bound)),
        "conv_b": Leaf((L, conv), ("uniform", -bound, bound)),
        "a_log": Leaf((L, H), ("log_uniform", 1.0, 16.0)),
        "d_skip": Leaf((L, H), "ones"),
        "dt_bias": Leaf((L, H), ("dt_bias", cfg.ssm_dt_min, cfg.ssm_dt_max,
                                 cfg.ssm_dt_floor)),
        "gate_g": Leaf((L, inner), "ones"),
        "out_proj": Leaf((L, inner, D), std),
    }


def _table_leaves(vocab: int, cfg) -> Dict[str, Leaf]:
    """A moe block's embedding table, head and final norm's gain. A tied
    table (``tied_head``) stands for both and is drawn as a head is: the
    logits read it."""
    D, std = cfg.d_model, ("named", INIT_STD)
    if cfg.tied_head:
        return {"emb": Leaf((vocab, D), std), "lnf_g": Leaf((D,), "ones")}
    return {"emb": Leaf((vocab, D), ("named", EMBED_INIT_STD)),
            "head": Leaf((vocab, D), std),
            "lnf_g": Leaf((D,), "ones")}


def _describe_mixers(vocab: int, cfg) -> Dict[str, Leaf]:
    D, F, std = cfg.d_model, cfg.ffn, ("named", INIT_STD)
    out = _table_leaves(vocab, cfg)
    for kind in MIXER_KINDS:
        L = cfg.mixer_pattern.count(kind)
        if not L:
            continue
        if kind == "mamba":
            leaves = _mamba_leaves(L, cfg)
        elif kind == "attn":
            leaves = _gqa_leaves(L, cfg, "full")
            del leaves["ffn_norm"]
        elif kind == "mlp":
            leaves = {"norm": Leaf((L, D), "ones"),
                      "w_gate": Leaf((L, D, F), std),
                      "w_up": Leaf((L, D, F), std),
                      "w_down": Leaf((L, F, D), std)}
        else:
            leaves = {"ffn_norm": Leaf((L, D), "ones"), **_moe_leaves(L, cfg)}
            if cfg.router_kind == "sigmoid_bias":
                leaves["router_b"] = Leaf((L, cfg.n_experts),
                                          ("named", BIAS_INIT_STD))
        out.update({f"{kind}/{k}": v for k, v in leaves.items()})
    return out


def _describe_gqa(vocab: int, cfg) -> Dict[str, Leaf]:
    D, F, std = cfg.d_model, cfg.ffn, ("named", INIT_STD)
    Ld = cfg.dense_layers
    out = _table_leaves(vocab, cfg)
    if Ld:
        dense = {**_gqa_leaves(Ld, cfg, layer_kind(cfg, 0)),
                 "w_gate": Leaf((Ld, D, F), std), "w_up": Leaf((Ld, D, F), std),
                 "w_down": Leaf((Ld, F, D), std)}
        out.update({f"dense/{k}": v for k, v in dense.items()})
    periods = (cfg.n_layers - Ld) // len(cfg.layer_pattern)
    for kind in LAYER_KINDS:
        L = periods * period_kinds(cfg).count(kind)
        if L:
            out.update({f"{kind}/{k}": v for k, v in {
                **_gqa_leaves(L, cfg, kind), **_moe_leaves(L, cfg)}.items()})
    return out


def _expert_layer_leaves(L: int, cfg) -> Dict[str, Leaf]:
    return {
        **_mla_leaves(L, cfg), **_moe_leaves(L, cfg),
        "router_b": Leaf((L, cfg.n_experts), ("named", BIAS_INIT_STD)),
    }


def describe_params(vocab: int, cfg) -> Dict[str, Leaf]:
    """``{"group/name": Leaf}`` of every parameter of the configured
    block, layer-stacked (leading dim = layers of that group)."""
    D, F, L = cfg.d_model, cfg.ffn, cfg.n_layers
    if cfg.mixer_pattern:
        return _describe_mixers(vocab, cfg)
    if cfg.attention_kind == "gqa":
        return _describe_gqa(vocab, cfg)
    if not is_moe(cfg):
        s = D ** -0.5
        blocks = {
            "ln1_g": Leaf((L, D), "ones"), "ln1_b": Leaf((L, D), "zeros"),
            "wq": Leaf((L, D, D), ("split", 2, s)),
            "wk": Leaf((L, D, D), ("split", 6, s)),
            "wv": Leaf((L, D, D), ("split", 7, s)),
            "wo": Leaf((L, D, D), ("split", 3, s)),
            "ln2_g": Leaf((L, D), "ones"), "ln2_b": Leaf((L, D), "zeros"),
            "w1": Leaf((L, D, F), ("split", 4, s)),
            "b1": Leaf((L, F), "zeros"),
            "w2": Leaf((L, F, D), ("split", 5, F ** -0.5)),
            "b2": Leaf((L, D), "zeros"),
        }
        return {
            "emb": Leaf((vocab, D), ("split", 0, s)),
            "pos": Leaf((cfg.max_len, D), ("split", 1, s)),
            **{f"blocks/{k}": v for k, v in blocks.items()},
            "lnf_g": Leaf((D,), "ones"), "lnf_b": Leaf((D,), "zeros"),
        }
    std = ("named", INIT_STD)
    Ld = cfg.dense_layers
    out = _table_leaves(vocab, cfg)
    if Ld:
        dense = {**_mla_leaves(Ld, cfg),
                 "w_gate": Leaf((Ld, D, F), std), "w_up": Leaf((Ld, D, F), std),
                 "w_down": Leaf((Ld, F, D), std)}
        out.update({f"dense/{k}": v for k, v in dense.items()})
    out.update({f"blocks/{k}": v
                for k, v in _expert_layer_leaves(L - Ld, cfg).items()})
    if cfg.mtp_depth:
        mtp = {"eh_proj": Leaf((2 * D, D), std), "h_norm": Leaf((D,), "ones"),
               "e_norm": Leaf((D,), "ones"), "lnf_g": Leaf((D,), "ones"),
               **_expert_layer_leaves(1, cfg)}
        out.update({f"mtp/{k}": v for k, v in mtp.items()})
    return out


def unflatten(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}`` (the trees are two deep)."""
    out: dict = {}
    for path, value in flat.items():
        group, _, name = path.rpartition("/")
        if group:
            out.setdefault(group, {})[name] = value
        else:
            out[name] = value
    return out


def name_key(seed: int, path: str):
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed), zlib.crc32(path.encode()) & 0x7FFFFFFF
    )


def init_from(desc: Dict[str, Leaf], seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    split = jax.random.split(jax.random.PRNGKey(seed), 8)
    flat = {}
    for path, leaf in desc.items():
        if leaf.init == "ones":
            flat[path] = jnp.ones(leaf.shape, jnp.float32)
        elif leaf.init == "zeros":
            flat[path] = jnp.zeros(leaf.shape, jnp.float32)
        elif leaf.init[0] == "split":
            _, i, scale = leaf.init
            flat[path] = jax.random.normal(split[i], leaf.shape) * scale
        elif leaf.init[0] == "named":
            flat[path] = jax.random.normal(
                name_key(seed, path), leaf.shape, jnp.float32
            ) * jnp.float32(leaf.init[1])
        else:
            flat[path] = _drawn(name_key(seed, path), leaf)
    return unflatten(flat)


def _drawn(key, leaf: Leaf):
    """A mamba layer's draws (``Leaf``): uniform, the log of a uniform, and
    ``dt_bias``, the inverse softplus of a log-uniform step size."""
    import math

    import jax
    import jax.numpy as jnp

    kind, lo, hi = leaf.init[:3]
    if kind == "dt_bias":
        u = jax.random.uniform(key, leaf.shape, jnp.float32)
        dt = jnp.maximum(
            jnp.exp(u * jnp.float32(math.log(hi) - math.log(lo))
                    + jnp.float32(math.log(lo))), jnp.float32(leaf.init[3]))
        return dt + jnp.log(-jnp.expm1(-dt))
    draw = jax.random.uniform(key, leaf.shape, jnp.float32, lo, hi)
    if kind == "log_uniform":
        return jnp.log(draw)
    if kind != "uniform":
        raise ValueError(f"unknown init {leaf.init!r}")
    return draw


# ------------------------------------------------------------------ layers
def _dtype(cfg):
    import jax.numpy as jnp

    return jnp.dtype(cfg.compute_dtype)


def mm(x, w, cd):
    """``x @ w`` with operands in the compute dtype, float32 out."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(cd), w.astype(cd),
                   preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta: float, rotary_dim: int = 0, inv_freq=None,
         factor: float = 1.0):
    """Rotary embedding of ``x [..., T, h, d]`` at positions ``pos [T]``,
    pairing dim ``i`` with ``i + r/2`` of the first ``r = rotary_dim`` dims
    (the rotate-half convention; 0 = the whole head; the dims behind pass
    through). ``inv_freq [r/2]`` replaces ``theta ** (-2i / r)``
    (:func:`yarn_inv_freq`), and ``factor`` multiplies ``cos`` and ``sin``."""
    import jax.numpy as jnp

    d = rotary_dim or x.shape[-1]
    half = d // 2
    if inv_freq is None:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * jnp.float32(factor), sin * jnp.float32(factor)
    a, b = x[..., :half], x[..., half:d]
    parts = [a * cos - b * sin, b * cos + a * sin]
    if d < x.shape[-1]:
        parts.append(x[..., d:])
    return jnp.concatenate(parts, axis=-1)


def yarn_inv_freq(theta: float, rotary_dim: int, factor: float,
                  original_len: int, beta_fast: float, beta_slow: float):
    """YaRN's frequency table ``[rotary_dim / 2]`` (float32 numpy): with
    ``f_j = theta ** (-2j / r)`` and ``dim(n) = r ln(original_len / (2 pi
    n)) / (2 ln theta)`` (the dim that turns ``n`` times over the original
    length), ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``
    (clamped to the table), ``ramp_j = clip((j - low) / (high - low), 0,
    1)``: a frequency below ``low`` is kept, one above ``high`` is divided
    by ``factor``, those between are blended, ``f_j (1 - ramp_j) + f_j /
    factor * ramp_j``."""
    import math

    import numpy as np

    r, half = rotary_dim, rotary_dim // 2
    f = float(theta) ** (-np.arange(half, dtype=np.float64) / half)

    def dim(turns):
        return r * math.log(original_len / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), r - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def swiglu(x, w_gate, w_up, w_down, cd, chunk: int = 0):
    """``W_down(silu(W_gate x) * W_up x)`` of ``x [N, D]``; with ``chunk``,
    that many tokens at a time under ``jax.checkpoint``, so the float32
    ``[N, F]`` hidden of a wide layer never stands whole."""
    import jax

    from pio_tpu.parallel.ring import pick_block

    w_gate, w_up, w_down = (w.astype(cd) for w in (w_gate, w_up, w_down))

    def part(x):
        hidden = jax.nn.silu(mm(x, w_gate, cd)) * mm(x, w_up, cd)
        return mm(hidden, w_down, cd)

    n = x.shape[0]
    size = pick_block(n, chunk) if chunk else n
    if size == n:
        return part(x)
    return jax.lax.map(
        jax.checkpoint(part), x.reshape(n // size, size, -1)
    ).reshape(n, -1)


def relu2_mlp(x, w_up, w_down, cd):
    """``W_down relu(W_up x)^2`` of ``x [N, D]``: two matrices, no gate."""
    import jax
    import jax.numpy as jnp

    return mm(jnp.square(jax.nn.relu(mm(x, w_up, cd))), w_down, cd)


def mla(blk, h, cfg, s_axis):
    """Multi-head latent attention on the local ``[B, T_loc, D]`` slice."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import ring_attention

    cd, eps = _dtype(cfg), cfg.norm_eps
    B, T, _ = h.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    t_off = 0 if s_axis is None else jax.lax.axis_index(s_axis) * T
    pos = t_off + jnp.arange(T)
    with jax.named_scope("seq.mla/proj"):
        x = rms_norm(h, blk["attn_norm"], eps)
        c_q = rms_norm(mm(x, blk["q_a"], cd), blk["q_norm"], eps)
        q = mm(c_q, blk["q_b"], cd).reshape(B, T, H, dn + dr)
        kv_a = mm(x, blk["kv_a"], cd)
        c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank], blk["kv_norm"], eps)
        k_r = kv_a[..., cfg.kv_lora_rank:].reshape(B, T, 1, dr)
        kv = mm(c_kv, blk["kv_b"], cd).reshape(B, T, H, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(rope(k_r, pos, cfg.rope_theta), (B, T, H, dr))],
            axis=-1)
        v = kv[..., dn:]
    with jax.named_scope("seq.mla/attn"):
        attn = ring_attention(
            q.astype(cd), k.astype(cd), v.astype(cd), axis=s_axis,
            causal=True, block=ATTN_BLOCK,
            scale=cfg.attn_scale or (dn + dr) ** -0.5,
        )
    with jax.named_scope("seq.mla/proj"):
        return mm(attn.reshape(B, T, H * dv), blk["o_proj"], cd)


def gqa(blk, h, cfg, s_axis, kind: str):
    """Grouped-query attention of a ``kind`` layer on the local ``[B,
    T_loc, D]`` slice -> ``(attention's output before the residual, tile
    counters)``: ``[2]``, the score tiles a window layer's loops ran (a KV
    head and row) and those a causal layer of its length runs, zeros for a
    full layer. The ``heads_of(kind) / kv_heads`` query heads of a KV head
    share its keys and values inside one score tile; a window layer's tiles
    outside the window are skipped. Every query head's output is scaled by
    its own gate, ``sigmoid(x W_g)``, before ``W_o`` (``attn_gate``), and
    ``q`` and ``k`` are rotated by position (``attn_rope``), after a
    per-head RMSNorm where ``attn_qk_norm``: without those the layer is
    plain grouped-query attention with no position encoding.
    The scores are scaled by ``attn_scale`` where set, else by ``head_dim **
    -0.5``."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import ring_attention

    cd, eps = _dtype(cfg), cfg.norm_eps
    B, T, _ = h.shape
    H, Hkv, d = heads_of(cfg, kind), cfg.kv_heads, cfg.head_dim
    t_off = 0 if s_axis is None else jax.lax.axis_index(s_axis) * T
    pos = t_off + jnp.arange(T)
    if kind == "window":
        window, turn = cfg.window, dict(theta=cfg.window_rope_theta)
    else:
        window = 0
        turn = dict(theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim)
        if cfg.yarn_factor > 1.0:
            turn.update(
                inv_freq=yarn_inv_freq(
                    cfg.rope_theta, cfg.rotary_dim or d, cfg.yarn_factor,
                    cfg.yarn_original_len, cfg.yarn_beta_fast,
                    cfg.yarn_beta_slow),
                factor=cfg.yarn_attention_factor)
    with jax.named_scope("seq.gqa/proj"):
        x = rms_norm(h, blk["attn_norm"], eps)
        q = mm(x, blk["q_proj"], cd).reshape(B, T, H, d)
        if cfg.attn_qk_norm:
            q = rms_norm(q, blk["q_norm"], eps)
        if cfg.attn_rope:
            q = rope(q, pos, **turn)
        k = mm(x, blk["k_proj"], cd).reshape(B, T, Hkv, d)
        if cfg.attn_qk_norm:
            k = rms_norm(k, blk["k_norm"], eps)
        if cfg.attn_rope:
            k = rope(k, pos, **turn)
        v = mm(x, blk["v_proj"], cd).reshape(B, T, Hkv, d)
    if cfg.attn_gate:
        with jax.named_scope("seq.gqa/gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                x, blk["g_proj"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))  # [B, T, H] float32
    with jax.named_scope(f"seq.gqa/attn/{kind}"):
        attn, tiles = ring_attention(
            q.astype(cd), k.astype(cd), v.astype(cd), axis=s_axis,
            causal=True, block=ATTN_BLOCK, scale=cfg.attn_scale or d ** -0.5,
            window=window, with_tiles=True,
        )
    # the window layers' tiles are what the counter is for
    tiles = tiles.astype(jnp.float32) * (kind == "window")
    if cfg.attn_gate:
        with jax.named_scope("seq.gqa/gate"):
            attn = attn.astype(jnp.float32) * gate[..., None]
    with jax.named_scope("seq.gqa/proj"):
        return mm(attn.reshape(B, T, H * d), blk["o_proj"], cd), tiles


def layer_norm(x, g, b, eps):
    """LayerNorm over the last dim, float32, a gain and a bias."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    c = x - x.mean(axis=-1, keepdims=True)
    return c * jax.lax.rsqrt((c * c).mean(axis=-1, keepdims=True) + eps) * g + b


def index_impl(platform: str, cd, heads: int, di: int, n: int,
               t: int) -> str:
    """What computes :func:`index_scores` of a block of ``n`` queries of
    ``heads`` heads of ``di`` against ``t`` keys, from what is visible at
    trace time: ``pallas`` / ``xla``. ``pallas`` is the pair of kernels of
    :mod:`pio_tpu.models.index_kernel` (a block's ``[rows, heads, keys]``
    products in VMEM, the key chunks after the block never computed): on a
    TPU, with bfloat16 operands, a key count that whole tiles divide and
    tiles that fit VMEM (``index_kernel.admits``). ``xla`` is the einsum of
    :func:`index_scores`: everywhere else (every CPU run, float32
    operands, shapes the tiles do not fit), and the kernels' oracle."""
    import jax.numpy as jnp

    from pio_tpu.models import index_kernel

    cd = jnp.dtype(cd)
    tiles = cd == jnp.bfloat16 and index_kernel.admits(heads, di, n, t,
                                                       cd.itemsize)
    return "pallas" if platform == "tpu" and tiles else "xla"


def index_scores(qi, ki, w, t0, cd):
    """The lightning indexer's scores of a block of queries at positions
    ``t0 + r``: ``qi [B, n, Hi, di]`` and ``ki [B, T, di]`` (rotated), ``w
    [B, n, Hi]`` (its weights, ``Hi ** -0.5`` in them) -> ``I [B, n, T]``
    float32, ``di ** -0.5 sum_j w_j relu(q_j . k)``, ``-inf`` at the keys
    after the query. Operands in ``cd``, float32 accumulation. On the
    kernels of :mod:`pio_tpu.models.index_kernel` where :func:`index_impl`
    says ``pallas`` (``pallas_interpret``, the same kernels interpreted, in
    tests), else on the einsum below, their oracle."""
    import jax
    import jax.numpy as jnp

    n, Hi, di = qi.shape[1:]
    impl = index_impl(jax.default_backend(), cd, Hi, di, n, ki.shape[1])
    if impl != "xla":
        from pio_tpu.models import index_kernel

        return index_kernel.scores(qi.astype(cd), ki.astype(cd), w, t0,
                                   interpret=impl == "pallas_interpret")
    s = jnp.einsum("bqhd,bkd->bqhk", qi.astype(cd), ki.astype(cd),
                   preferred_element_type=jnp.float32)
    scores = (jax.nn.relu(s) * w[..., None]).sum(axis=2) * jnp.float32(
        qi.shape[-1] ** -0.5)
    n, T = scores.shape[1:]
    seen = jnp.arange(T)[None, :] <= (t0 + jnp.arange(n))[:, None]
    return jnp.where(seen[None], scores, -jnp.inf)


def top_keys(scores, k: int):
    """``(sel, ties)`` of index scores ``[B, n, T]`` (``-inf`` at keys not
    seen): ``sel`` bool, each row's ``k`` largest seen scores, **ties to the
    earlier key** (+0.0 and -0.0 are one score), every seen key of a row that
    sees at most ``k``; ``ties [B, n]`` bool, the rows whose ``k``-th and
    ``k + 1``-th largest scores are equal. The ``k``-th is found by bisection
    (:func:`_bisect_kth`), not by ``jax.lax.top_k``, which the TPU compiler
    makes a whole sort of each row: 0.025 s against 0.106 s for a layer's
    16,384 rows of 16,384 keys on a v5e (PERF.md section 6)."""
    import jax.numpy as jnp

    scores = jnp.where(scores == 0, 0.0, scores)  # no -0.0
    valid = scores > -jnp.inf
    n_valid = valid.sum(axis=-1)
    T = scores.shape[-1]
    if k >= T:
        return valid, jnp.zeros(n_valid.shape, bool)
    thr, last = _bisect_kth(scores, valid, k)
    pos = jnp.arange(T)
    sel = valid & ((scores > thr[..., None]) | (
        (scores == thr[..., None]) & (pos <= last[..., None])))
    sel = jnp.where((n_valid <= k)[..., None], valid, sel)
    ties = (n_valid > k) & (
        (valid & (scores >= thr[..., None])).sum(axis=-1) > k)
    return sel, ties


def _bisect_kth(scores, valid, k: int):
    """``(thr, last)`` of each row: its ``k``-th largest seen score, found a
    bit at a time over the scores' order-preserving integer bits (the
    largest ``theta`` with ``k`` keys at or above it), and the position of
    the last key at that score the selection takes (the ``k - above``-th
    such key, found a bit at a time over positions)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    flip = jnp.int32(0x7FFFFFFF)
    key = jnp.where(bits < 0, bits ^ flip, bits)  # int32 order = score order
    top = jnp.uint32(0x80000000)
    u = jnp.where(valid, jax.lax.bitcast_convert_type(key, jnp.uint32) ^ top,
                  jnp.uint32(0))
    rows = scores.shape[:-1]

    def value_bit(b, theta):
        cand = theta | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        return jnp.where((u >= cand[..., None]).sum(axis=-1) >= k, cand, theta)

    theta = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(rows, jnp.uint32))
    take = k - (u > theta[..., None]).sum(axis=-1)
    at = u == theta[..., None]
    T = scores.shape[-1]
    pos = jnp.arange(T, dtype=jnp.int32)
    n_bits = max(T - 1, 1).bit_length()

    def position_bit(b, last):
        cand = last | (jnp.int32(1) << (n_bits - 1 - b))
        before = (at & (pos < cand[..., None])).sum(axis=-1)
        return jnp.where((cand < T) & (before < take), cand, last)

    last = jax.lax.fori_loop(0, n_bits, position_bit,
                             jnp.zeros(rows, jnp.int32))
    key = jax.lax.bitcast_convert_type(theta ^ top, jnp.int32)
    thr = jax.lax.bitcast_convert_type(jnp.where(key < 0, key ^ flip, key),
                                       jnp.float32)
    return thr, last


def select_keys(qi, ki, w, k: int, bq: int, bk: int, cd):
    """The layer's selection, a block of ``bq`` queries after another (its
    scores :data:`INDEX_BLOCK` rows at a time): ``((bits, order, count),
    selected pairs, tie rows, position sum)`` — ``bits [B, T / bq * W, T]``
    int32 as ``ring.attention_partial``'s ``select`` takes it, the active
    key blocks of every query block and their count
    (``ring.selected_blocks``: a key block is active where some query of the
    block, in any row, selected a key of it); the position sum, float32, is
    the selected keys' positions summed over the queries, the selection's
    checksum (each query's sum exact in int32). No gradient."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import (pack_selection, pick_block,
                                       select_words, selected_blocks)

    B, T = ki.shape[:2]
    nq, nk, sub = T // bq, T // bk, pick_block(bq, INDEX_BLOCK)

    def block(i):
        def part(r):
            t0 = i * bq + r * sub
            with jax.named_scope("seq.dsa/index"):
                scores = index_scores(
                    jax.lax.dynamic_slice_in_dim(qi, t0, sub, axis=1), ki,
                    jax.lax.dynamic_slice_in_dim(w, t0, sub, axis=1), t0, cd)
            with jax.named_scope("seq.dsa/select"):
                return top_keys(scores, k)

        sel, ties = jax.lax.map(part, jnp.arange(bq // sub))
        with jax.named_scope("seq.dsa/select"):
            sel = jnp.moveaxis(sel, 0, 1).reshape(B, bq, T)
            active = sel.reshape(B, bq, nk, bk).any(axis=(0, 1, 3))
            pos_sum = jnp.where(sel, jnp.arange(T, dtype=jnp.int32), 0).sum(
                axis=-1, dtype=jnp.int32)
            return (pack_selection(sel), active, sel.sum(dtype=jnp.int32),
                    ties.sum(dtype=jnp.int32),
                    pos_sum.astype(jnp.float32).sum())

    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    bits, active, pairs, ties, pos_sum = jax.lax.map(block, jnp.arange(nq))
    bits = jnp.moveaxis(bits, 0, 1).reshape(B, nq * select_words(bq), T)
    with jax.named_scope("seq.dsa/select"):
        order, count = selected_blocks(active)
    return (bits, order, count), pairs.sum(), ties.sum(), pos_sum.sum()


def _head_probs(q, k, lse, sel, order, count, bk: int, scale: float):
    """The main attention's probabilities summed over the query heads and
    divided by their number, over the selection: ``q [B, n, H, d]``, ``k [B,
    T, Hkv, d]`` (what the tiles were given), ``lse [B, n, H]`` (theirs) ->
    ``[B, n, T]`` float32, 0 off ``sel``; the key blocks ``order[:count]``
    alone (the others hold no selected key)."""
    import jax
    import jax.numpy as jnp

    B, n, H, d = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, n, Hkv, H // Hkv, d)
    lg = lse.reshape(B, n, Hkv, H // Hkv, 1)

    def key_block(idx, p):
        j = order[idx]
        kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        pj = jnp.exp(s - lg).sum(axis=(2, 3)) / H
        seen = jax.lax.dynamic_slice_in_dim(sel, j * bk, bk, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(
            p, jnp.where(seen, pj, 0.0), j * bk, axis=2)

    return jax.lax.fori_loop(0, count, key_block,
                             jnp.zeros((B, n, T), jnp.float32))


def _selection_rows(bits, order, count, r, bq: int, sub: int):
    """Block ``r`` of ``sub`` queries: its rows of the selection ``[B, sub,
    T]`` bool, and its query block's active key blocks and their count."""
    import jax

    from pio_tpu.parallel.ring import select_words, unpack_selection

    i = r * sub // bq
    w_rows = select_words(bq)
    words = jax.lax.dynamic_slice_in_dim(bits, i * w_rows, w_rows, axis=1)
    rows = unpack_selection(words, bq, r * sub - i * bq, sub)
    return rows, order[i], count[i]


def _kl_rows(scores, sel, p):
    """``(sum over the rows of KL(p || softmax over sel of scores), its
    gradient in the scores)``."""
    import jax
    import jax.numpy as jnp

    z = jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf), axis=-1)
    mass = p.sum(axis=-1)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    kl = (plogp.sum(axis=-1) - jnp.where(sel, p * scores, 0.0).sum(axis=-1)
          + z * mass).sum()
    grad = jnp.where(sel, mass[..., None] * jnp.exp(scores - z[..., None]) - p,
                     0.0)
    return kl, grad


#: A sparse layer's leaves of the lightning indexer (:func:`indexer`)
INDEXER = ("idx_q", "idx_k", "idx_k_norm_g", "idx_k_norm_b", "idx_w")


def indexer(ip, xi, cfg):
    """The lightning indexer's ``(qi, ki, w)`` (as :func:`index_scores` takes
    them) of a sparse layer's normed input ``xi [B, T, D]``, from its own
    parameters ``ip`` (the layer's ``idx_*`` leaves): ``qi = RoPE_half(xi
    W_qi)``, ``ki = RoPE_half(LayerNorm(xi W_ki))``, ``w = xi W_w / sqrt(
    index_heads)``."""
    import jax.numpy as jnp

    cd, eps = _dtype(cfg), cfg.norm_eps
    B, T, _ = xi.shape
    Hi, di = cfg.index_heads, cfg.index_head_dim
    pos = jnp.arange(T)
    qi = rope(mm(xi, ip["idx_q"], cd).reshape(B, T, Hi, di), pos,
              cfg.rope_theta, di // 2).astype(cd)
    ki = layer_norm(mm(xi, ip["idx_k"], cd), ip["idx_k_norm_g"],
                    ip["idx_k_norm_b"], eps)
    ki = rope(ki[:, :, None], pos, cfg.rope_theta, di // 2)[:, :, 0]
    return qi, ki.astype(cd), mm(xi, ip["idx_w"], cd) * jnp.float32(Hi ** -0.5)


@functools.lru_cache(maxsize=16)
def _index_loss(bq: int, bk: int, sub: int, scale: float, cfg):
    """The indexer's loss of one layer, as :func:`index_loss` computes it,
    with its own backward pass. The queries are walked ``sub`` at a time,
    once: undifferentiated for the loss alone; differentiated (``fwd``) each
    block's gradient in its scores (:func:`_kl_rows`) is carried back through
    the scores at once, and the walk's gradient through the indexer's
    projections after it. That gradient in the indexer's parameters, for a
    cotangent of 1, is the one residual (named :data:`INDEX_GRAD`); the
    backward pass scales it by the loss's cotangent, since nothing before it
    depends on that scalar, and walks nothing. The gradient reaches the
    indexer's parameters alone."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    cd = _dtype(cfg)

    def project(ip, xi):
        with jax.named_scope("seq.dsa/index"):
            return indexer(ip, xi, cfg)

    def part(qi, ki, w, q, k, lse, bits, order, count, r):
        t0 = r * sub
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, sub, axis=1)
        with jax.named_scope("seq.dsa/index"):
            scores, back = jax.vjp(
                lambda qi_r, ki, w_r: index_scores(qi_r, ki, w_r, t0, cd),
                rows(qi), ki, rows(w))
        with jax.named_scope("seq.dsa/kl"):
            sel, order_i, count_i = _selection_rows(bits, order, count, r,
                                                    bq, sub)
            p = _head_probs(rows(q), k, rows(lse), sel, order_i, count_i, bk,
                            scale)
            kl, grad = _kl_rows(scores, sel, p)
        return kl, grad, back

    @jax.custom_vjp
    def loss(ip, xi, *rest):
        qi, ki, w = project(ip, xi)
        return jax.lax.map(lambda r: part(qi, ki, w, *rest, r)[0],
                           jnp.arange(qi.shape[1] // sub)).sum()

    def fwd(ip, xi, *rest):
        (qi, ki, w), back_proj = jax.vjp(lambda ip: project(ip, xi), ip)
        n = qi.shape[1] // sub

        def step(dki, r):
            kl, grad, back = part(qi, ki, w, *rest, r)
            with jax.named_scope("seq.dsa/index"):
                dqi_r, dki_r, dw_r = back(grad)
            return dki + dki_r, (kl, dqi_r, dw_r)

        dki, (kl, dqi, dw) = jax.lax.scan(
            step, jnp.zeros(ki.shape, jnp.float32), jnp.arange(n))
        merge = lambda a: jnp.moveaxis(a, 0, 1).reshape(
            a.shape[1], n * sub, *a.shape[3:])
        with jax.named_scope("seq.dsa/index"):
            (dip,) = back_proj((merge(dqi).astype(qi.dtype),
                                dki.astype(ki.dtype), merge(dw).astype(w.dtype)))
        return kl.sum(), checkpoint_name(dip, INDEX_GRAD)

    def bwd(dip, g):
        return (jax.tree.map(lambda a: a * g, dip),) + (None,) * 7

    loss.defvjp(fwd, bwd)
    return loss


def index_loss(ip, xi, q, k, lse, select, bq: int, bk: int, scale: float,
               cfg):
    """The indexer's loss of one layer, summed over its queries: ``sum_t
    KL(p_t || softmax over S_t of I[t])``, ``p_t`` the main attention's
    probabilities over the selection ``S_t`` summed over the query heads
    over their number (``q [B, T, H, d]``, ``k [B, T, Hkv, d]``, ``lse [B,
    T, H]``: what the tiles were given and gave back), ``I`` the index
    scores of :func:`indexer` of ``ip`` (the layer's ``idx_*`` leaves) and
    ``xi``. Only ``ip`` takes a gradient, and it is taken in the forward
    pass (:func:`_index_loss`); ``select`` is ``(bits, order, count)``
    (:func:`select_keys`)."""
    import jax

    from pio_tpu.parallel.ring import pick_block

    sub = pick_block(bq, INDEX_BLOCK)
    xi, q, k, lse = (jax.lax.stop_gradient(a) for a in (xi, q, k, lse))
    return _index_loss(bq, bk, sub, float(scale), cfg)(ip, xi, q, k, lse,
                                                       *select)


def dsa(blk, h, cfg):
    """A sparse layer on ``[B, T, D]`` (DeepSeek Sparse Attention's form)
    -> ``(attention's output before the residual, counters)``. With ``x``
    the normed input: ``q = RoPE(norm(x W_q))``, ``k = RoPE(norm(x W_k))``
    (the per-head norm where ``attn_qk_norm``), ``v = x W_v``; the indexer
    reads ``x`` detached (:func:`indexer`): ``qi = RoPE_half(x W_qi)``
    (``index_heads`` heads of ``index_head_dim``, the first half of each
    rotated), ``ki = RoPE_half(LayerNorm(x W_ki))`` (one head), ``w = x W_w /
    sqrt(index_heads)``, ``I[t, s] = index_head_dim ** -0.5 sum_j w[t, j]
    relu(qi[t, j] . ki[s])`` for ``s <= t``; ``S_t`` the ``index_topk``
    keys of largest ``I[t]`` (:func:`top_keys`: ties to the earlier key),
    one selection for every head; the heads attend over ``S_t`` alone
    (``ring.attention_partial`` with the selection, key blocks no query of a
    block selected skipped); ``o W_o``. Counters: ``dsa`` ``[selected
    pairs, key blocks the attention's loops ran, those a causal loop runs,
    rows tied at the ``k``-th score]``, ``index_kl`` (:func:`index_loss`,
    summed over the layer's queries; differentiated, its gradient in the
    indexer's parameters is taken here, named :data:`INDEX_GRAD`),
    ``l_select`` (the selected keys' positions summed over the queries:
    :func:`select_keys`)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from pio_tpu.parallel.ring import (attention_partial, fold_groups,
                                       pick_block, unfold_groups)

    cd, eps = _dtype(cfg), cfg.norm_eps
    B, T, _ = h.shape
    H, Hkv, d = cfg.heads_full, cfg.kv_heads, cfg.head_dim
    pos = jnp.arange(T)
    turn = dict(theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim)
    with jax.named_scope("seq.gqa/proj"):
        x = rms_norm(h, blk["attn_norm"], eps)
        q = mm(x, blk["q_proj"], cd).reshape(B, T, H, d)
        k = mm(x, blk["k_proj"], cd).reshape(B, T, Hkv, d)
        if cfg.attn_qk_norm:
            q = rms_norm(q, blk["q_norm"], eps)
            k = rms_norm(k, blk["k_norm"], eps)
        q, k = rope(q, pos, **turn).astype(cd), rope(k, pos, **turn).astype(cd)
        v = mm(x, blk["v_proj"], cd).reshape(B, T, Hkv, d).astype(cd)
    with jax.named_scope("seq.dsa/index"):
        xi = jax.lax.stop_gradient(x)
        ip = {name: blk[name] for name in INDEXER}
        qi, ki, wi = indexer(ip, xi, cfg)
    blk_size = pick_block(T, ATTN_BLOCK)
    group = H // Hkv
    select, pairs, ties, pos_sum = select_keys(qi, ki, wi, cfg.index_topk,
                                               blk_size, blk_size, cd)
    select = tuple(checkpoint_name(a, SELECTION) for a in select)
    scale = cfg.attn_scale or d ** -0.5
    with jax.named_scope("seq.gqa/attn/sparse"):
        o, lse, tiles = attention_partial(
            fold_groups(q, blk_size, group), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), jnp.int32(0), jnp.int32(0), True, scale,
            blk_size, blk_size, 0, group, select)
        o = unfold_groups(o, blk_size, group).astype(cd)
        lse = unfold_groups(lse, blk_size, group)
    kl = index_loss(ip, xi, q, k, lse, select, blk_size, blk_size, scale, cfg)
    with jax.named_scope("seq.gqa/proj"):
        out = mm(o.reshape(B, T, H * d), blk["o_proj"], cd)
    counters = jnp.stack([pairs, tiles[0], tiles[1], ties]).astype(jnp.float32)
    return out, {"dsa": counters, "index_kl": kl, "l_select": pos_sum}


def attend(blk, h, cfg, s_axis, kind):
    """``(attention's output, its counters)`` of the configured attention
    in a layer of the stack ``kind``: the gqa/moe block counts ``tiles``,
    a sparse layer ``dsa`` (:func:`dsa`'s four counters), ``index_kl``
    (its indexer's loss summed over its queries) and ``l_select`` (its
    selection's checksum), the mla/moe block nothing."""
    if kind == "sparse":
        return dsa(blk, h, cfg)
    if cfg.attention_kind == "gqa":
        out, tiles = gqa(blk, h, cfg, s_axis, kind)
        return out, {"tiles": tiles}
    return mla(blk, h, cfg, s_axis), {}


def route(x, router_w, router_b, cfg):
    """``(idx [N, k], gate [N, k], load [E])``: the selected experts of
    every token, their normalised, scaled weights and the count per expert.
    ``router_kind="sigmoid_bias"``: top-k of ``sigmoid(x W_r) + b`` (``b``
    takes no gradient), weights from the sigmoids. ``"softmax"``: top-k of
    ``softmax(x W_r)``, weights from the probabilities, no bias."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if cfg.router_kind == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(s, cfg.experts_per_token)
    else:
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(router_b),
                               cfg.experts_per_token)
    picked = jnp.take_along_axis(s, idx, axis=1)
    gate = cfg.routed_scale * picked / (
        picked.sum(axis=-1, keepdims=True) + 1e-20)
    load = (idx.reshape(-1, 1) == jnp.arange(cfg.n_experts)[None, :]).sum(
        axis=0).astype(jnp.float32)
    return idx, gate, load


def pass_widths(n_pairs: int, held: int, n_experts: int) -> tuple:
    """The passes of the routed experts, each the equal widths of its
    chunks, from what a layer sees at trace time: ``n_pairs`` (token,
    expert) pairs sorted held experts first, ``held`` of ``n_experts``
    experts here. The first pass takes ``MOE_PASS_OVER_HELD`` times the pairs
    a balanced router holds here (whole sublanes of 8; every pair where a
    chip holds every expert). The passes behind it run only while held pairs
    are left, and each takes as many rows as all before it (``w0, w0, 2 w0,
    ..``) while that keeps them within ``MOE_CHUNK_SHARE`` of the pairs (a
    second of the first's width whatever that is); the last takes what is
    left, in equal chunks of at most that share."""
    chunk = max(8, round_up(int(n_pairs * MOE_CHUNK_SHARE), 8))
    first = min(n_pairs, max(8, round_up(
        -(-MOE_PASS_OVER_HELD * n_pairs * held // n_experts), 8)))
    passes, covered = [(first,)], first
    while (covered < n_pairs and covered <= max(chunk // 2, first)
           and len(passes) < MOE_MAX_PASSES - 1):
        passes.append((min(covered, round_up(n_pairs - covered, 8)),))
        covered += passes[-1][0]
    if covered < n_pairs:
        n = -(-(n_pairs - covered) // chunk)
        passes.append((round_up(-(-(n_pairs - covered) // n), 8),) * n)
    return tuple(passes)


def pass_plan(sizes, offsets):
    """``[chunks, held]``: how many rows of each expert's group lie in chunk
    ``c``'s slice ``[offsets[c], offsets[c + 1])`` of the sorted pairs."""
    import jax.numpy as jnp

    ends = jnp.cumsum(sizes)[None, :]
    lo = jnp.asarray(offsets[:-1], sizes.dtype)[:, None]
    hi = jnp.asarray(offsets[1:], sizes.dtype)[:, None]
    return jnp.clip(ends, lo, hi) - jnp.clip(ends - sizes[None, :], lo, hi)


def experts_impl(platform: str, cfg) -> str:
    """Which grouped matmul the routed experts run, from what is visible at
    trace time: ``gmm`` (:func:`grouped_matmul`, the Pallas kernel) on a TPU
    where ``expert_matmul`` asks for it, ``ragged_dot`` (XLA's) everywhere
    else, and the kernel's oracle; ``none`` for a block of single mixers
    without a ``moe`` layer."""
    if not has_experts(cfg):
        return "none"
    return ("gmm" if platform == "tpu" and cfg.expert_matmul == "gmm"
            else "ragged_dot")


def attn_impls(platform: str, cfg, t_local: int) -> Dict[str, str]:
    """What runs the attention tiles of each kind of attention layer the
    block has (``mla``; the gqa block's ``full``, ``window`` and
    ``sparse``), over rows
    of ``t_local`` events: ``ring.attention_impl``'s answer at the shapes
    :func:`mla` and :func:`gqa` hand it. A block with sparse layers adds
    ``index`` where their indexer's scores run on its kernels
    (:func:`index_impl` at the shapes :func:`select_keys` hands it); where
    XLA's einsum computes them the map names the tiles alone, which is
    what the benchmark's CPU rehearsal of the sparse cell reads."""
    from pio_tpu.parallel.ring import attention_impl, pick_block

    blk = pick_block(t_local, ATTN_BLOCK)
    if cfg.attention_kind != "gqa":
        widths = {"mla": (cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.v_head_dim)}
    elif cfg.mixer_pattern:
        widths = {"full": (cfg.head_dim, cfg.head_dim)}
    else:
        widths = {kind: (cfg.head_dim, cfg.head_dim)
                  for kind in sorted(set(cfg.layer_pattern))}
    impls = {kind: attention_impl(platform, _dtype(cfg), d_k, d_v, blk, blk,
                                  True, t_local, kind == "sparse")
             for kind, (d_k, d_v) in widths.items()}
    if "sparse" in impls:
        index = index_impl(platform, _dtype(cfg), cfg.index_heads,
                           cfg.index_head_dim, pick_block(blk, INDEX_BLOCK),
                           t_local)
        if index != "xla":
            impls["index"] = index
    return impls


def _gmm_tiles(which: str, k: int, n: int):
    tm, tk, tn = GMM_TILES[which]
    return tm, min(tk, k), min(tn, n)


def grouped_matmul(a, w, sizes, interpret: bool = False):
    """``a [M, K]`` times ``w [G, K, N]`` by groups of sorted rows (``sizes
    [G]``, ``int32``) -> ``[M, N]`` float32, as ``jax.lax.ragged_dot``, on
    the Pallas TPU kernel JAX ships (megablox ``gmm`` / ``tgmm``). Its grid
    ends at the last row tile a group touches, so the time follows the rows
    given and not ``M``; rows past the last group hold whatever the buffer
    held, in the result and in ``a``'s gradient. Operands stay in ``a``'s
    dtype and accumulate in float32, backward too: the cotangent is cast to
    that dtype before its two matmuls (what XLA's default precision does
    to a float32 operand on a TPU)."""
    import importlib

    import jax
    import jax.numpy as jnp

    # the package's ``gmm`` is its own differentiable wrapper; the module
    # of that name holds the two kernels
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    @jax.custom_vjp
    def run(a, w, sizes):
        return backend.gmm(a, w, sizes, jnp.float32,
                           _gmm_tiles("out", a.shape[1], w.shape[2]),
                           interpret=interpret)

    def fwd(a, w, sizes):
        return run(a, w, sizes), (a, w, sizes)

    def bwd(kept, ct):
        a, w, sizes = kept
        ct = ct.astype(a.dtype)
        k, n = a.shape[1], w.shape[2]
        da = backend.gmm(ct, w, sizes, jnp.float32,
                         _gmm_tiles("d_rows", n, k), transpose_rhs=True,
                         interpret=interpret)
        dw = backend.tgmm(a.swapaxes(0, 1), ct, sizes, jnp.float32,
                          _gmm_tiles("d_weights", k, n),
                          num_actual_groups=w.shape[0], interpret=interpret)
        return da.astype(a.dtype), dw.astype(w.dtype), None

    run.defvjp(fwd, bwd)
    m = a.shape[0]
    # the kernels take whole row tiles (the three row tiles are powers of 2)
    over = -m % max(tiles[0] for tiles in GMM_TILES.values())
    with jax.named_scope("gmm"):
        if over:
            a = jnp.pad(a, ((0, over), (0, 0)))
        return run(a, w, sizes)[:m]


def routed_experts(blk, x, idx, gate, cfg, first, held: int):
    """The held experts' part of the layer's output, dropless
    (``expert_act``: ``W_down(silu(W_gate x) * W_up x)``, or the two-matrix
    ``W_down relu(W_up x)^2``).

    ``x [N, D]``; ``first`` (may be traced) and ``held`` say which experts'
    weights ``blk["e_*"]`` are. The (token, expert) pairs are sorted by
    held expert (pairs of absent experts last) and the three matmuls run
    grouped (``jax.lax.ragged_dot``, or :func:`grouped_matmul` where
    :func:`experts_impl` says) over the sorted pairs, a pass at a time
    (:func:`pass_widths`: the first sized to the pairs held, the passes
    together covering every pair, a wide one chunk after chunk). A pass runs
    only while held pairs are left, so the work follows the load and no pair
    is dropped whatever the imbalance. Returns ``(y [N, D] float32,
    counters)``, the counters ``int32``: ``pairs`` routed to held experts,
    ``dropped`` (those of them that no grouped matmul that ran was given),
    the ``passes`` that ran and the rows they ``staged``."""
    import itertools

    import jax
    import jax.numpy as jnp

    cd = _dtype(cfg)
    impl = experts_impl(jax.default_backend(), cfg)
    N, D = x.shape
    k = cfg.experts_per_token
    M = N * k
    passes = pass_widths(M, held, cfg.n_experts)
    offsets = (0, *itertools.accumulate(itertools.chain(*passes)))
    with jax.named_scope("seq.moe/route"):
        local = idx.reshape(-1) - first
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)
        sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(
            axis=0).astype(jnp.int32)
        pairs = is_held.sum().astype(jnp.int32)
        plan = pass_plan(sizes, offsets)
        pad = offsets[-1] - M
        gate_sorted = jnp.pad(gate.reshape(-1)[order], (0, pad))
        token_sorted = jnp.pad(order // k, (0, pad))
    xc = x.astype(cd)
    gated = cfg.expert_act == "swiglu"
    w_gate, w_up, w_down = (blk[n].astype(cd) if n in blk else None
                            for n in ("e_gate", "e_up", "e_down"))

    @jax.checkpoint
    def weighted(tok, g, sizes_here, valid):
        """One pass's rows ``g_e E_e(x)``; nothing of it is kept for the
        backward pass but its small arguments."""
        rows = valid[:, None]

        def grouped(a, w):
            # the TPU's grouped matmul writes only the rows of a group:
            # the rows past the last group (pairs of absent experts, the
            # padding) hold whatever the buffer held, in its result and in
            # its transpose's. Selecting on both sides keeps them out of
            # the sum and out of every gradient.
            a = jnp.where(rows, a, jnp.zeros((), a.dtype))
            if impl == "gmm":
                return jnp.where(rows, grouped_matmul(a, w, sizes_here), 0.0)
            return jnp.where(rows, jax.lax.ragged_dot(
                a, w, sizes_here, preferred_element_type=jnp.float32), 0.0)

        with jax.named_scope("seq.moe/experts"):
            xs = xc[tok]
            if gated:
                hidden = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
            else:
                hidden = jnp.square(jax.nn.relu(grouped(xs, w_up)))
            ys = grouped(hidden.astype(cd), w_down)
        with jax.named_scope("seq.moe/route"):
            return ys * g[:, None]

    def chunk(y, staged):
        tok, g, sizes_here, valid = staged
        rows = weighted(tok, g, sizes_here, valid)
        with jax.named_scope("seq.moe/route"):
            return y.at[tok].add(rows), None

    # the passes are unrolled, not scanned: a scan would stack what each
    # pass's cond keeps for the backward pass, the experts' weights among it.
    # The last pass scans its chunks inside its own cond, where the weights
    # are the scan's constants and only the chunks' small arguments stack.
    state = (jnp.zeros((N, D), jnp.float32), jnp.zeros(3, jnp.int32))
    done = 0
    for widths in passes:
        n, lo, hi = len(widths), offsets[done], offsets[done + len(widths)]
        staged = (token_sorted[lo:hi].reshape(n, -1),
                  gate_sorted[lo:hi].reshape(n, -1), plan[done:done + n],
                  (jnp.arange(lo, hi) < pairs).reshape(n, -1))
        done += n

        def run(state, staged=staged, n=n, rows=hi - lo):
            y, counted = state
            if n == 1:
                y, _ = chunk(y, jax.tree.map(lambda a: a[0], staged))
            else:
                y, _ = jax.lax.scan(chunk, y, staged)
            # counted where it is consumed: the rows this pass's grouped
            # matmuls were told to compute, the pass, the rows it staged
            return y, counted + jnp.stack([staged[2].sum(), 1, rows])

        state = jax.lax.cond(lo < pairs, run, lambda state: state, state)
    y, (given, ran, staged) = state
    return y, {"pairs": pairs, "dropped": pairs - given, "passes": ran,
               "staged": staged}


def moe(blk, x, cfg, m_axis):
    """Expert feed-forward of the normalised ``x [B, T, D]``: the held
    routed experts' sum (closed over ``m_axis`` when experts shard there)
    plus the shared expert where there is one. Returns ``(y,
    counters)``."""
    import jax
    import jax.numpy as jnp

    B, T, D = x.shape
    flat = x.reshape(B * T, D)
    with jax.named_scope("seq.moe/route"):
        idx, gate, load = route(flat, blk["router_w"], blk.get("router_b"), cfg)
    held = blk["e_up"].shape[0]
    first = cfg.experts_first
    if m_axis is not None:
        first = first + jax.lax.axis_index(m_axis) * held
    y, counters = routed_experts(blk, flat, idx, gate, cfg, first, held)
    if m_axis is not None:
        y, counters = jax.lax.psum((y, counters), m_axis)
    if "s_up" in blk:  # a shared expert
        with jax.named_scope("seq.ffn"):
            if cfg.expert_act == "swiglu":
                y = y + swiglu(flat, blk["s_gate"], blk["s_up"],
                               blk["s_down"], _dtype(cfg))
            else:
                y = y + relu2_mlp(flat, blk["s_up"], blk["s_down"],
                                  _dtype(cfg))
    counters = {"load": load, **jax.tree.map(
        lambda a: a.astype(jnp.float32), counters)}
    return y.reshape(B, T, D), counters


def carried_states(own, decay):
    """The state that enters each chunk. ``own [C, ...]`` is what each
    chunk's own events leave behind at its end and ``decay [C, ...]`` (two
    dims fewer) what the chunk's steps leave of a state that enters it:
    ``S_in[0] = 0``, ``S_in[c + 1] = decay[c] S_in[c] + own[c]``, float32,
    one chunk after another."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):
        own_c, decay_c = xs
        return decay_c[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(step, jnp.zeros_like(own[0]), (own, decay))
    return entering


def ssd_scan(x, dt, a, b, c, chunk: int, cd, head_block: int = 0):
    """Mamba-2's selective recurrence by chunks (the SSD form).

    ``x [B, T, H, P]``, step sizes ``dt [B, T, H]`` (positive), ``a [H]``
    (negative), ``b, c [B, T, G, N]``, all float32; head ``h`` reads group
    ``h // (H / G)``. Computes ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
    b_t`` (``S_{-1} = 0``), ``y_t = S_t c_t`` without holding a state a
    step: inside a chunk of ``chunk`` steps (clamped to a divisor of ``T``)
    ``Y = (L * C B^T)(dt X)`` with ``L_ts = exp(sum_{s<r<=t} dt_r a)``; each
    chunk's own state; the states carried from chunk to chunk
    (:func:`carried_states`); ``C`` against the state that entered. The
    chunks are batched, the heads mapped a block after another under
    ``jax.checkpoint``: the ``[chunk, chunk]`` weights of one block's heads
    stand at a time, forward and in the recomputing backward pass. A block
    is ``head_block`` heads of one group (clamped to a divisor of the
    group's ``H / G``; 0 = the group): where it is the group, ``C B^T`` is
    computed in the group's turn; where a group takes several turns, once a
    group before the map, and each turn reads its group's. Matmul operands
    are cast to ``cd`` and accumulate in float32; the cumulative sums, every
    ``exp`` and the carried state are float32.

    Returns ``(y [B, T, H, P] float32, chunks, absmax, turns)``: the chunks
    a block's carrying loop ran times the rows, the largest magnitude of a
    carried state, and the turns of the map, read from its length."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import pick_block

    B, T, H, P = x.shape
    G, N = b.shape[2:]
    Q = pick_block(T, chunk)
    C = T // Q
    R = pick_block(H // G, head_block) if head_block else H // G
    per_group = H // G // R  # turns a group takes
    f32 = jnp.float32
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def scores(cg, bg):
        return jnp.einsum("...qn,...sn->...qs", cg, bg,
                          preferred_element_type=f32)

    def heads(xg, dtg, ag, bg, cg, cb):
        """One block: ``xg [B,C,Q,R,P]``, ``dtg [B,C,Q,R]``, ``ag [R]``, its
        group's ``bg, cg [B,C,Q,N]`` (in ``cd``) and ``cb = C B^T``."""
        # log of what the steps up to and with t leave of a state
        cum = jnp.cumsum(dtg * ag, axis=2)  # [B, C, Q, R]
        dtx32 = dtg[..., None] * xg
        dtx = dtx32.astype(cd)
        # inside a chunk: Y = (L * C B^T) (dt X)
        by_head = cum.transpose(0, 1, 3, 2)  # [B, C, R, Q]
        span = by_head[..., :, None] - by_head[..., None, :]
        weights = jnp.exp(jnp.where(seen, span, -jnp.inf)) * cb[:, :, None]
        y = jnp.einsum("bcrqs,bcsrp->bcqrp", weights.astype(cd), dtx,
                       preferred_element_type=f32)
        # each chunk's own state at its end, and the states carried forward
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        own = jnp.einsum("bcsrp,bcsn->bcrpn",
                         (to_end[..., None] * dtx32).astype(cd), bg,
                         preferred_element_type=f32)
        entering = jnp.swapaxes(carried_states(
            jnp.swapaxes(own, 0, 1),
            jnp.swapaxes(jnp.exp(cum[:, :, -1]), 0, 1)), 0, 1)  # [B,C,R,P,N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bcqn,bcrpn->bcqrp", cg, entering.astype(cd),
            preferred_element_type=f32)
        chunks = jnp.float32(B * entering.shape[1])  # the loop's length
        return y, chunks, jnp.abs(jax.lax.stop_gradient(entering)).max()

    def by_block(v, *rest):  # [B, T, H, ..] -> [blocks, B, C, Q, R, ..]
        return jnp.moveaxis(v.reshape(B, C, Q, H // R, R, *rest), 3, 0)

    def by_group(v):  # [B, T, G, N] -> [G, B, C, Q, N]
        return jnp.moveaxis(v.reshape(B, C, Q, G, N), 3, 0)

    if per_group == 1:
        @jax.checkpoint
        def turn(args):
            xg, dtg, ag, bg, cg = args
            bg, cg = bg.astype(cd), cg.astype(cd)
            return heads(xg, dtg, ag, bg, cg, scores(cg, bg))

        mapped = (by_block(x, P), by_block(dt), a.reshape(G, R), by_group(b),
                  by_group(c))
    else:
        b_all, c_all = by_group(b).astype(cd), by_group(c).astype(cd)
        cb_all = scores(c_all, b_all)  # [G, B, C, Q, Q], once a group

        @jax.checkpoint
        def turn(args):
            xg, dtg, ag, g = args
            return heads(xg, dtg, ag, b_all[g], c_all[g], cb_all[g])

        mapped = (by_block(x, P), by_block(dt), a.reshape(H // R, R),
                  jnp.arange(H // R) // per_group)
    y, chunks, absmax = jax.lax.map(turn, mapped)
    return (jnp.moveaxis(y, 0, 3).reshape(B, T, H, P), chunks[0],
            absmax.max(), jnp.float32(y.shape[0]))


def ssd_impl(platform: str, cd, chunk: int, p: int, n: int,
             heads_per_group: int) -> str:
    """What runs :func:`ssd_scan`'s chunks, from what is visible at trace
    time: ``pallas`` / ``xla``. ``pallas`` is the pair of kernels of
    :mod:`pio_tpu.models.ssd_kernel` (a head block's state in VMEM across
    its chunks, the decay tiles never in HBM): on a TPU, with bfloat16
    operands, a chunk and a state width that are multiples of the 128 lanes,
    a group's ``x`` columns a multiple of the state width (the kernels read
    ``B`` and ``C`` where they lie beside ``x``), and a head block whose
    ``x`` columns fill whole lane tiles and whose tiles fit VMEM
    (``ssd_kernel.head_block``, ``ssd_kernel.fits``). ``xla`` is
    :func:`ssd_scan`: everywhere else (every CPU run, float32 operands,
    chunks of 64), and the kernels' oracle."""
    import jax.numpy as jnp

    from pio_tpu.models import ssd_kernel

    r = ssd_kernel.head_block(heads_per_group, p)
    tiles = (jnp.dtype(cd) == jnp.bfloat16 and chunk % 128 == 0
             and n % 128 == 0 and heads_per_group * p % n == 0
             and ssd_kernel.fits(chunk, p, n, r))
    return "pallas" if platform == "tpu" and tiles else "xla"


def ssm_impl(platform: str, cfg, t_local: int) -> str:
    """What runs the chunks of the block's Mamba-2 mixers over rows of
    ``t_local`` events: :func:`ssd_impl`'s answer at the shapes
    :func:`mamba` hands it; ``none`` for a block without a mamba layer."""
    from pio_tpu.parallel.ring import pick_block

    if "mamba" not in cfg.mixer_pattern:
        return "none"
    return ssd_impl(platform, _dtype(cfg), pick_block(t_local, cfg.ssm_chunk),
                    cfg.ssm_head_dim, cfg.ssm_state,
                    cfg.ssm_heads // cfg.ssm_groups)


def mamba(blk, h, cfg):
    """The Mamba-2 mixer of the normed ``h [B, T, D]`` -> ``(its output
    before the residual, counters)``: ``[z | xBC | dt] = x W_in``; ``xBC <-
    silu(conv(xBC) + b)``, a causal depthwise convolution over the last
    ``ssm_conv`` steps (zeros before the first); ``xBC`` split into ``x [H,
    P]``, ``B, C [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``; the recurrence (:func:`ssd_scan`) plus ``D x``; the gate
    first, ``u = y silu(z)``, then RMSNorm over each of the ``G`` groups of
    channels times a gain; ``u W_out``. The recurrence runs on
    :mod:`pio_tpu.models.ssd_kernel` where :func:`ssd_impl` says ``pallas``
    (``pallas_interpret``, the same kernels interpreted, in tests), else on
    :func:`ssd_scan`; ``ssm_head_blocks`` is then the kernels' blocks of
    heads. The sequence is whole here
    (``check_block`` refuses a ``seq`` axis). The convolution and the gated
    norm are recomputed in the backward pass from their inputs, as the
    recurrence is: beside 16 B a parameter a layer's float32 ``[T, 4096]``
    intermediates do not all fit."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import pick_block

    cd, eps = _dtype(cfg), cfg.norm_eps
    B, T, _ = h.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, conv = ssm_widths(cfg)
    K = cfg.ssm_conv

    @jax.checkpoint
    def convolved(xbc, w, bias):
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        return jax.nn.silu(bias + sum(
            w[j] * padded[:, j:j + T] for j in range(K)))

    @jax.checkpoint
    def gated_norm(y, z, gain):
        u = (y * jax.nn.silu(z)).reshape(B, T, G, inner // G)
        u = u * jax.lax.rsqrt((u * u).mean(axis=-1, keepdims=True) + eps)
        return u.reshape(B, T, inner) * gain

    with jax.named_scope("seq.ssm/proj"):
        zxd = mm(rms_norm(h, blk["norm"], eps), blk["in_proj"], cd)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + conv],
                      zxd[..., inner + conv:])
    with jax.named_scope("seq.ssm/conv"):
        xbc = convolved(xbc, blk["conv_w"], blk["conv_b"])
    with jax.named_scope("seq.ssm/ssd"):
        dt = jax.nn.softplus(dt + blk["dt_bias"])
        a = -jnp.exp(blk["a_log"])
        Q = pick_block(T, cfg.ssm_chunk)
        impl = ssd_impl(jax.default_backend(), cd, Q, P, N, H // G)
        if impl == "xla":
            x = xbc[..., :inner].reshape(B, T, H, P)
            b = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
            c = xbc[..., inner + G * N:].reshape(B, T, G, N)
            y, chunks, absmax, turns = ssd_scan(
                x, dt, a, b, c, Q, cd, SSM_HEAD_BLOCK)
            y = (y + blk["d_skip"][:, None] * x).reshape(B, T, inner)
        else:
            from pio_tpu.models import ssd_kernel

            y, chunks, absmax, turns = ssd_kernel.scan(
                xbc, dt, a, blk["d_skip"], (H, P, G, N), Q, cd,
                impl == "pallas_interpret")
    with jax.named_scope("seq.ssm/norm"):
        u = gated_norm(y, z, blk["gate_g"])
    with jax.named_scope("seq.ssm/proj"):
        out = mm(u, blk["out_proj"], cd)
    return out, {"ssm_chunks": chunks, "ssm_state_absmax": absmax,
                 "ssm_head_blocks": turns}


def residual(h, out, cfg):
    """``h + residual_scale * out``: what a layer adds to the stream."""
    if cfg.residual_scale != 1.0:
        out = out * cfg.residual_scale
    return h + out


def dense_mlp(blk, h, cfg, norm: str):
    """``h`` plus the dense SwiGLU of ``rms_norm(h, blk[norm])``,
    ``TOKEN_CHUNK`` tokens at a time."""
    import jax

    with jax.named_scope("seq.ffn"):
        B, T, D = h.shape
        x = rms_norm(h, blk[norm], cfg.norm_eps).reshape(B * T, D)
        return residual(h, swiglu(
            x, blk["w_gate"], blk["w_up"], blk["w_down"], _dtype(cfg),
            TOKEN_CHUNK).reshape(B, T, D), cfg)


def mixer_layer(blk, h, cfg, m_axis, s_axis, kind):
    """``(h + mixer(norm(h)), the mixer's counters)`` of a layer that is one
    mixer alone (``blk`` has no layer dim): a ``mamba`` layer counts its
    chunks, its map's turns and its largest carried state, a ``moe`` layer
    what :func:`moe` counts, an ``attn`` or ``mlp`` layer nothing."""
    if kind == "mlp":
        return dense_mlp(blk, h, cfg, "norm"), {}
    if kind == "mamba":
        out, counters = mamba(blk, h, cfg)
    elif kind == "attn":
        out, counters = gqa(blk, h, cfg, s_axis, "full")[0], {}
    else:
        out, counters = moe(
            blk, rms_norm(h, blk["ffn_norm"], cfg.norm_eps), cfg, m_axis)
    return residual(h, out, cfg), counters


def dense_layer(blk, h, cfg, m_axis, s_axis, kind):
    """``(h, the attention's counters)`` of one dense layer."""
    out, counters = attend(blk, h, cfg, s_axis, kind)
    return dense_mlp(blk, residual(h, out, cfg), cfg, "ffn_norm"), counters


def expert_layer(blk, h, cfg, m_axis, s_axis, kind):
    """``(h, counters)`` of one expert layer (``blk`` has no layer dim)."""
    out, attn_counters = attend(blk, h, cfg, s_axis, kind)
    h = residual(h, out, cfg)
    y, counters = moe(blk, rms_norm(h, blk["ffn_norm"], cfg.norm_eps), cfg,
                      m_axis)
    return residual(h, y, cfg), {**counters, **attn_counters}


def update_router_bias(router_b, load, rate: float):
    """DeepSeek-V3's auxiliary-loss-free balancing: after a step, every
    expert's selection bias moves by ``rate`` towards the mean load."""
    import jax.numpy as jnp

    return router_b + rate * jnp.sign(
        load.mean(axis=-1, keepdims=True) - load)
