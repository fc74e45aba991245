"""Plain reference of the ``nemotron_h`` training step (NVIDIA-Nemotron-3-Nano's
block: layers that are one mixer alone): forward, loss, gradients and Adam in
straightforward ``jax.numpy``, float32, ``jax.default_matmul_precision(
"highest")``. Imports nothing of ``pio_tpu``; the rounding, the norm, the
head's loss and the host's Adam are ``seq_reference``'s own, the rotation of
the planted RoPE fault and the stacks' slicing ``laguna_reference``'s, unchanged.

Written from the published ``config.json`` (``m`` is the model dict
:func:`model` makes of the configuration's file; ``x`` is the residual stream
of one row, ``[T, D]``; every norm is RMSNorm; no bias but the convolution's).
Layer ``l`` is ``x + mixer_l(norm_l(x))``, its one mixer the kind that
``hybrid_override_pattern[l]`` names:

- ``M``, Mamba-2 (``H`` heads of ``P`` channels, ``G`` groups of state ``N``,
  ``K`` taps): ``[z | xBC | dt] = xn W_in``; ``xBC <- silu(sum_j w_j xBC_{t-K+1+j}
  + b)``, zeros before the first event; ``xBC`` split into ``x_t [H, P]``,
  ``B_t, C_t [G, N]``; ``dt_t = softplus(dt_t + dt_bias)``; ``A = -exp(A_log)``;
  for head ``h`` with its group ``g = h // (H / G)``: ``S_t = exp(dt_t A) S_{t-1}
  + dt_t x_t (x) B_t`` (``S_{-1} = 0``), ``y_t = S_t C_t + D x_t``. **The
  recurrence itself, one time step after another** (``lax.scan``), not the
  chunked form the program computes. Then the gate first, ``u = y silu(z)``,
  RMSNorm over each of the ``G`` groups of ``H P / G`` channels, times a gain;
  ``u W_out``.
- ``E``, experts: ``s = sigmoid(xn W_r)``; the ``num_experts_per_tok`` largest
  of ``s + b``; ``w = scale * s_top / (sum(s_top) + 1e-20)``; ``y = sum over e
  selected and held of w_e E_e(xn) + E_shared(xn)``, ``E(x) = W_down relu(W_up
  x)^2``. Dense over tokens: every held expert runs on every token and is
  weighted by ``w_e`` or 0. ``b`` takes no gradient; after a step it moves by
  ``bias_update_rate * sign(mean load - load)``.
- ``*``, attention: ``q = xn W_q`` ``[T, H_q, d]``, ``k, v`` ``[T, H_kv, d]``;
  **no position encoding**; query head ``j`` scores against KV head ``j //
  (H_q / H_kv)``, ``q k^T / sqrt(d)``, causal softmax; ``concat(o) W_o``. One
  query head at a time, a dense mask.
- Loss: mean cross-entropy of the next event over the vocabulary slice.
- Adam as ``optax.adam``.

Arranged only so that it fits one chip: rows one at a time, ``jax.checkpoint``
a row, a layer, a block of ``STEP_BLOCK`` time steps, a query head and chunk,
an expert and a 2,048-token slice of the head. None of that changes a number.

``quantize = k`` rounds both operands of every matmul but the router's to ``k``
mantissa bits, and what the recurrence multiplies (``dt x``, ``B``, ``C``): 7
is the configuration's own bfloat16, the witness; 3 the control. ``fault``
plants one wrong equation (``FAULTS``).
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

from laguna_reference import Q_CHUNK, _plain_inv_freq, _rotate, _stack
from seq_reference import _adam, _ce_sum, _dot, _norm, _rounded

FAULTS = ("state_not_carried", "conv_left_out", "dt_bias_ignored",
          "a_log_for_a", "head_group_mod", "norm_over_all_channels",
          "norm_before_gate", "d_left_out", "silu_gated_experts",
          "rope_applied", "kv_head_mod", "topk_not_normalised", "scale_one",
          "bias_ignored", "expert_dropped", "half_batch")
GROUPS = ("embedding", "head", "ssm_proj", "ssm_scan", "attn", "router",
          "routed_experts", "shared_expert", "norms")
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
#: time steps to a checkpointed block of the recurrence: the backward pass
#: holds a block's states (2 MB a step at the published sizes), not a layer's
STEP_BLOCK = 256
#: the per-step numbers a training call's trace and this reference share
TRACE_KEYS = ("l_main", "pairs", "grad_norm")
LOSSES = ("main",)


# ------------------------------------------------------------------- model
def model(config: dict) -> dict:
    """The flat dict the layers below read, from the configuration's file:
    the published keys, the layers here (the first ``num_hidden_layers``
    characters of the pattern), the experts and vocabulary rows held, the
    ``init`` rule's numbers, the bias's rate and the learning rate."""
    n = config["num_hidden_layers"]
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != n or set(pattern) - set(KINDS):
        raise ValueError("hybrid_override_pattern names every layer here")
    if (config["mamba_hidden_act"], config["mlp_hidden_act"]) != (
            "silu", "relu2") or (config["n_group"], config["topk_group"]) != (
            1, 1) or not config["norm_topk_prob"] or not config[
            "use_conv_bias"] or config["mamba_proj_bias"] or config[
            "attention_bias"] or config["mlp_bias"] or config[
            "tie_word_embeddings"] or config["n_shared_experts"] != 1:
        raise ValueError("a key this reference has no equation for")
    init = config["init"]
    return {
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "num_hidden_layers": n,
        "mixer_pattern": tuple(KINDS[c] for c in pattern),
        "mamba_num_heads": config["mamba_num_heads"],
        "mamba_head_dim": config["mamba_head_dim"],
        "n_groups": config["n_groups"],
        "ssm_state_size": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"],
        "chunk_size": config["chunk_size"],
        "time_step_min": config["time_step_min"],
        "time_step_max": config["time_step_max"],
        "time_step_floor": config["time_step_floor"],
        "head_dim": config["head_dim"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": config["norm_eps"],
        "router_width": config["deployment"]["router_width"],
        "experts_first": config["deployment"]["experts_first"],
        "experts_held": config["n_routed_experts"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "moe_shared_expert_intermediate_size":
            config["moe_shared_expert_intermediate_size"],
        "shared_experts": config["moe_shared_expert_intermediate_size"]
        // config["moe_intermediate_size"],
        "routed_scaling_factor": config["routed_scaling_factor"],
        "init_std": init["init_std"],
        "embed_init_std": init["embed_init_std"],
        "bias_init_std": init["bias_init_std"],
        "bias_update_rate": config["bias_update_rate"],
        "learning_rate": config["algorithm_params"]["learning_rate"],
    }


def ssm_widths(m: dict) -> tuple:
    """``(inner, convolved)``: the heads' channels, and ``x | B | C``."""
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    return inner, inner + 2 * m["n_groups"] * m["ssm_state_size"]


# ------------------------------------------------------------------ shapes
def shapes(m: dict) -> dict:
    """``{"stack/name": shape}``: the layers stacked by kind, ``mamba/*``,
    ``moe/*`` and ``attn/*``, in the order they occur."""
    D, V, H = m["hidden_size"], m["vocab_size"], m["mamba_num_heads"]
    inner, conv = ssm_widths(m)
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    Fe, Fs = m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]
    held, E = m["experts_held"], m["router_width"]
    out = {"emb": (V, D), "head": (V, D), "lnf_g": (D,)}
    of_kind = {
        "mamba": lambda L: {
            "norm": (L, D), "in_proj": (L, D, inner + conv + H),
            "conv_w": (L, m["conv_kernel"], conv), "conv_b": (L, conv),
            "a_log": (L, H), "d_skip": (L, H), "dt_bias": (L, H),
            "gate_g": (L, inner), "out_proj": (L, inner, D)},
        "moe": lambda L: {
            "ffn_norm": (L, D), "router_w": (L, D, E), "router_b": (L, E),
            "e_up": (L, held, D, Fe), "e_down": (L, held, Fe, D),
            "s_up": (L, D, Fs), "s_down": (L, Fs, D)},
        "attn": lambda L: {
            "attn_norm": (L, D), "q_proj": (L, D, Hq * d),
            "k_proj": (L, D, Hkv * d), "v_proj": (L, D, Hkv * d),
            "o_proj": (L, Hq * d, D)},
    }
    for kind in ("mamba", "moe", "attn"):
        L = m["mixer_pattern"].count(kind)
        if L:
            out.update({f"{kind}/{k}": v for k, v in of_kind[kind](L).items()})
    return out


def init_params(m: dict, seed: int) -> dict:
    """The configuration's rule. A norm's gain, the gated norm's gain and
    ``d_skip`` are 1. Every other parameter is drawn under ``fold_in(PRNGKey(
    seed), crc32(path) & 0x7fffffff)``, float32: ``a_log = log U[1, 16)``;
    ``dt_bias = dt + log(-expm1(-dt))``, ``dt = max(exp(U[0, 1) (ln max - ln
    min) + ln min), floor)``; ``conv_w``, ``conv_b`` ``U[-K ** -0.5, K ** -0.5)``;
    the rest normal times ``init_std`` (``embed_init_std`` for ``emb``,
    ``bias_init_std`` for ``router_b``)."""
    import math

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    out = {}
    for path, shape in shapes(m).items():
        name = path.rpartition("/")[2]
        if name.endswith("norm") or name in ("lnf_g", "gate_g", "d_skip"):
            out[path] = jnp.ones(shape, f32)
            continue
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(path.encode()) & 0x7FFFFFFF)
        if name == "a_log":
            out[path] = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        elif name == "dt_bias":
            lo, hi = m["time_step_min"], m["time_step_max"]
            dt = jnp.maximum(
                jnp.exp(jax.random.uniform(key, shape, f32)
                        * f32(math.log(hi) - math.log(lo)) + f32(math.log(lo))),
                f32(m["time_step_floor"]))
            out[path] = dt + jnp.log(-jnp.expm1(-dt))
        elif name in ("conv_w", "conv_b"):
            bound = m["conv_kernel"] ** -0.5
            out[path] = jax.random.uniform(key, shape, f32, -bound, bound)
        else:
            std = m[{"router_b": "bias_init_std",
                     "emb": "embed_init_std"}.get(name, "init_std")]
            out[path] = jax.random.normal(key, shape, f32) * f32(std)
    return out


def group_of(path: str, m: dict = None) -> str:
    """Which of ``GROUPS`` a parameter is compared under: a mamba layer's two
    projections apart from what its recurrence reads, the attention layers'
    four projections together, every norm's gain together."""
    stack, _, name = path.rpartition("/")
    if name.endswith("norm") or name == "lnf_g":
        return "norms"
    if path == "emb":
        return "embedding"
    if path == "head":
        return "head"
    if stack == "mamba":
        return "ssm_proj" if name.endswith("_proj") else "ssm_scan"
    if stack == "attn":
        return "attn"
    if name.startswith("router"):
        return "router"
    return {"e_": "routed_experts", "s_": "shared_expert"}[name[:2]]


# ------------------------------------------------------------------- layers
def _is(fault, name: str):
    """Whether the planted fault is ``name``: a Python bool for a name (or
    ``None``); a traced bool where ``fault`` is a number, 1 + its place in
    ``FAULTS`` and 0 for none, so one compiled step serves every fault."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


def recurrence(dtx, dt, a, b, c, group_of_head, reset_every=None):
    """``y [T, H, P]`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``,
    ``y_t = S_t c_t``, one time step after another from ``S_{-1} = 0``. ``dtx
    [T, H, P]`` is ``dt_t x_t``, ``dt [T, H]``, ``a [H]``, ``b, c [T, G, N]``;
    head ``h`` reads group ``group_of_head[h]``. ``reset_every`` (a planted fault) forgets the
    state before every step whose index it divides. The steps run in blocks
    of ``STEP_BLOCK`` under ``jax.checkpoint``."""
    import jax
    import jax.numpy as jnp

    T, H, P = dtx.shape
    N = b.shape[-1]
    block = STEP_BLOCK if T % STEP_BLOCK == 0 else T

    def step(state, xs):
        t, dt_t, dtx_t, b_t, c_t = xs
        if reset_every is not None:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        b_h, c_h = b_t[group_of_head], c_t[group_of_head]  # [H, N]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dtx_t[:, :, None] * b_h[:, None, :])
        return state, (state * c_h[:, None, :]).sum(axis=-1)

    @jax.checkpoint
    def steps(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = jax.tree.map(lambda v: v.reshape(T // block, block, *v.shape[1:]),
                      (jnp.arange(T), dt, dtx, b, c))
    _, y = jax.lax.scan(steps, jnp.zeros((H, P, N), jnp.float32), xs)
    return y.reshape(T, H, P)


def _mamba(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> the mixer's output ``[T, D]``."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, P, G, N = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state_size"])
    K, eps = m["conv_kernel"], m["rms_norm_eps"]
    inner, conv = ssm_widths(m)
    zxd = _dot(_norm(x, w["norm"], eps), w["in_proj"], q)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), xbc.dtype), xbc])
    convolved = w["conv_b"] + sum(w["conv_w"][j] * padded[j:j + T]
                                  for j in range(K))
    xbc = jax.nn.silu(jnp.where(_is(fault, "conv_left_out"), xbc, convolved))
    xs = xbc[:, :inner].reshape(T, H, P)
    b = xbc[:, inner:inner + G * N].reshape(T, G, N)
    c = xbc[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + jnp.where(_is(fault, "dt_bias_ignored"), 0.0,
                                        w["dt_bias"]))
    a = jnp.where(_is(fault, "a_log_for_a"), -w["a_log"], -jnp.exp(w["a_log"]))
    heads = jnp.arange(H)
    group_of_head = jnp.where(_is(fault, "head_group_mod"), heads % G,
                              heads // (H // G))
    reset = jnp.where(_is(fault, "state_not_carried"), m["chunk_size"], T + 1)
    dtx = dt[:, :, None] * xs
    if q is not None:  # what the recurrence multiplies, in the low precision
        dtx, b, c = _rounded(dtx, q), _rounded(b, q), _rounded(c, q)
    y = recurrence(dtx, dt, a, b, c, group_of_head, reset)
    y = y + jnp.where(_is(fault, "d_left_out"), 0.0, w["d_skip"])[:, None] * xs
    y, gate = y.reshape(T, inner), jax.nn.silu(z)

    def normed(u):
        by_group = u.reshape(T, G, inner // G)
        by_group = by_group / jnp.sqrt(
            (by_group * by_group).mean(axis=-1, keepdims=True) + eps)
        whole = u / jnp.sqrt((u * u).mean(axis=-1, keepdims=True) + eps)
        return jnp.where(_is(fault, "norm_over_all_channels"), whole,
                         by_group.reshape(T, inner)) * w["gate_g"]

    u = jnp.where(_is(fault, "norm_before_gate"), normed(y) * gate,
                  normed(y * gate))
    return _dot(u, w["out_proj"], q)


def _attention(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> attention's output ``[T, D]``
    before the residual: causal, grouped queries, no position encoding."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    xn = _norm(x, w["attn_norm"], m["rms_norm_eps"])
    qh = _dot(xn, w["q_proj"], q).reshape(T, H, d)
    kh = _dot(xn, w["k_proj"], q).reshape(T, Hkv, d)
    vh = _dot(xn, w["v_proj"], q).reshape(T, Hkv, d)
    turned = _plain_inv_freq(m["rope_theta"], d)
    qh = jnp.where(_is(fault, "rope_applied"), _rotate(qh, turned), qh)
    kh = jnp.where(_is(fault, "rope_applied"), _rotate(kh, turned), kh)
    kh, vh = kh.transpose(1, 0, 2), vh.transpose(1, 0, 2)  # [Hkv, T, d]
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T
    s_pos = jnp.arange(T)

    def head(args):
        qj, j = args  # [T, d] of query head j
        pair = jnp.where(_is(fault, "kv_head_mod"), j % Hkv, j // (H // Hkv))
        kj, vj = kh[pair], vh[pair]

        @jax.checkpoint
        def rows(args):
            qc, t_pos = args  # a chunk of queries against all keys
            s = _dot(qc, kj.T, q) / np.sqrt(d)
            p = jax.nn.softmax(
                jnp.where(t_pos[:, None] >= s_pos[None, :], s, -jnp.inf), axis=-1)
            return _dot(p, vj, q)

        return jax.lax.map(rows, (qj.reshape(-1, chunk, d),
                                  s_pos.reshape(-1, chunk))).reshape(T, d)

    per_head = jax.lax.map(jax.checkpoint(head),
                           (qh.transpose(1, 0, 2), jnp.arange(H)))
    return _dot(per_head.transpose(1, 0, 2).reshape(T, H * d), w["o_proj"], q)


def _route(w, xn, m, fault):
    """``(idx [T, k], weight [T, k])`` of the normalised input."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(xn, w["router_w"],
                               precision=jax.lax.Precision.HIGHEST))
    choose = jnp.where(_is(fault, "bias_ignored"), s, s + w["router_b"])
    _, idx = jax.lax.top_k(choose, m["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, axis=1)
    picked = picked / jnp.where(
        _is(fault, "topk_not_normalised"), 1.0,
        picked.sum(axis=-1, keepdims=True) + 1e-20)
    scale = jnp.where(_is(fault, "scale_one"), 1.0, m["routed_scaling_factor"])
    return idx, scale * picked


def _expert(x, w_up, w_down, q, fault):
    """``W_down relu(W_up x)^2``."""
    import jax
    import jax.numpy as jnp

    up = _dot(x, w_up, q)
    hidden = jnp.where(_is(fault, "silu_gated_experts"), jax.nn.silu(up) * up,
                       jnp.square(jax.nn.relu(up)))
    return _dot(hidden, w_down, q)


def _moe(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> ``(y [T, D], load [E], pairs)``:
    held experts dense over tokens."""
    import jax
    import jax.numpy as jnp

    xn = _norm(x, w["ffn_norm"], m["rms_norm_eps"])
    idx, gate = _route(w, xn, m, fault)
    E, held, first = m["router_width"], m["experts_held"], m["experts_first"]
    onehot = idx[:, :, None] == jnp.arange(E)[None, None, :]  # [T, k, E]
    load = onehot.sum(axis=(0, 1)).astype(jnp.float32)
    weight = (gate[:, :, None] * onehot).sum(axis=1)[:, first:first + held]
    pairs = load[first:first + held].sum()
    weight = weight.at[:, held - 1].multiply(
        jnp.where(_is(fault, "expert_dropped"), 0.0, 1.0))

    @jax.checkpoint
    def expert(args):
        wu, wd, we = args
        return we[:, None] * _expert(xn, wu, wd, q, fault)

    y, _ = jax.lax.scan(
        lambda acc, args: (acc + expert(args), None), jnp.zeros_like(xn),
        (w["e_up"], w["e_down"], weight.T))
    return y + _expert(xn, w["s_up"], w["s_down"], q, fault), load, pairs


def trunk(params, ids, m, q=None, fault=None):
    """One row ``ids [T]`` -> ``(h [T, D] before the final norm, load [Le, E],
    pairs [Le])``, layer by layer in the model's order."""
    import jax
    import jax.numpy as jnp

    x = params["emb"][ids]
    taken = dict.fromkeys(KINDS.values(), 0)
    loads, pairs = [], []
    for kind in m["mixer_pattern"]:
        w = {k: v[taken[kind]] for k, v in _stack(params, kind).items()}
        taken[kind] += 1
        if kind == "moe":
            y, load, n = jax.checkpoint(
                lambda w, x: _moe(w, x, m, q, fault))(w, x)
            loads.append(load)
            pairs.append(n)
        else:
            mixer = _mamba if kind == "mamba" else _attention
            y = jax.checkpoint(
                lambda w, x, mixer=mixer: mixer(w, x, m, q, fault))(w, x)
        x = x + y
    return x, jnp.stack(loads), jnp.stack(pairs)


def row_sums(params, ids, m, q=None, fault=None):
    """One row's ``(ce, load [Le, E], pairs [Le])``; the target is the next
    event, 0 = pad, unsupervised."""
    import jax.numpy as jnp

    t1 = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    m1 = ((t1 > 0) & (ids > 0)).astype(jnp.float32)
    h, load, pairs = trunk(params, ids, m, q, fault)
    return _ce_sum(h, params["lnf_g"], params["head"], t1, m1, m, q), load, pairs


def batch_loss(params, rows, m, q=None, fault=None):
    """``(loss, (l_main, load, pairs))`` of a batch ``rows [B, T]``, one row
    at a time."""
    import jax
    import jax.numpy as jnp

    ce, load, pairs = jax.lax.map(
        jax.checkpoint(lambda ids: row_sums(params, ids, m, q, fault)), rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    l_main = ce.sum() / jnp.maximum(((t1 > 0) & (rows > 0)).sum(), 1)
    return l_main, (l_main, load.sum(axis=0), pairs.sum(axis=0))


def grad_norms(grads: dict):
    """``[len(GROUPS)]``: the Frobenius norm of each group's gradient."""
    import jax.numpy as jnp

    total = dict.fromkeys(GROUPS, 0.0)
    for path, g in grads.items():
        total[group_of(path)] = total[group_of(path)] + jnp.sum(g * g)
    return jnp.sqrt(jnp.stack([jnp.asarray(total[k], jnp.float32)
                               for k in GROUPS]))


@functools.lru_cache(maxsize=4)
def _grad_fn(m_items: tuple):
    """The batch's gradients, one jitted program: ``(params, rows, fault,
    bits) -> (grads, (l_main, load, pairs, grad_norms))``; ``fault`` a number
    (:func:`_is`) and ``bits`` ``None`` or the traced number of mantissa bits,
    as ``seq_reference._grad_fn`` has them."""
    import jax

    m = dict(m_items)

    @jax.jit
    def grad(params, rows, fault, bits):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(batch_loss, has_aux=True)(
                params, rows, m, bits, fault)
        return grads, aux + (grad_norms(grads),)

    return grad


def train(m: dict, sequences, *, seed: int, steps: int, batch: int,
          quantize=None, fault=None, on_step=None) -> dict:
    """``steps`` Adam steps from the rule's weights; step ``i`` takes rows
    ``[(i mod n/batch) * batch, + batch)``. The gradients come from the
    device; Adam and the selection bias's rule run on the host. Returns
    numpy: ``l_main`` ``[steps]``, ``pairs`` ``[steps, expert layers]``,
    ``grad_norm`` ``[steps, len(GROUPS)]``, ``init`` and ``final`` ``{path:
    array}``. ``on_step(i, params, grads)`` sees each step first.
    ``half_batch`` pads out the second half of every step's events: of its
    rows, or of its one row's positions."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    if quantize is not None and not 1 <= int(quantize) <= 22:
        raise ValueError("quantize is a number of mantissa bits, 1 to 22")
    params = {k: np.array(v)  # writable copies
              for k, v in jax.device_get(init_params(m, seed)).items()}
    mom = var = None  # made after the first gradients: the compiler needs
    # its gigabytes of the host first
    rows = np.asarray(sequences, np.int32)
    n_batches = max(1, rows.shape[0] // batch)
    grad = _grad_fn(tuple(sorted(m.items())))
    bits = None if quantize is None else jnp.int32(quantize)
    planted = jnp.int32(0 if fault is None else FAULTS.index(fault) + 1)
    rate = np.float32(m["bias_update_rate"])
    trace = []
    with ThreadPoolExecutor(max_workers=4) as pool:
        for i in range(steps):
            b0 = (i % n_batches) * batch
            step_rows = rows[b0:b0 + batch]
            if fault == "half_batch":
                step_rows = step_rows.copy()
                if batch > 1:
                    step_rows[batch // 2:] = 0
                else:
                    step_rows[:, step_rows.shape[1] // 2:] = 0
            grads, out = jax.device_get(grad(
                params, jnp.asarray(step_rows), planted, bits))
            if on_step is not None:
                on_step(i, params, grads)
            if mom is None:
                mom = {k: np.zeros_like(v) for k, v in params.items()}
                var = {k: np.zeros_like(v) for k, v in params.items()}
            l_main, load, pairs, norms = out
            trace.append((l_main, pairs, norms))
            list(pool.map(
                lambda k: _adam(params[k], mom[k], var[k], grads[k], i + 1,
                                m["learning_rate"]),
                [k for k in params if not k.endswith("router_b")]))
            # b takes no gradient; the balancing rule moves it towards the
            # mean load
            params["moe/router_b"] += rate * np.sign(
                load.mean(axis=-1, keepdims=True) - load)
            del grads
    return {
        "l_main": np.array([t[0] for t in trace], np.float64),
        "pairs": np.array([t[1] for t in trace], np.float64),
        "grad_norm": np.array([t[2] for t in trace], np.float64),
        "final": params, "init": jax.device_get(init_params(m, seed)),
    }


def next_item_logits(params: dict, history, m: dict):
    """Serving's forward: the last position's logits over the vocabulary
    slice for one history ``[T]`` (no padding)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        h, _, _ = trunk(params, jnp.asarray(history, jnp.int32), m)
        last = _norm(h[-1], params["lnf_g"], m["rms_norm_eps"])
        return np.asarray(_dot(last, params["head"].T, None))
