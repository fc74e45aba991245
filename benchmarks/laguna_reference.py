"""Plain reference of the ``laguna`` training step (Laguna-S-2.1's block):
forward, loss, gradients and Adam in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. Imports nothing of ``pio_tpu``;
the rounding, the SwiGLU, the norm and the host's Adam are ``seq_reference``'s
own, unchanged.

Written from the published ``config.json`` (``m`` is the model dict
:func:`model` makes of the configuration's file; ``x`` is the normed residual
stream of one row, ``[T, D]``; every norm is RMSNorm, no bias anywhere):

- Layer ``i`` is of kind ``layer_types[i]``: ``full`` or ``window``, and has a
  dense SwiGLU (``mlp_only_layers``) or an expert layer behind its attention.
- Attention of a layer of kind ``K``: ``q = x W_q`` ``[T, H_K, d]``, ``k = x
  W_k``, ``v = x W_v`` ``[T, H_kv, d]``; RoPE on ``q`` and ``k`` (rotate-half
  pairing, ``assumed``); query head ``j`` scores against KV head ``j // (H_K /
  H_kv)``, ``q k^T / sqrt(d)``; softmax over the visible keys: ``s <= t`` on a
  full layer, ``0 <= t - s < sliding_window`` on a window layer; ``o_j = P_j
  v``; the per-head gate ``g = sigmoid(x W_g)`` ``[T, H_K]`` scales ``o_j``
  (``assumed``: the head-wise form of gated attention); ``concat(o) W_o``. One
  query head at a time, a dense mask, the score rows of ``Q_CHUNK`` queries
  against all keys standing at once.
- RoPE, window layer: ``rope_type`` default, theta 10,000, the whole head.
  Full layer: the first ``partial_rotary_factor * d`` dims, YaRN
  (:func:`yarn_inv_freq`), ``cos`` and ``sin`` times ``attention_factor``.
- Expert layer: ``p = softmax(x W_r)`` over all the router's outputs; the
  ``num_experts_per_tok`` largest; ``w = scale * p_top / sum(p_top)``; ``y = sum
  over e selected and held of w_e E_e(x) + E_shared(x)``, ``E(x) = W_down(
  silu(W_gate x) * W_up x)``. Dense over tokens: every held expert runs on
  every token and is weighted by ``w_e`` or 0. What absent experts (and absent
  heads) would add is left out.
- Loss: mean cross-entropy of the next event over the vocabulary slice.
- Adam as ``optax.adam``: ``m, v`` bias-corrected, ``eps`` 1e-8 outside the root.

Arranged only so that it fits one chip (Adam's moments stay on the host): rows
one at a time, ``jax.checkpoint`` a row, a layer, a head, a chunk of ``Q_CHUNK``
queries, an expert and a 2,048-token slice of the head. None of that changes
a number.

``quantize = k`` rounds both operands of every matmul but the router's and
the gate's to ``k`` mantissa bits (the program keeps those two in float32): 7
is the configuration's own bfloat16, the witness; 3 the control. ``fault``
plants one wrong equation (``FAULTS``).
"""

from __future__ import annotations

import functools
import math
import zlib

import numpy as np

from seq_reference import HEAD_CHUNK, _adam, _dot, _norm, _swiglu

FAULTS = ("window_ignored", "window_256", "gate_left_out", "yarn_left_out",
          "whole_head_rotated", "kv_head_mod", "topk_not_normalised",
          "scale_one", "expert_dropped", "half_batch")
GROUPS = ("embedding", "head", "attn_window", "attn_full", "gate", "router",
          "routed_experts", "shared_expert", "dense_mlp", "norms")
Q_CHUNK = 4096
#: the per-step numbers a training call's trace and this reference share
TRACE_KEYS = ("l_main", "pairs", "grad_norm")
LOSSES = ("main",)


# ------------------------------------------------------------------- model
def model(config: dict) -> dict:
    """The flat dict the layers below read, from the configuration's file:
    the published keys, the layers here (the first ``num_hidden_layers`` of
    the published lists), the heads, experts and vocabulary rows held, the
    ``init`` rule's numbers and the learning rate."""
    n = config["num_hidden_layers"]
    kinds = tuple({"full_attention": "full", "sliding_attention": "window"}[k]
                  for k in config["layer_types"][:n])
    dense = tuple(t == "dense" for t in config["mlp_layer_types"][:n])
    n_dense = sum(dense)
    if dense != (True,) * n_dense + (False,) * (n - n_dense):
        raise ValueError("the dense layers lead")
    period = next(p for p in range(1, n + 1)
                  if all(kinds[i] == kinds[i % p] for i in range(n)))
    heads = {k: {config["num_attention_heads_per_layer"][i]
                 for i in range(n) if kinds[i] == k} for k in set(kinds)}
    if any(len(v) != 1 for v in heads.values()):
        raise ValueError("a kind of layer has one count of query heads")
    full = config["rope_parameters"]["full_attention"]
    window = config["rope_parameters"]["sliding_attention"]
    d = config["head_dim"]
    if (full["rope_type"], window["rope_type"]) != ("yarn", "default") or (
            window["partial_rotary_factor"] != 1
            or config["moe_router_logit_softcapping"]
            or config["moe_apply_router_weight_on_input"]
            or not config["norm_topk_prob"] or config["gating"] != "per-head"):
        raise ValueError("a key this reference has no equation for")
    return {
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "intermediate_size": config["intermediate_size"],
        "num_hidden_layers": n, "dense_layers": n_dense,
        "layer_pattern": kinds[:period],
        "head_dim": d, "kv_heads": config["num_key_value_heads"],
        "heads_full": heads.get("full", {0}).pop(),
        "heads_window": heads.get("window", {0}).pop(),
        "sliding_window": config["sliding_window"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta_window": float(window["rope_theta"]),
        "rope_theta_full": float(full["rope_theta"]),
        "rotary_dim_full": int(d * full["partial_rotary_factor"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original_len": full["original_max_position_embeddings"],
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": full["attention_factor"],
        "router_width": config["deployment"]["router_width"],
        "experts_first": config["deployment"]["experts_first"],
        "experts_held": config["num_experts"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "shared_expert_intermediate_size":
            config["shared_expert_intermediate_size"],
        "shared_experts": config["shared_expert_intermediate_size"]
        // config["moe_intermediate_size"],
        "moe_routed_scaling_factor": config["moe_routed_scaling_factor"],
        "init_std": config["init"]["init_std"],
        "embed_init_std": config["init"]["embed_init_std"],
        "learning_rate": config["algorithm_params"]["learning_rate"],
    }


def kind_of(m: dict, layer: int) -> str:
    return m["layer_pattern"][layer % len(m["layer_pattern"])]


def heads_of(m: dict, kind: str) -> int:
    return m["heads_" + kind]


def expert_layer_kinds(m: dict) -> tuple:
    return tuple(kind_of(m, i)
                 for i in range(m["dense_layers"], m["num_hidden_layers"]))


# ------------------------------------------------------------------ shapes
def _attention_shapes(L, m, kind):
    D, H, d, Hkv = m["hidden_size"], heads_of(m, kind), m["head_dim"], m["kv_heads"]
    return {"attn_norm": (L, D), "q_proj": (L, D, H * d),
            "k_proj": (L, D, Hkv * d), "v_proj": (L, D, Hkv * d),
            "g_proj": (L, D, H), "o_proj": (L, H * d, D), "ffn_norm": (L, D)}


def shapes(m: dict) -> dict:
    """``{"stack/name": shape}``: ``dense/*``, then the expert layers stacked
    by kind, ``window/*`` and ``full/*``, in the order they occur."""
    D, V, F = m["hidden_size"], m["vocab_size"], m["intermediate_size"]
    Fe, Fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    out = {"emb": (V, D), "head": (V, D), "lnf_g": (D,)}
    Ld = m["dense_layers"]
    if Ld:
        dense = {**_attention_shapes(Ld, m, kind_of(m, 0)), "w_gate": (Ld, D, F),
                 "w_up": (Ld, D, F), "w_down": (Ld, F, D)}
        out.update({"dense/" + k: v for k, v in dense.items()})
    kinds = expert_layer_kinds(m)
    for kind in ("full", "window"):
        L = kinds.count(kind)
        if L:
            held = m["experts_held"]
            layer = {**_attention_shapes(L, m, kind),
                     "router_w": (L, D, m["router_width"]),
                     "e_gate": (L, held, D, Fe), "e_up": (L, held, D, Fe),
                     "e_down": (L, held, Fe, D), "s_gate": (L, D, Fs),
                     "s_up": (L, D, Fs), "s_down": (L, Fs, D)}
            out.update({f"{kind}/{k}": v for k, v in layer.items()})
    return out


def init_params(m: dict, seed: int) -> dict:
    """The configuration's rule: a norm's gain is 1; every other parameter is
    ``normal(fold_in(PRNGKey(seed), crc32(path) & 0x7fffffff), shape, float32)
    * std``, std ``init_std``, or ``embed_init_std`` for ``emb``."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, shape in shapes(m).items():
        name = path.rpartition("/")[2]
        if name.endswith("norm") or name == "lnf_g":
            out[path] = jnp.ones(shape, jnp.float32)
            continue
        std = m["embed_init_std" if name == "emb" else "init_std"]
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(path.encode()) & 0x7FFFFFFF)
        out[path] = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return out


def group_of(path: str, m: dict) -> str:
    """Which of ``GROUPS`` a parameter is compared under: the projections of
    a layer under its kind's attention, the gate's map alone, every norm's
    gain together."""
    stack, _, name = path.rpartition("/")
    if name.endswith("norm") or name == "lnf_g":
        return "norms"
    if path == "emb":
        return "embedding"
    if path == "head":
        return "head"
    if name == "g_proj":
        return "gate"
    if name.endswith("_proj"):
        return "attn_" + (kind_of(m, 0) if stack == "dense" else stack)
    if name.startswith("router"):
        return "router"
    return {"e_": "routed_experts", "s_": "shared_expert",
            "w_": "dense_mlp"}[name[:2]]


# ------------------------------------------------------------------- layers
def _is(fault, name: str):
    """Whether the planted fault is ``name``: a Python bool for a name (or
    ``None``); a traced bool where ``fault`` is a number, 1 + its place in
    ``FAULTS`` and 0 for none, so one compiled step serves every fault."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


def yarn_inv_freq(theta, rotary_dim, factor, original_len, beta_fast, beta_slow):
    """``[rotary_dim / 2]`` float32: ``f_j = theta ** (-2j / r)``; ``dim(n) =
    r ln(original_len / (2 pi n)) / (2 ln theta)``; ``low = max(floor(dim(
    beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), r - 1)``; ``ramp_j =
    clip((j - low) / (high - low), 0, 1)``; ``f_j (1 - ramp_j) + f_j / factor *
    ramp_j``."""
    r = rotary_dim
    j = np.arange(r // 2, dtype=np.float64)
    f = float(theta) ** (-2.0 * j / r)
    dim = lambda n: r * math.log(original_len / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(theta))
    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), r - 1)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    return (f * (1 - ramp) + f / factor * ramp).astype(np.float32)


def _rotate(x, inv_freq, factor=1.0):
    """``x [T, h, d]``: the first ``2 len(inv_freq)`` dims of every head
    rotated at positions 0..T-1, dim ``i`` paired with ``i + len(inv_freq)``;
    the other dims pass through."""
    import jax.numpy as jnp

    half = len(inv_freq)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * jnp.float32(factor))[:, None, :]
    sin = (jnp.sin(ang) * jnp.float32(factor))[:, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def _plain_inv_freq(theta, rotary_dim):
    import jax.numpy as jnp

    half = rotary_dim // 2
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def _rope(x, m, kind, fault):
    import jax.numpy as jnp

    d = m["head_dim"]
    if kind == "window":
        return _rotate(x, _plain_inv_freq(m["rope_theta_window"], d))
    theta, r = m["rope_theta_full"], m["rotary_dim_full"]
    yarn = (m["yarn_factor"], m["yarn_original_len"], m["yarn_beta_fast"],
            m["yarn_beta_slow"])
    out = _rotate(x, yarn_inv_freq(theta, r, *yarn), m["yarn_attention_factor"])
    out = jnp.where(_is(fault, "yarn_left_out"),
                    _rotate(x, _plain_inv_freq(theta, r)), out)
    return jnp.where(
        _is(fault, "whole_head_rotated"),
        _rotate(x, yarn_inv_freq(theta, d, *yarn), m["yarn_attention_factor"]),
        out)


def _attention(w, x, m, kind, q, fault):
    """``x [T, D]`` (the residual stream) -> attention's output ``[T, D]``
    before the residual."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, Hkv, d = heads_of(m, kind), m["kv_heads"], m["head_dim"]
    xn = _norm(x, w["attn_norm"], m["rms_norm_eps"])
    qh = _rope(_dot(xn, w["q_proj"], q).reshape(T, H, d), m, kind, fault)
    kh = _rope(_dot(xn, w["k_proj"], q).reshape(T, Hkv, d), m, kind, fault)
    vh = _dot(xn, w["v_proj"], q).reshape(T, Hkv, d)
    gate = jax.nn.sigmoid(jnp.dot(xn, w["g_proj"],
                                  precision=jax.lax.Precision.HIGHEST))
    gate = jnp.where(_is(fault, "gate_left_out"), 1.0, gate)
    if kind == "window":
        reach = jnp.where(_is(fault, "window_ignored"), T + 1, jnp.where(
            _is(fault, "window_256"), 256, m["sliding_window"]))
    else:
        reach = T + 1
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
            behind = t_pos[:, None] - s_pos[None, :]
            s = _dot(qc, kj.T, q) / np.sqrt(d)
            p = jax.nn.softmax(
                jnp.where((behind >= 0) & (behind < reach), s, -jnp.inf), axis=-1)
            return _dot(p, vj, q)

        return jax.lax.map(rows, (qj.reshape(-1, chunk, d),
                                  s_pos.reshape(-1, chunk))).reshape(T, d)

    per_head = jax.lax.map(jax.checkpoint(head),
                           (qh.transpose(1, 0, 2), jnp.arange(H)))
    out = per_head.transpose(1, 0, 2) * gate[:, :, None]
    return _dot(out.reshape(T, H * d), w["o_proj"], q)


def _route(w, xn, m, fault):
    """``(idx [T, k], weight [T, k])`` of the normalised input."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.dot(xn, w["router_w"],
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, idx = jax.lax.top_k(p, m["num_experts_per_tok"])
    picked = picked / jnp.where(_is(fault, "topk_not_normalised"), 1.0,
                                picked.sum(axis=-1, keepdims=True))
    scale = jnp.where(_is(fault, "scale_one"), 1.0,
                      m["moe_routed_scaling_factor"])
    return idx, scale * picked


def _moe(w, xn, m, q, fault):
    """``(y [T, D], load [E], pairs)``: held experts dense over tokens."""
    import jax
    import jax.numpy as jnp

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
        wg, wu, wd, we = args
        return we[:, None] * _swiglu(xn, wg, wu, wd, q)

    y, _ = jax.lax.scan(
        lambda acc, args: (acc + expert(args), None), jnp.zeros_like(xn),
        (w["e_gate"], w["e_up"], w["e_down"], weight.T))
    return y + _swiglu(xn, w["s_gate"], w["s_up"], w["s_down"], q), load, pairs


def _layer(w, x, m, kind, dense, q, fault):
    """One layer -> ``(x, load [E], pairs)`` (zeros behind a dense MLP)."""
    import jax.numpy as jnp

    x = x + _attention(w, x, m, kind, q, fault)
    xn = _norm(x, w["ffn_norm"], m["rms_norm_eps"])
    if dense:
        y = _swiglu(xn, w["w_gate"], w["w_up"], w["w_down"], q)
        return x + y, jnp.zeros((m["router_width"],)), jnp.float32(0.0)
    y, load, pairs = _moe(w, xn, m, q, fault)
    return x + y, load, pairs


def _stack(params, stack):
    return {k.partition("/")[2]: v for k, v in params.items()
            if k.startswith(stack + "/")}


def trunk(params, ids, m, q=None, fault=None):
    """One row ``ids [T]`` -> ``(h [T, D] before the final norm, load [Le, E],
    pairs [Le])``, layer by layer in the model's order."""
    import jax
    import jax.numpy as jnp

    x = params["emb"][ids]
    taken = {"dense": 0, "full": 0, "window": 0}
    loads, pairs = [], []
    for i in range(m["num_hidden_layers"]):
        dense, kind = i < m["dense_layers"], kind_of(m, i)
        stack = "dense" if dense else kind
        w = {k: v[taken[stack]] for k, v in _stack(params, stack).items()}
        taken[stack] += 1
        x, load, n = jax.checkpoint(
            lambda w, x, kind=kind, dense=dense: _layer(
                w, x, m, kind, dense, q, fault))(w, x)
        if not dense:
            loads.append(load)
            pairs.append(n)
    return x, jnp.stack(loads), jnp.stack(pairs)


def _ce_sum(h, norm_g, head, targets, mask, m, q):
    """Sum over the row of ``mask * (logsumexp(logits) - logit[target])``."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    chunk = HEAD_CHUNK if T % HEAD_CHUNK == 0 else T

    @jax.checkpoint
    def part(args):
        hc, tc, mc = args
        logits = _dot(_norm(hc, norm_g, m["rms_norm_eps"]), head.T, q)
        z = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
        return ((z - picked) * mc).sum()

    return jax.lax.map(part, (h.reshape(-1, chunk, h.shape[1]),
                              targets.reshape(-1, chunk),
                              mask.reshape(-1, chunk))).sum()


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


def grad_norms(grads: dict, m: dict):
    """``[len(GROUPS)]``: the Frobenius norm of each group's gradient."""
    import jax.numpy as jnp

    total = dict.fromkeys(GROUPS, 0.0)
    for path, g in grads.items():
        total[group_of(path, m)] = total[group_of(path, m)] + jnp.sum(g * g)
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
        return grads, aux + (grad_norms(grads, m),)

    return grad


def _hashable(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def train(m: dict, sequences, *, seed: int, steps: int, batch: int,
          quantize=None, fault=None, on_step=None) -> dict:
    """``steps`` Adam steps from the rule's weights; step ``i`` takes rows
    ``[(i mod n/batch) * batch, + batch)``. The gradients come from the
    device, Adam runs on the host. Returns numpy: ``l_main`` ``[steps]``,
    ``pairs`` ``[steps, expert layers]``, ``grad_norm`` ``[steps, len(GROUPS)]``,
    ``init`` and ``final`` ``{path: array}``. ``on_step(i, params, grads)``
    sees each step first. ``half_batch`` pads out the second half of every
    step's events: of its rows, or of its one row's positions."""
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
    grad = _grad_fn(_hashable(m))
    bits = None if quantize is None else jnp.int32(quantize)
    planted = jnp.int32(0 if fault is None else FAULTS.index(fault) + 1)
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
            l_main, _load, pairs, norms = out
            trace.append((l_main, pairs, norms))
            list(pool.map(
                lambda k: _adam(params[k], mom[k], var[k], grads[k], i + 1,
                                m["learning_rate"]), list(params)))
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
