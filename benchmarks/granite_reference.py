"""Plain reference of the ``granitemoehybrid`` training step (IBM Granite
4.0-H Micro's block: a layer is a mixer *and* a dense MLP, no experts, a tied
table, four multipliers): forward, loss, gradients and Adam in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``. Imports
nothing of ``pio_tpu``; the rounding, the norm, the SwiGLU and the host's Adam
are ``seq_reference``'s own, the rotation of the planted RoPE fault and the
stacks' slicing ``laguna_reference``'s, the time-step recurrence
``nemotron_reference``'s, unchanged.

Written from the published ``config.json`` (``m`` is the model dict
:func:`model` makes of the configuration's file; ``x`` is the residual stream
of one row, ``[T, D]``; every norm is RMSNorm; no bias but the convolution's).
The stream starts as ``embedding_multiplier * E[ids]``. Layer ``l`` is ``x <- x
+ residual_multiplier * mixer_l(norm(x))``, then ``x <- x +
residual_multiplier * mlp_l(norm'(x))``: here two entries of ``mixer_pattern``,
each with its own norm, the first the kind ``layer_types[l]`` names:

- ``mamba``, Mamba-2 (``H`` heads of ``P`` channels, ``G`` groups of state
  ``N``, ``K`` taps): ``[z | xBC | dt] = xn W_in``; ``xBC <- silu(sum_j w_j
  xBC_{t-K+1+j} + b)``, zeros before the first event; ``xBC`` split into ``x_t
  [H, P]``, ``B_t, C_t [G, N]``; ``dt_t = softplus(dt_t + dt_bias)`` (the
  published ``time_step_limit`` (0, inf) clamps nothing); ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` (``S_{-1} = 0``), ``y_t = S_t
  C_t + D x_t``, **the recurrence itself, one time step after another**, not the
  chunked form the program computes. Then the gate first, ``u = y silu(z)``,
  RMSNorm over each of the ``G`` groups of ``H P / G`` channels (one group here:
  all 4,096 channels of all 64 heads), times a gain; ``u W_out``.
- ``attention``: ``q = xn W_q`` ``[T, H_q, d]``, ``k, v`` ``[T, H_kv, d]``; **no
  position encoding** (``position_embedding_type`` "nope"); query head ``j``
  scores against KV head ``j // (H_q / H_kv)``, ``q k^T * attention_multiplier``
  (not ``1 / sqrt(d)``), causal softmax; ``concat(o) W_o``. One query head at a
  time, one masked softmax over all keys, in blocks of query rows.
- ``mlp`` (the published ``shared_mlp``): ``W_down(silu(W_gate xn) * W_up xn)``,
  the published ``input_linear`` being ``W_gate | W_up`` side by side.
- Loss: logits ``norm(x) E^T / logits_scaling`` over the vocabulary slice, **the
  embedding table itself** (``tie_word_embeddings``); mean cross-entropy of the
  next event. The table's gradient is the sum of its two uses.
- Adam as ``optax.adam``.

Arranged only so that it fits one chip: rows one at a time, ``jax.checkpoint``
a row, a layer, a block of 256 time steps, a query head and block of rows, and
a 2,048-token slice of the logits. None of that changes a number.

``quantize = k`` rounds both operands of every matmul to ``k`` mantissa bits,
and what the recurrence multiplies (``dt x``, ``B``, ``C``): 7 is the
configuration's own bfloat16, the witness; 3 the control. ``fault`` plants one
wrong equation (``FAULTS``). There are no experts: ``pairs`` is ``[steps, 0]``.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

from laguna_reference import Q_CHUNK, _plain_inv_freq, _rotate, _stack
from nemotron_reference import recurrence
from seq_reference import HEAD_CHUNK, _adam, _dot, _norm, _rounded, _swiglu

FAULTS = ("state_not_carried", "residual_one", "scale_rsqrt_d",
          "logits_not_divided", "embedding_not_multiplied",
          "head_not_in_table_gradient", "norm_per_8_heads",
          "conv_taps_reversed", "rope_applied", "kv_head_mod",
          "gate_up_exchanged")
#: no ``head`` (the table is tied), no router, no experts
GROUPS = ("embedding", "ssm_proj", "ssm_scan", "attn", "dense_mlp", "norms")
KINDS = {"mamba": "mamba", "attention": "attn"}
#: the per-step numbers a training call's trace and this reference share
TRACE_KEYS = ("l_main", "pairs", "grad_norm")
LOSSES = ("main",)


# ------------------------------------------------------------------- model
def model(config: dict) -> dict:
    """The flat dict the layers below read, from the configuration's file:
    the published keys, the layers here (``layer_types``, each followed by its
    MLP), the vocabulary rows held, the ``init`` rule's numbers and the
    learning rate."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types names every layer here")
    if (config["hidden_act"], config["normalization_function"],
            config["position_embedding_type"]) != ("silu", "rmsnorm", "nope") or (
            config["num_local_experts"] or config["num_experts_per_tok"]
            or config["attention_bias"] or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"]
            or not config["tie_word_embeddings"]
            or config["hidden_size"] % config["num_attention_heads"]):
        raise ValueError("a key this reference has no equation for")
    init = config["init"]
    return {
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "num_hidden_layers": config["num_hidden_layers"],
        "mixer_pattern": tuple(k for kind in kinds for k in (KINDS[kind], "mlp")),
        "n_mixers": 2 * len(kinds),
        "mamba_n_heads": config["mamba_n_heads"],
        "mamba_d_head": config["mamba_d_head"],
        "mamba_n_groups": config["mamba_n_groups"],
        "mamba_d_state": config["mamba_d_state"],
        "mamba_d_conv": config["mamba_d_conv"],
        "mamba_chunk_size": config["mamba_chunk_size"],
        "time_step_min": init["time_step_min"],
        "time_step_max": init["time_step_max"],
        "time_step_floor": init["time_step_floor"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": config["rms_norm_eps"],
        "shared_intermediate_size": config["shared_intermediate_size"],
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logit_multiplier": 1.0 / float(config["logits_scaling"]),
        "init_std": init["init_std"],
        "learning_rate": config["algorithm_params"]["learning_rate"],
    }


def ssm_widths(m: dict) -> tuple:
    """``(inner, convolved)``: the heads' channels, and ``x | B | C``. The
    inner width is heads x head width; ``mamba_expand`` is not read."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    return inner, inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


# ------------------------------------------------------------------ shapes
def shapes(m: dict) -> dict:
    """``{"stack/name": shape}``: the mixers stacked by kind, ``mamba/*``,
    ``attn/*`` and ``mlp/*``, in the order they occur; one table."""
    D, V, H = m["hidden_size"], m["vocab_size"], m["mamba_n_heads"]
    inner, conv = ssm_widths(m)
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    F = m["shared_intermediate_size"]
    out = {"emb": (V, D), "lnf_g": (D,)}
    of_kind = {
        "mamba": lambda L: {
            "norm": (L, D), "in_proj": (L, D, inner + conv + H),
            "conv_w": (L, m["mamba_d_conv"], conv), "conv_b": (L, conv),
            "a_log": (L, H), "d_skip": (L, H), "dt_bias": (L, H),
            "gate_g": (L, inner), "out_proj": (L, inner, D)},
        "attn": lambda L: {
            "attn_norm": (L, D), "q_proj": (L, D, Hq * d),
            "k_proj": (L, D, Hkv * d), "v_proj": (L, D, Hkv * d),
            "o_proj": (L, Hq * d, D)},
        "mlp": lambda L: {
            "norm": (L, D), "w_gate": (L, D, F), "w_up": (L, D, F),
            "w_down": (L, F, D)},
    }
    for kind in ("mamba", "attn", "mlp"):
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
    the rest, the table's rows too, normal times ``init_std``."""
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
            bound = m["mamba_d_conv"] ** -0.5
            out[path] = jax.random.uniform(key, shape, f32, -bound, bound)
        else:
            out[path] = jax.random.normal(key, shape, f32) * f32(m["init_std"])
    return out


def group_of(path: str, m: dict = None) -> str:
    """Which of ``GROUPS`` a parameter is compared under: a mamba mixer's two
    projections apart from what its recurrence reads, the attention mixer's
    four projections together, the MLP mixers' three matrices, every norm's
    gain together, the one table."""
    stack, _, name = path.rpartition("/")
    if name.endswith("norm") or name == "lnf_g":
        return "norms"
    if path == "emb":
        return "embedding"
    if stack == "mamba":
        return "ssm_proj" if name.endswith("_proj") else "ssm_scan"
    return {"attn": "attn", "mlp": "dense_mlp"}[stack]


# ------------------------------------------------------------------- layers
def _is(fault, name: str):
    """Whether the planted fault is ``name``: a Python bool for a name (or
    ``None``); a traced bool where ``fault`` is a number, 1 + its place in
    ``FAULTS`` and 0 for none, so one compiled step serves every fault."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


def _mamba(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> the mixer's output ``[T, D]``."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, P, G, N = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"],
                  m["mamba_d_state"])
    K, eps = m["mamba_d_conv"], m["rms_norm_eps"]
    inner, conv = ssm_widths(m)
    zxd = _dot(_norm(x, w["norm"], eps), w["in_proj"], q)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), xbc.dtype), xbc])
    taps = jnp.where(_is(fault, "conv_taps_reversed"), w["conv_w"][::-1],
                     w["conv_w"])
    xbc = jax.nn.silu(w["conv_b"] + sum(taps[j] * padded[j:j + T]
                                        for j in range(K)))
    xs = xbc[:, :inner].reshape(T, H, P)
    b = xbc[:, inner:inner + G * N].reshape(T, G, N)
    c = xbc[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])
    group_of_head = jnp.arange(H) // (H // G)
    reset = jnp.where(_is(fault, "state_not_carried"), m["mamba_chunk_size"],
                      T + 1)
    dtx = dt[:, :, None] * xs
    if q is not None:  # what the recurrence multiplies, in the low precision
        dtx, b, c = _rounded(dtx, q), _rounded(b, q), _rounded(c, q)
    y = recurrence(dtx, dt, a, b, c, group_of_head, reset)
    u = (y + w["d_skip"][:, None] * xs).reshape(T, inner) * jax.nn.silu(z)

    def normed(width):  # RMSNorm over runs of ``width`` channels
        runs = u.reshape(T, inner // width, width)
        return (runs / jnp.sqrt((runs * runs).mean(axis=-1, keepdims=True)
                                + eps)).reshape(T, inner)

    per_8_heads = min(inner // G, 8 * P)
    u = jnp.where(_is(fault, "norm_per_8_heads"), normed(per_8_heads),
                  normed(inner // G)) * w["gate_g"]
    return _dot(u, w["out_proj"], q)


def _attention(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> attention's output ``[T, D]``
    before the residual: causal, grouped queries, no position encoding,
    scores times ``attention_multiplier``."""
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
    scale = jnp.where(_is(fault, "scale_rsqrt_d"), d ** -0.5,
                      m["attention_multiplier"])
    block = Q_CHUNK if T % Q_CHUNK == 0 else T
    s_pos = jnp.arange(T)

    def head(args):
        qj, j = args  # [T, d] of query head j
        pair = jnp.where(_is(fault, "kv_head_mod"), j % Hkv, j // (H // Hkv))
        kj, vj = kh[pair], vh[pair]

        @jax.checkpoint
        def rows(args):
            qc, t_pos = args  # a block of queries against all keys
            s = _dot(qc, kj.T, q) * scale
            p = jax.nn.softmax(
                jnp.where(t_pos[:, None] >= s_pos[None, :], s, -jnp.inf), axis=-1)
            return _dot(p, vj, q)

        return jax.lax.map(rows, (qj.reshape(-1, block, d),
                                  s_pos.reshape(-1, block))).reshape(T, d)

    per_head = jax.lax.map(jax.checkpoint(head),
                           (qh.transpose(1, 0, 2), jnp.arange(H)))
    return _dot(per_head.transpose(1, 0, 2).reshape(T, H * d), w["o_proj"], q)


def _mlp(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> the dense SwiGLU's output."""
    import jax.numpy as jnp

    xn = _norm(x, w["norm"], m["rms_norm_eps"])
    exchanged = _is(fault, "gate_up_exchanged")
    return _swiglu(xn, jnp.where(exchanged, w["w_up"], w["w_gate"]),
                   jnp.where(exchanged, w["w_gate"], w["w_up"]), w["w_down"], q)


MIXERS = {"mamba": _mamba, "attn": _attention, "mlp": _mlp}


def trunk(params, ids, m, q=None, fault=None):
    """One row ``ids [T]`` -> ``h [T, D]`` before the final norm, mixer by
    mixer in the model's order."""
    import jax
    import jax.numpy as jnp

    x = params["emb"][ids] * jnp.where(
        _is(fault, "embedding_not_multiplied"), 1.0, m["embedding_multiplier"])
    scale = jnp.where(_is(fault, "residual_one"), 1.0, m["residual_multiplier"])
    taken = dict.fromkeys(MIXERS, 0)
    for kind in m["mixer_pattern"]:
        w = {k: v[taken[kind]] for k, v in _stack(params, kind).items()}
        taken[kind] += 1
        x = x + scale * jax.checkpoint(
            lambda w, x, mixer=MIXERS[kind]: mixer(w, x, m, q, fault))(w, x)
    return x


def _ce_sum(h, norm_g, table, targets, mask, m, q, fault):
    """Sum over the row of ``mask * (logsumexp(logits) - logit[target])``,
    the logits read from the embedding table and divided by
    ``logits_scaling``."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    chunk = HEAD_CHUNK if T % HEAD_CHUNK == 0 else T
    table = jnp.where(_is(fault, "head_not_in_table_gradient"),
                      jax.lax.stop_gradient(table), table)
    scale = jnp.where(_is(fault, "logits_not_divided"), 1.0,
                      m["logit_multiplier"])

    @jax.checkpoint
    def part(args):
        hc, tc, mc = args
        logits = _dot(_norm(hc, norm_g, m["rms_norm_eps"]), table.T, q) * scale
        z = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
        return ((z - picked) * mc).sum()

    return jax.lax.map(part, (h.reshape(-1, chunk, h.shape[1]),
                              targets.reshape(-1, chunk),
                              mask.reshape(-1, chunk))).sum()


def row_sums(params, ids, m, q=None, fault=None):
    """One row's summed cross-entropy; the target is the next event, 0 =
    pad, unsupervised."""
    import jax.numpy as jnp

    t1 = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    m1 = ((t1 > 0) & (ids > 0)).astype(jnp.float32)
    h = trunk(params, ids, m, q, fault)
    return _ce_sum(h, params["lnf_g"], params["emb"], t1, m1, m, q, fault)


def batch_loss(params, rows, m, q=None, fault=None):
    """``(loss, l_main)`` of a batch ``rows [B, T]``, one row at a time."""
    import jax
    import jax.numpy as jnp

    ce = jax.lax.map(
        jax.checkpoint(lambda ids: row_sums(params, ids, m, q, fault)), rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    l_main = ce.sum() / jnp.maximum(((t1 > 0) & (rows > 0)).sum(), 1)
    return l_main, l_main


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
    bits) -> (grads, (l_main, grad_norms))``; ``fault`` a number (:func:`_is`)
    and ``bits`` ``None`` or the traced number of mantissa bits, as
    ``seq_reference._grad_fn`` has them."""
    import jax

    m = dict(m_items)

    @jax.jit
    def grad(params, rows, fault, bits):
        with jax.default_matmul_precision("highest"):
            (_, l_main), grads = jax.value_and_grad(batch_loss, has_aux=True)(
                params, rows, m, bits, fault)
        return grads, (l_main, grad_norms(grads))

    return grad


def train(m: dict, sequences, *, seed: int, steps: int, batch: int,
          quantize=None, fault=None, on_step=None) -> dict:
    """``steps`` Adam steps from the rule's weights; step ``i`` takes rows
    ``[(i mod n/batch) * batch, + batch)``. The gradients come from the
    device; Adam runs on the host. Returns numpy: ``l_main`` ``[steps]``,
    ``pairs`` ``[steps, 0]`` (no expert layer), ``grad_norm`` ``[steps,
    len(GROUPS)]``, ``init`` and ``final`` ``{path: array}``. ``on_step(i,
    params, grads)`` sees each step first."""
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
    trace = []
    with ThreadPoolExecutor(max_workers=4) as pool:
        for i in range(steps):
            b0 = (i % n_batches) * batch
            grads, out = jax.device_get(grad(
                params, jnp.asarray(rows[b0:b0 + batch]), planted, bits))
            if on_step is not None:
                on_step(i, params, grads)
            if mom is None:
                mom = {k: np.zeros_like(v) for k, v in params.items()}
                var = {k: np.zeros_like(v) for k, v in params.items()}
            trace.append(out)
            list(pool.map(
                lambda k: _adam(params[k], mom[k], var[k], grads[k], i + 1,
                                m["learning_rate"]), list(params)))
            del grads
    return {
        "l_main": np.array([t[0] for t in trace], np.float64),
        "pairs": np.zeros((steps, 0), np.float64),
        "grad_norm": np.array([t[1] for t in trace], np.float64),
        "final": params, "init": jax.device_get(init_params(m, seed)),
    }


def next_item_logits(params: dict, history, m: dict):
    """Serving's forward: the last position's logits over the vocabulary
    slice for one history ``[T]`` (no padding)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        h = trunk(params, jnp.asarray(history, jnp.int32), m)
        last = _norm(h[-1], params["lnf_g"], m["rms_norm_eps"])
        return np.asarray(_dot(last, params["emb"].T, None)
                          * m["logit_multiplier"])
