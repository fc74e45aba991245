"""Plain reference of the ``glm4_moe_lite`` / DeepSeek-V3 training step:
forward, loss, gradients and Adam in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. Imports nothing of ``pio_tpu``.

Written from the published equations (``m`` is the model dict below; x is the
residual stream; every norm is RMSNorm, no bias anywhere):

- MLA: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> heads x [nope | rope];
  ``[c_kv | k_r] = x W_kva``; ``c_kv = norm(c_kv)``; ``[k_nope | v] = c_kv
  W_kvb``; RoPE (rotate-half pairing, ``assumed``) on ``q_rope`` and on
  ``k_r``, which all heads share; scores ``(q_nope.k_nope + q_rope.k_r) /
  sqrt(nope + rope)``, causal softmax, ``concat_heads(P v) W_o``. One head
  at a time, the whole ``[T, T]`` score matrix of that head standing.
- Expert layer: ``s = sigmoid(x W_r)``; the selected experts are the top-k
  of ``s + b``; ``g_e = scale * s_e / sum of the selected s``; ``y = sum over
  e selected and held of g_e E_e(x) + E_shared(x)``, ``E(x) = W_down(silu(W_gate
  x) * W_up x)``. Dense over tokens: every held expert runs on every token and
  is weighted by ``g_e`` or 0. What absent experts would add is left out.
  ``b`` takes no gradient; after each step ``b += rate * sign(mean load -
  load_e)`` over all the router's counts.
- MTP (depth 1, DeepSeek-V3 report 2.2): ``h' = W_eh [norm(h_t) ;
  norm(Emb(x_{t+1}))]`` (``h_t`` the residual stream before the final norm),
  one more expert layer, its own final norm, the shared head, cross-entropy on
  ``x_{t+2}``; loss ``L_main + w L_mtp``.
- Adam as ``optax.adam``: ``m, v`` bias-corrected, ``eps`` 1e-8 outside the root.

Arranged only so that it fits one chip (Adam's moments stay on the host): rows
one at a time (``lax.map``), the expert layers one at a time (``lax.scan``),
``jax.checkpoint`` a row, a layer, a head, an expert and a 2,048-token slice
of the head. None of that changes a number.

``quantize = k`` rounds both operands of every matmul to ``k`` mantissa bits
(float32's exponent kept, :func:`_rounded`): 7 is the configuration's own
bfloat16, the witness; 3 is fp8 e4m3 under an ideal scale, the control.
``fault`` plants one wrong equation (``FAULTS``; ``half_batch`` pads out the
second half of every step's rows instead).
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

FAULTS = ("bias_ignored", "topk_not_normalised", "scale_one", "rope_on_nope",
          "mtp_left_out", "expert_dropped", "half_batch")
HEAD_CHUNK = 2048
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
GROUPS = ("embedding", "head", "mla", "router", "routed_experts",
          "shared_expert", "dense_mlp", "mtp")


# ------------------------------------------------------------------ shapes
def _mla_shapes(L, m):
    D, H = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return {
        "attn_norm": (L, D), "q_a": (L, D, m["q_lora_rank"]),
        "q_norm": (L, m["q_lora_rank"]),
        "q_b": (L, m["q_lora_rank"], H * qk),
        "kv_a": (L, D, m["kv_lora_rank"] + m["qk_rope_head_dim"]),
        "kv_norm": (L, m["kv_lora_rank"]),
        "kv_b": (L, m["kv_lora_rank"],
                 H * (m["qk_nope_head_dim"] + m["v_head_dim"])),
        "o_proj": (L, H * m["v_head_dim"], D), "ffn_norm": (L, D),
    }


def _expert_shapes(L, m):
    D, F = m["hidden_size"], m["moe_intermediate_size"]
    E, held, Fs = m["router_width"], m["n_routed_experts"], F * m["n_shared_experts"]
    return {
        **_mla_shapes(L, m),
        "router_w": (L, D, E), "router_b": (L, E),
        "e_gate": (L, held, D, F), "e_up": (L, held, D, F),
        "e_down": (L, held, F, D),
        "s_gate": (L, D, Fs), "s_up": (L, D, Fs), "s_down": (L, Fs, D),
    }


def shapes(m: dict) -> dict:
    """``{"group/name": shape}`` of the model ``m`` describes."""
    D, V = m["hidden_size"], m["vocab_size"]
    Ld = m["first_k_dense_replace"]
    out = {"emb": (V, D), "head": (V, D), "lnf_g": (D,)}
    if Ld:
        F = m["intermediate_size"]
        dense = {**_mla_shapes(Ld, m), "w_gate": (Ld, D, F),
                 "w_up": (Ld, D, F), "w_down": (Ld, F, D)}
        out.update({"dense/" + k: v for k, v in dense.items()})
    out.update({"blocks/" + k: v for k, v in
                _expert_shapes(m["num_hidden_layers"] - Ld, m).items()})
    if m["num_nextn_predict_layers"]:
        mtp = {"eh_proj": (2 * D, D), "h_norm": (D,), "e_norm": (D,),
               "lnf_g": (D,), **_expert_shapes(1, m)}
        out.update({"mtp/" + k: v for k, v in mtp.items()})
    return out


def init_params(m: dict, seed: int) -> dict:
    """The configuration's rule: a norm's gain is 1; every other parameter
    is ``normal(fold_in(PRNGKey(seed), crc32(path) & 0x7fffffff), shape,
    float32) * std``, std ``init_std``, ``embed_init_std`` for ``emb`` or
    ``bias_init_std`` for ``router_b``."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, shape in shapes(m).items():
        name = path.rpartition("/")[2]
        if name.endswith("norm") or name == "lnf_g":
            out[path] = jnp.ones(shape, jnp.float32)
            continue
        std = m[{"router_b": "bias_init_std",
                 "emb": "embed_init_std"}.get(name, "init_std")]
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(path.encode()) & 0x7FFFFFFF)
        out[path] = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return out


def group_of(path: str) -> str:
    """Which of ``GROUPS`` a parameter is compared under."""
    group, _, name = path.rpartition("/")
    if group == "mtp":
        return "mtp"
    if path == "emb":
        return "embedding"
    if path == "head":
        return "head"
    if name.startswith("router"):
        return "router"
    if name.startswith("e_"):
        return "routed_experts"
    if name.startswith("s_"):
        return "shared_expert"
    if name.startswith("w_"):
        return "dense_mlp"
    return "mla"  # attention's matrices, the norms, the final norm


# ------------------------------------------------------------------- layers
def _is(fault, name: str):
    """Whether the planted fault is ``name``: a Python bool for a ``fault``
    given by name (or ``None``); a traced bool where ``fault`` is a number,
    1 + its place in ``FAULTS`` and 0 for none, as ``train`` passes it, so
    that one compiled step serves the reference and every fault."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


@functools.cache
def _rounding():
    """``(a, bits) -> a`` (float32) rounded to nearest-even at ``bits``
    mantissa bits, float32's exponent kept: ``lax.reduce_precision(a, 8,
    bits)`` with ``bits`` a traced number, so that one compiled program
    serves the witness (7: bfloat16) and the control (3: fp8 e4m3 as an ideal
    per-tensor scale leaves it; plain e4m3 flushes weights of std 0.02 to
    zero and tests the exponent's range, not the mantissa). Backward it
    rounds the cotangent alike, as ``reduce_precision`` does: the backward
    matmuls' operands are in the low precision too."""
    import jax
    import jax.numpy as jnp

    def nearest(a, bits):
        u = jax.lax.bitcast_convert_type(a, jnp.uint32)
        drop = (23 - bits).astype(jnp.uint32)  # low bits that go, at least 1
        one = jnp.uint32(1)
        u = u + ((one << (drop - one)) - one) + ((u >> drop) & one)
        return jax.lax.bitcast_convert_type((u >> drop) << drop, jnp.float32)

    rounded = jax.custom_vjp(nearest)
    rounded.defvjp(
        lambda a, bits: (nearest(a, bits), bits),
        lambda bits, ct: (nearest(ct, bits),
                          np.zeros(bits.shape, jax.dtypes.float0)))
    return rounded


def _rounded(a, bits):
    return _rounding()(a, bits)


def _dot(a, b, quantize):
    import jax
    import jax.numpy as jnp

    if quantize is not None:
        a = _rounded(a, quantize)
        b = _rounded(b, quantize)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """``x [T, ..., d]`` rotated at positions 0..T-1, dim i paired with
    i + d/2."""
    import jax.numpy as jnp

    T, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _swiglu(x, w_gate, w_up, w_down, q):
    import jax

    return _dot(jax.nn.silu(_dot(x, w_gate, q)) * _dot(x, w_up, q), w_down, q)


def _mla(w, x, m, q, fault):
    """``x [T, D]`` -> attention output ``[T, D]`` (before the residual)."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    xn = _norm(x, w["attn_norm"], eps)
    c_q = _norm(_dot(xn, w["q_a"], q), w["q_norm"], eps)
    qh = _dot(c_q, w["q_b"], q).reshape(T, H, dn + dr)
    kv_a = _dot(xn, w["kv_a"], q)
    c_kv = _norm(kv_a[:, :m["kv_lora_rank"]], w["kv_norm"], eps)
    k_r = kv_a[:, m["kv_lora_rank"]:]
    kv = _dot(c_kv, w["kv_b"], q).reshape(T, H, dn + dv)
    q_nope, q_rope = qh[..., :dn], qh[..., dn:]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    wrong = _is(fault, "rope_on_nope")
    q_nope, k_nope, q_rope, k_r = (
        jnp.where(wrong, _rope(q_nope, theta), q_nope),
        jnp.where(wrong, _rope(k_nope, theta), k_nope),
        jnp.where(wrong, q_rope, _rope(q_rope, theta)),
        jnp.where(wrong, k_r, _rope(k_r, theta)))
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(args):
        qn, qr, kn, vh = args  # [T, .] of one head
        s = (_dot(qn, kn.T, q) + _dot(qr, k_r.T, q)) / np.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _dot(p, vh, q)

    per_head = jax.lax.map(head, tuple(
        a.transpose(1, 0, 2) for a in (q_nope, q_rope, k_nope, v)))
    return _dot(per_head.transpose(1, 0, 2).reshape(T, H * dv), w["o_proj"], q)


def _route(w, xn, m, fault):
    """``(idx [T, k], gate [T, k])`` of the normalised input."""
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
    scale = jnp.where(_is(fault, "scale_one"), 1.0,
                      m["routed_scaling_factor"])
    return idx, scale * picked


def _moe(w, xn, m, q, fault):
    """``(y [T, D], load [E], pairs)``: held experts dense over tokens."""
    import jax
    import jax.numpy as jnp

    idx, gate = _route(w, xn, m, fault)
    E, held, first = m["router_width"], m["n_routed_experts"], m["experts_first"]
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


def _expert_layer(w, x, m, q, fault):
    x = x + _mla(w, x, m, q, fault)
    y, load, pairs = _moe(w, _norm(x, w["ffn_norm"], m["rms_norm_eps"]), m,
                          q, fault)
    return x + y, load, pairs


def _dense_layer(w, x, m, q, fault):
    x = x + _mla(w, x, m, q, fault)
    xn = _norm(x, w["ffn_norm"], m["rms_norm_eps"])
    return x + _swiglu(xn, w["w_gate"], w["w_up"], w["w_down"], q)


def _group(params, group):
    return {k.partition("/")[2]: v for k, v in params.items()
            if k.startswith(group + "/")}


def trunk(params, ids, m, q=None, fault=None):
    """One row ``ids [T]`` -> ``(h [T, D] before the final norm, load [Le, E],
    pairs [Le])``."""
    import jax
    import jax.numpy as jnp

    x = params["emb"][ids]
    dense, blocks = _group(params, "dense"), _group(params, "blocks")
    for layer in range(m["first_k_dense_replace"]):
        w = {k: v[layer] for k, v in dense.items()}
        x = jax.checkpoint(lambda w, x: _dense_layer(w, x, m, q, fault))(w, x)

    def layer(x, w):
        x, load, n = jax.checkpoint(
            lambda w, x: _expert_layer(w, x, m, q, fault))(w, x)
        return x, (load, n)

    x, (loads, pairs) = jax.lax.scan(layer, x, blocks)  # one layer at a time
    return x, loads, pairs


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
    """One row's ``(ce, ce_mtp, load [Lm, E], pairs [Lm])``; targets are the
    row shifted by one (main) and two (MTP), 0 = pad, unsupervised."""
    import jax
    import jax.numpy as jnp

    zero = jnp.zeros((1,), ids.dtype)
    t1 = jnp.concatenate([ids[1:], zero])
    t2 = jnp.concatenate([ids[2:], zero, zero])
    m1 = ((t1 > 0) & (ids > 0)).astype(jnp.float32)
    m2 = m1 * (t2 > 0)
    h, load, pairs = trunk(params, ids, m, q, fault)
    ce = _ce_sum(h, params["lnf_g"], params["head"], t1, m1, m, q)
    ce2 = jnp.float32(0.0)
    if m["num_nextn_predict_layers"]:
        mtp = _group(params, "mtp")
        eps = m["rms_norm_eps"]
        both = jnp.concatenate([_norm(h, mtp["h_norm"], eps),
                                _norm(params["emb"][t1], mtp["e_norm"], eps)],
                               axis=-1)
        w = {k: v[0] for k, v in mtp.items()
             if k not in ("eh_proj", "h_norm", "e_norm", "lnf_g")}
        h2, load2, pairs2 = jax.checkpoint(
            lambda w, x: _expert_layer(w, x, m, q, fault)
        )(w, _dot(both, mtp["eh_proj"], q))
        ce2 = _ce_sum(h2, mtp["lnf_g"], params["head"], t2, m2, m, q)
        load = jnp.concatenate([load, load2[None]])
        pairs = jnp.concatenate([pairs, pairs2[None]])
    return ce, ce2, load, pairs


def batch_loss(params, rows, m, q=None, fault=None):
    """``(loss, (l_main, l_mtp, load, pairs))`` of a batch ``rows [B, T]``,
    one row at a time."""
    import jax
    import jax.numpy as jnp

    ce, ce2, load, pairs = jax.lax.map(
        jax.checkpoint(lambda ids: row_sums(params, ids, m, q, fault)), rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    t2 = jnp.pad(rows[:, 2:], ((0, 0), (0, 2)))
    m1 = (t1 > 0) & (rows > 0)
    l_main = ce.sum() / jnp.maximum(m1.sum(), 1)
    l_mtp = ce2.sum() / jnp.maximum((m1 & (t2 > 0)).sum(), 1)
    loss = l_main
    if m["num_nextn_predict_layers"]:
        loss = loss + jnp.where(_is(fault, "mtp_left_out"), 0.0,
                                m["mtp_weight"]) * l_mtp
    return loss, (l_main, l_mtp, load.sum(axis=0), pairs.sum(axis=0))


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
    bits) -> (grads, (l_main, l_mtp, load, pairs, grad_norms))``. ``fault``
    is a number (:func:`_is`), so the faults share the reference's compiled
    program; ``bits`` is ``None`` (the plain reference) or the traced number
    of mantissa bits every matmul operand keeps, so the witness and the
    control share a second. Adam's moments never reach the device: beside 8 B
    more a parameter the chip's compiler has to squeeze the step (or refuses
    it)."""
    import jax

    m = dict(m_items)

    @jax.jit
    def grad(params, rows, fault, bits):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(batch_loss, has_aux=True)(
                params, rows, m, bits, fault)
        return grads, aux + (grad_norms(grads),)

    return grad


def _hashable(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def _adam(p, mom, var, g, t: int, lr: float) -> None:
    """One leaf's Adam step as ``optax.adam``'s (``m, v`` bias-corrected,
    ``eps`` outside the root), on the host in numpy float32 and in place;
    ``g`` is used up as the scratch array, so a 400 MB leaf costs no copy."""
    one = np.float32(1.0)
    b1, b2 = np.float32(ADAM_B1), np.float32(ADAM_B2)
    if not g.flags.writeable:
        g = g.copy()
    mom *= b1
    mom += (one - b1) * g
    np.multiply(g, g, out=g)
    g *= one - b2
    var *= b2
    var += g
    np.divide(var, one - b2 ** np.float32(t), out=g)  # v_hat
    np.sqrt(g, out=g)
    g += np.float32(ADAM_EPS)
    np.divide(mom, g, out=g)
    g *= np.float32(lr) / (one - b1 ** np.float32(t))  # lr * m_hat / (...)
    p -= g


def train(m: dict, sequences, *, seed: int, steps: int, batch: int,
          quantize=None, fault=None, on_step=None) -> dict:
    """``steps`` Adam steps from the rule's weights; step ``i`` takes rows
    ``[(i mod n/batch) * batch, + batch)``. The gradients come from the
    device, Adam and the selection-bias rule run on the host. Returns numpy:
    ``l_main``, ``l_mtp`` ``[steps]``, ``pairs`` ``[steps, expert layers
    (+1)]``, ``grad_norm`` ``[steps, len(GROUPS)]``, ``init`` and ``final``
    ``{path: array}``. ``on_step(i, params, grads)`` sees each step first."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    params = {k: np.array(v)  # writable copies
              for k, v in jax.device_get(init_params(m, seed)).items()}
    mom = var = None  # made after the first gradients: the compiler needs
    # its gigabytes of the host first
    rows = np.asarray(sequences, np.int32)
    n_batches = max(1, rows.shape[0] // batch)
    if quantize is not None and not 1 <= int(quantize) <= 22:
        raise ValueError("quantize is a number of mantissa bits, 1 to 22")
    grad = _grad_fn(_hashable(m))
    bits = None if quantize is None else jnp.int32(quantize)
    planted = jnp.int32(0 if fault is None else FAULTS.index(fault) + 1)
    lr, rate = m["learning_rate"], np.float32(m["bias_update_rate"])
    n_main = params["blocks/router_b"].shape[0]
    trace = []
    # numpy frees the GIL: a leaf a thread, four at a time (each holds one
    # leaf-sized temporary; the host has the calls' models to keep as well)
    with ThreadPoolExecutor(max_workers=4) as pool:
        for i in range(steps):
            b0 = (i % n_batches) * batch
            step_rows = rows[b0:b0 + batch]
            if fault == "half_batch":  # the second half padded out: no loss
                step_rows = step_rows.copy()
                step_rows[batch // 2:] = 0
            grads, out = jax.device_get(grad(
                params, jnp.asarray(step_rows), planted, bits))
            if on_step is not None:
                on_step(i, params, grads)
            if mom is None:
                mom = {k: np.zeros_like(v) for k, v in params.items()}
                var = {k: np.zeros_like(v) for k, v in params.items()}
            l_main, l_mtp, load, pairs, norms = out
            trace.append((l_main, l_mtp, pairs, norms))
            list(pool.map(
                lambda k: _adam(params[k], mom[k], var[k], grads[k], i + 1, lr),
                [k for k in params if not k.endswith("router_b")]))
            # b takes no gradient; the balancing rule moves it towards the
            # mean load
            for k, part in (("blocks/router_b", load[:n_main]),
                            ("mtp/router_b", load[n_main:])):
                if k in params:
                    params[k] += rate * np.sign(
                        part.mean(axis=-1, keepdims=True) - part)
            del grads
    return {
        "l_main": np.array([t[0] for t in trace], np.float64),
        "l_mtp": np.array([t[1] for t in trace], np.float64),
        "pairs": np.array([t[2] for t in trace], np.float64),
        "grad_norm": np.array([t[3] for t in trace], np.float64),
        "final": params, "init": jax.device_get(init_params(m, seed)),
    }


def next_item_logits(params: dict, history, m: dict):
    """Serving's forward: the last position's logits over the vocabulary
    slice for one history ``[T]`` (no padding), no MTP module."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        h, _, _ = trunk(params, jnp.asarray(history, jnp.int32), m)
        last = _norm(h[-1], params["lnf_g"], m["rms_norm_eps"])
        return np.asarray(_dot(last, params["head"].T, None))

