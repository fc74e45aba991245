"""Test-side plain reference of the gqa/moe block (Laguna-S-2.1's): the
forward pass and the loss of one row in plain ``jax.numpy``, float32, one
layer, one head and one expert at a time with dense masks, written from the
equations in ISSUE 33 and independent of ``pio_tpu`` and of the benchmark's
``laguna_reference.py`` (a test holds the two references to each other).
Gradients are ``jax.grad`` of :func:`loss`.

``m`` describes the model: ``D, d, kv_heads, heads={"full", "window"},
kinds=(kind of every layer), dense_layers, window, eps, theta={"full",
"window"}, rotary_full, yarn=(factor, original_len, beta_fast, beta_slow,
attention_factor), router_width, experts_first, top_k, scale``. The weights
are the program's tree, flat: ``{"dense/q_proj": [layers, ...], ...}``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def dot(a, b):
    return jnp.dot(a, b, precision=HI)


def norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_table(theta, r, factor, original_len, beta_fast, beta_slow):
    """The closed form of ISSUE 33: ``low`` 9 and ``high`` 18 at the
    published numbers."""
    def dim(turns):
        return r * math.log(original_len / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), r - 1)
    out = []
    for j in range(r // 2):
        f = theta ** (-2.0 * j / r)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / factor * ramp)
    return np.array(out, np.float32)


def rotate(v, freqs, factor=1.0):
    """``v [T, d]`` of one head: dim ``i`` paired with ``i + len(freqs)``."""
    half = len(freqs)
    ang = jnp.arange(v.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = v[:, :half], v[:, half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            v[:, 2 * half:]], axis=1)


def turn(v, m, kind):
    if kind == "window":
        half = m["d"] // 2
        return rotate(v, m["theta"]["window"] ** (
            -jnp.arange(half, dtype=jnp.float32) / half))
    *table, attention_factor = m["yarn"]
    return rotate(v, yarn_table(m["theta"]["full"], m["rotary_full"], *table),
                  attention_factor)


def attention(w, h, m, kind):
    """``h [T, D]`` -> what attention adds to the residual stream."""
    T, d, H, Hkv = h.shape[0], m["d"], m["heads"][kind], m["kv_heads"]
    x = norm(h, w["attn_norm"], m["eps"])
    q, k, v = dot(x, w["q_proj"]), dot(x, w["k_proj"]), dot(x, w["v_proj"])
    gate = jax.nn.sigmoid(dot(x, w["g_proj"]))  # [T, H]
    t = jnp.arange(T)
    seen = t[:, None] >= t[None, :]
    if kind == "window":
        seen = seen & (t[:, None] - t[None, :] < m["window"])
    heads = []
    for j in range(H):
        pair = j // (H // Hkv)
        qj = turn(q[:, j * d:(j + 1) * d], m, kind)
        kj = turn(k[:, pair * d:(pair + 1) * d], m, kind)
        s = dot(qj, kj.T) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        heads.append(gate[:, j:j + 1] * dot(p, v[:, pair * d:(pair + 1) * d]))
    return dot(jnp.concatenate(heads, axis=1), w["o_proj"])


def swiglu(x, gate, up, down):
    return dot(jax.nn.silu(dot(x, gate)) * dot(x, up), down)


def moe(w, x, m):
    """``x [T, D]`` normed -> ``(held experts' weighted sum + shared expert,
    pairs routed to held experts)``."""
    p = jax.nn.softmax(dot(x, w["router_w"]), axis=-1)
    top, idx = jax.lax.top_k(p, m["top_k"])
    weight = m["scale"] * top / top.sum(axis=-1, keepdims=True)
    y, pairs = swiglu(x, w["s_gate"], w["s_up"], w["s_down"]), 0
    for e in range(w["e_gate"].shape[0]):
        chosen = idx == m["experts_first"] + e  # [T, k]
        pairs = pairs + chosen.sum()
        y = y + (weight * chosen).sum(axis=1, keepdims=True) * swiglu(
            x, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
    return y, pairs


def layer_weights(params, m):
    """The layers' weights in the model's order: ``[(kind, dense?, w)]``."""
    taken, out = {"dense": 0, "full": 0, "window": 0}, []
    for i, kind in enumerate(m["kinds"]):
        stack = "dense" if i < m["dense_layers"] else kind
        out.append((kind, stack == "dense", {
            k.split("/")[1]: a[taken[stack]] for k, a in params.items()
            if k.startswith(stack + "/")}))
        taken[stack] += 1
    return out


def hidden(params, ids, m):
    h = params["emb"][ids]
    for kind, dense, w in layer_weights(params, m):
        h = h + attention(w, h, m, kind)
        x = norm(h, w["ffn_norm"], m["eps"])
        h = h + (swiglu(x, w["w_gate"], w["w_up"], w["w_down"]) if dense
                 else moe(w, x, m)[0])
    return h


def logits(params, ids, m):
    return dot(norm(hidden(params, ids, m), params["lnf_g"], m["eps"]),
               params["head"].T)


def loss(params, rows, m):
    """Mean cross-entropy of the next event over the rows' supervised
    positions (0 = pad)."""
    total, count = 0.0, 0
    for ids in rows:
        z = logits(params, jnp.asarray(ids), m)[:-1]
        target = jnp.asarray(ids[1:])
        keep = (target > 0) & (jnp.asarray(ids[:-1]) > 0)
        ce = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, target[:, None], axis=1)[:, 0]
        total, count = total + (ce * keep).sum(), count + keep.sum()
    return total / count
