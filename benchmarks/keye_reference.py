"""Plain reference of the ``keye`` training step (Keye-VL-2.0-30B-A3B's
language model, text only): forward, both losses, gradients and Adam in
straightforward ``jax.numpy``, float32, ``jax.default_matmul_precision(
"highest")``. Imports nothing of ``pio_tpu``; the rounding, the SwiGLU, the
norm and the host's Adam are ``seq_reference``'s own, unchanged.

Written from the published ``config.json`` (``m`` is the model dict
:func:`model` makes of the configuration's file; ``x`` is the normed
residual stream of one row, ``[T, D]``; no bias anywhere but the indexer's
LayerNorm):

- Every layer alike: grouped-query attention whose keys a lightning indexer
  selects (``sa_config``), then an expert layer.
- Attention: ``q = RoPE(RMSNorm_head(x W_q))`` ``[T, H, d]``, ``k =
  RoPE(RMSNorm_head(x W_k))``, ``v = x W_v`` ``[T, H_kv, d]``; RoPE
  rotate-half over the whole head, theta ``rope_theta`` (``mrope_section``
  spans the head and a history's three position ids are equal: 1-D RoPE);
  query head ``j`` reads KV head ``j // (H / H_kv)``.
- Indexer, on ``x`` detached: ``qi = RoPE_half(x W_qi)`` ``[T, Hi, di]``,
  ``ki = RoPE_half(LayerNorm(x W_ki))`` ``[T, di]`` (the first ``di / 2``
  dims of a head rotated, theta ``rope_theta``), ``w = x W_w / sqrt(Hi)``,
  ``I[t, s] = di ** -0.5 sum_j w[t, j] relu(qi[t, j] . ki[s])``, ``s <= t``.
- Selection: ``S_t`` = the ``min(t + 1, topk)`` keys ``s <= t`` of largest
  ``I[t, s]``, ties to the earlier key: this reference's own stable sort
  (+0.0 and -0.0 one score). One selection for every head.
- ``o_j = softmax over S_t of (q_j k^T / sqrt(d)) v``; ``concat(o) W_o``.
- The selection's checksum: the positions of ``S_t``'s keys summed over
  the step's layers and queries (a selection per KV head: their mean).
- The indexer's loss: ``p[t] = mean over heads of the probabilities above``
  (no gradient), ``L_I = sum over layers, mean over the step's positions, of
  KL(p[t] || softmax over S_t of I[t])``. The step minimises ``L_main +
  L_I``; ``x`` detached, the indexer learns from ``L_I`` alone.
- Expert layer: ``p = softmax(x W_r)`` over all the router's outputs; the
  ``num_experts_per_tok`` largest; ``w = p_top / sum(p_top)``; ``y = sum
  over e selected and held of w_e E_e(x)``, ``E(x) = W_down(silu(W_gate x)
  * W_up x)``; no shared expert. Dense over tokens: every held expert runs
  on every token and is weighted by ``w_e`` or 0. What absent experts would
  add is left out.
- Loss: mean cross-entropy of the next event over the vocabulary slice.
- Adam as ``optax.adam``: ``m, v`` bias-corrected, ``eps`` 1e-8 outside the
  root.

Arranged only so that it fits one chip (Adam's moments stay on the host):
rows one at a time, ``jax.checkpoint`` a row, a layer, a chunk of
``Q_CHUNK`` queries (whose index scores ``[Q_CHUNK, Hi, T]`` stand at once),
a query head within it, an expert and a 2,048-token slice of the head. None
of that changes a number.

``quantize = k`` rounds both operands of every matmul but the router's to
``k`` mantissa bits (the program keeps that one in float32): 7 is the
configuration's own bfloat16, the witness; 3 the control. ``fault`` plants
one wrong equation (``FAULTS``).
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

from seq_reference import HEAD_CHUNK, _adam, _dot, _norm, _rounded, _swiglu

FAULTS = ("dense_attention", "topk_1024", "relu_left_out", "ties_to_later",
          "target_attached", "qk_norm_left_out", "selection_per_kv_head",
          "topk_not_normalised", "expert_dropped")
GROUPS = ("embedding", "head", "attn", "indexer", "router", "routed_experts",
          "norms")
Q_CHUNK = 256
#: the per-step numbers a training call's trace and this reference share
TRACE_KEYS = ("l_main", "l_index", "l_select", "pairs", "grad_norm")
#: ``select`` is no loss: the selected keys' positions summed over the
#: step's layers and queries, a checksum of the selection held beside them
LOSSES = ("main", "index", "select")


# ------------------------------------------------------------------- model
def model(config: dict) -> dict:
    """The flat dict the layers below read, from the configuration's file:
    the published keys, the layers here, the experts and vocabulary rows
    held, the ``init`` rule's numbers and the learning rate."""
    sa = config["sa_config"]
    rope = config["rope_scaling"]
    d = config["head_dim"]
    deployment = config["deployment"]
    if (config["mlp_only_layers"] or config["use_sliding_window"]
            or config["attention_bias"] or not config["norm_topk_prob"]
            or config["decoder_sparse_step"] != 1
            or config["tie_word_embeddings"]
            or rope.get("rope_type", "default") != "default"
            or sum(rope["mrope_section"]) != d // 2
            or sa["indexer_num_kv_heads"] != 1
            or config["num_local_experts"] != deployment["router_width"]):
        raise ValueError("a key this reference has no equation for")
    return {
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "num_hidden_layers": config["num_hidden_layers"],
        "head_dim": d, "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "index_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"],
        "router_width": deployment["router_width"],
        "experts_first": deployment["experts_first"],
        "experts_held": config["num_experts"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "init_std": config["init"]["init_std"],
        "embed_init_std": config["init"]["embed_init_std"],
        "learning_rate": config["algorithm_params"]["learning_rate"],
    }


# ------------------------------------------------------------------ shapes
def shapes(m: dict) -> dict:
    """``{"sparse/name": shape}`` of the layers, stacked, and the tables."""
    D, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    H, Hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    Hi, di = m["index_heads"], m["index_head_dim"]
    Fe, held = m["moe_intermediate_size"], m["experts_held"]
    layer = {
        "attn_norm": (L, D), "q_proj": (L, D, H * d), "k_proj": (L, D, Hkv * d),
        "v_proj": (L, D, Hkv * d), "o_proj": (L, H * d, D),
        "q_norm": (L, d), "k_norm": (L, d),
        "idx_q": (L, D, Hi * di), "idx_k": (L, D, di),
        "idx_k_norm_g": (L, di), "idx_k_norm_b": (L, di), "idx_w": (L, D, Hi),
        "ffn_norm": (L, D), "router_w": (L, D, m["router_width"]),
        "e_gate": (L, held, D, Fe), "e_up": (L, held, D, Fe),
        "e_down": (L, held, Fe, D),
    }
    return {"emb": (V, D), "head": (V, D), "lnf_g": (D,),
            **{"sparse/" + k: v for k, v in layer.items()}}


def init_params(m: dict, seed: int) -> dict:
    """The configuration's rule: a norm's gain is 1, the indexer's LayerNorm
    bias 0; every other parameter is ``normal(fold_in(PRNGKey(seed),
    crc32(path) & 0x7fffffff), shape, float32) * std``, std ``init_std``, or
    ``embed_init_std`` for ``emb``."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, shape in shapes(m).items():
        name = path.rpartition("/")[2]
        if name.endswith("norm") or name in ("lnf_g", "idx_k_norm_g"):
            out[path] = jnp.ones(shape, jnp.float32)
            continue
        if name == "idx_k_norm_b":
            out[path] = jnp.zeros(shape, jnp.float32)
            continue
        std = m["embed_init_std" if name == "emb" else "init_std"]
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(path.encode()) & 0x7FFFFFFF)
        out[path] = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return out


def group_of(path: str, m: dict) -> str:
    """Which of ``GROUPS`` a parameter is compared under: the indexer's
    ``idx_*`` apart, attention's four projections together, every other
    norm's gain together."""
    name = path.rpartition("/")[2]
    if name.startswith("idx_"):
        return "indexer"
    if name.endswith("norm") or name == "lnf_g":
        return "norms"
    if path in ("emb", "head"):
        return {"emb": "embedding", "head": "head"}[path]
    if name.endswith("_proj"):
        return "attn"
    if name.startswith("router"):
        return "router"
    return "routed_experts"


# ------------------------------------------------------------------- layers
def _is(fault, name: str):
    """Whether the planted fault is ``name``: a Python bool for a name (or
    ``None``); a traced bool where ``fault`` is a number, 1 + its place in
    ``FAULTS`` and 0 for none, so one compiled step serves every fault."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


def _rotate(x, theta, rotary: int):
    """``x [T, h, d]``: the first ``rotary`` dims of every head rotated at
    positions 0..T-1, dim ``i`` paired with ``i + rotary / 2``; the other
    dims pass through."""
    import jax.numpy as jnp

    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    c = x - x.mean(axis=-1, keepdims=True)
    return c / jnp.sqrt((c * c).mean(axis=-1, keepdims=True) + eps) * g + b


def _select(scores, k, fault):
    """``[n, T]`` scores (``-inf`` where not seen) -> the selection, bool:
    each row's first ``k`` keys in a stable sort by score, largest first, so
    ties go to the earlier key (to the later under ``ties_to_later``, which
    sorts the row reversed). The ``k``-th score ``thr`` and the first and
    last positions taken at it say which keys are in: above ``thr``, or at it
    between those two."""
    import jax
    import jax.numpy as jnp

    n, T = scores.shape
    scores = jnp.where(scores == 0, 0.0, scores)
    later = _is(fault, "ties_to_later")
    pos = jnp.broadcast_to(jnp.arange(T), (n, T))
    flipped = jnp.where(later, scores[:, ::-1], scores)
    down, order = jax.lax.sort((-flipped, pos), num_keys=1, is_stable=True)
    order = jnp.where(later, T - 1 - order, order)
    k = jnp.minimum(k, T)
    thr = -jnp.take_along_axis(down, jnp.full((n, 1), k - 1), axis=1)
    taken = (pos < k) & (-down == thr)  # the tied keys the sort took
    lo = jnp.where(taken, order, T).min(axis=1, keepdims=True)
    hi = jnp.where(taken, order, -1).max(axis=1, keepdims=True)
    inside = (scores > thr) | ((scores == thr) & (pos >= lo) & (pos <= hi))
    return inside & (scores > -jnp.inf)


def _index_scores(w, xn, m, q, fault):
    """The indexer of the detached ``xn``: ``(qi [T, Hi, di], ki [T, di],
    weights [T, Hi])``."""
    import jax

    T = xn.shape[0]
    Hi, di, eps = m["index_heads"], m["index_head_dim"], m["rms_norm_eps"]
    xi = jax.lax.stop_gradient(xn)
    qi = _rotate(_dot(xi, w["idx_q"], q).reshape(T, Hi, di), m["rope_theta"],
                 di // 2)
    ki = _layer_norm(_dot(xi, w["idx_k"], q), w["idx_k_norm_g"],
                     w["idx_k_norm_b"], eps)
    ki = _rotate(ki[:, None], m["rope_theta"], di // 2)[:, 0]
    return qi, ki, _dot(xi, w["idx_w"], q) / np.sqrt(Hi)


def _attention(w, x, m, q, fault):
    """``x [T, D]`` (the residual stream) -> ``(attention's output [T, D]
    before the residual, the indexer's loss summed over the row's
    positions, the selection's checksum)``."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, Hkv, d, eps = m["heads"], m["kv_heads"], m["head_dim"], m["rms_norm_eps"]
    Hi, di, theta = m["index_heads"], m["index_head_dim"], m["rope_theta"]
    group = H // Hkv
    xn = _norm(x, w["attn_norm"], eps)
    qh = _dot(xn, w["q_proj"], q).reshape(T, H, d)
    kh = _dot(xn, w["k_proj"], q).reshape(T, Hkv, d)
    no_norm = _is(fault, "qk_norm_left_out")
    qh = jnp.where(no_norm, qh, _norm(qh, w["q_norm"], eps))
    kh = jnp.where(no_norm, kh, _norm(kh, w["k_norm"], eps))
    qh, kh = _rotate(qh, theta, d), _rotate(kh, theta, d)
    vh = _dot(xn, w["v_proj"], q).reshape(T, Hkv, d)
    qi, ki, wi = _index_scores(w, xn, m, q, fault)
    k_top = jnp.where(_is(fault, "topk_1024"), 1024, m["index_topk"])
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T
    s_pos = jnp.arange(T)
    kh, vh = kh.transpose(1, 0, 2), vh.transpose(1, 0, 2)  # [Hkv, T, d]
    relu = lambda a: jnp.where(_is(fault, "relu_left_out"), a,  # noqa: E731
                               jax.nn.relu(a))

    def selection(index, part, seen):
        """``[n, Hkv, T]``: one selection shared by every head, or under
        ``selection_per_kv_head`` one a KV head from the indexer heads of
        its share; every earlier key under ``dense_attention``."""
        sel = jax.lax.cond(
            _is(fault, "selection_per_kv_head"),
            lambda: jax.vmap(lambda a: _select(a, k_top, fault), 1, 1)(part),
            lambda: jnp.broadcast_to(_select(index, k_top, fault)[:, None],
                                     part.shape))
        sel = jnp.where(_is(fault, "dense_attention"), seen[:, None], sel)
        return jax.lax.stop_gradient(sel)

    @jax.checkpoint
    def rows(args):
        qc, qic, wic, t_pos = args  # a chunk of queries against all keys
        seen = t_pos[:, None] >= s_pos[None, :]
        a, b = (x if q is None else _rounded(x, q) for x in (qic, ki))
        prod = relu(jnp.einsum("qhd,kd->qhk", a, b,
                               precision=jax.lax.Precision.HIGHEST))
        by_head = prod * wic[:, :, None] / np.sqrt(di)  # [n, Hi, T]
        index = jnp.where(seen, by_head.sum(axis=1), -jnp.inf)
        part = jnp.where(seen[:, None], by_head.reshape(
            by_head.shape[0], Hkv, Hi // Hkv, T).sum(axis=2), -jnp.inf)
        apart = _is(fault, "selection_per_kv_head")
        sel = selection(index, part, seen)

        def head(mass, j):
            pair = j // group
            s = _dot(qc[:, j], kh[pair].T, q) / np.sqrt(d)
            p = jax.nn.softmax(jnp.where(sel[:, pair], s, -jnp.inf), axis=-1)
            return mass.at[pair].add(p), _dot(p, vh[pair], q)

        mass, out = jax.lax.scan(
            jax.checkpoint(head), jnp.zeros((Hkv,) + index.shape),
            jnp.arange(H))
        # the target: the heads' mean (of each KV head's share, apart)
        target = jnp.where(apart, mass / group,
                           mass.sum(axis=0, keepdims=True) / H)
        target = jnp.where(_is(fault, "target_attached"), target,
                           jax.lax.stop_gradient(target))
        scores = jnp.where(apart, part.transpose(1, 0, 2), index[None])
        kept = sel.transpose(1, 0, 2)  # [Hkv, n, T]
        z = jax.nn.logsumexp(jnp.where(kept, scores, -jnp.inf), axis=-1)
        logq = jnp.where(kept, scores - z[..., None], 0.0)
        plogp = jnp.where(target > 0, target * jnp.log(
            jnp.where(target > 0, target, 1.0)), 0.0)
        kl = jnp.where(kept, plogp - target * logq, 0.0).sum(axis=-1)
        pos_sum = jnp.where(sel, s_pos, 0).sum(axis=-1, dtype=jnp.int32)
        return (out.transpose(1, 0, 2), kl.mean(axis=0).sum(),
                pos_sum.astype(jnp.float32).mean(axis=1).sum())

    out, kl, pos_sum = jax.lax.map(rows, (qh.reshape(-1, chunk, H, d),
                                 qi.reshape(-1, chunk, Hi, di),
                                 wi.reshape(-1, chunk, Hi),
                                 s_pos.reshape(-1, chunk)))
    return (_dot(out.reshape(T, H * d), w["o_proj"], q), kl.sum(),
            pos_sum.sum())


def _route(w, xn, m, fault):
    """``(idx [T, k], weight [T, k])`` of the normalised input."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.dot(xn, w["router_w"],
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, idx = jax.lax.top_k(p, m["num_experts_per_tok"])
    return idx, picked / jnp.where(_is(fault, "topk_not_normalised"), 1.0,
                                   picked.sum(axis=-1, keepdims=True))


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
    return y, load, pairs


def _layer(w, x, m, q, fault):
    """One layer -> ``(x, load [E], pairs, the indexer's loss, the
    selection's checksum)``."""
    out, kl, at = _attention(w, x, m, q, fault)
    x = x + out
    y, load, pairs = _moe(w, _norm(x, w["ffn_norm"], m["rms_norm_eps"]), m, q,
                          fault)
    return x + y, load, pairs, kl, at


def trunk(params, ids, m, q=None, fault=None):
    """One row ``ids [T]`` -> ``(h [T, D] before the final norm, load [L,
    E], pairs [L], the indexers' loss summed over layers and positions, the
    selections' checksum summed over layers)``."""
    import jax
    import jax.numpy as jnp

    x = params["emb"][ids]
    stack = {k.partition("/")[2]: v for k, v in params.items()
             if k.startswith("sparse/")}
    loads, pairs, kl, at = [], [], jnp.float32(0.0), jnp.float32(0.0)
    for i in range(m["num_hidden_layers"]):
        w = {k: v[i] for k, v in stack.items()}
        x, load, n, part, at_i = jax.checkpoint(
            lambda w, x: _layer(w, x, m, q, fault))(w, x)
        loads.append(load)
        pairs.append(n)
        kl, at = kl + part, at + at_i
    return x, jnp.stack(loads), jnp.stack(pairs), kl, at


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
    """One row's ``(ce, load [L, E], pairs [L], kl, the selections'
    checksum)``; the target is the next event, 0 = pad, unsupervised."""
    import jax.numpy as jnp

    t1 = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    m1 = ((t1 > 0) & (ids > 0)).astype(jnp.float32)
    h, load, pairs, kl, at = trunk(params, ids, m, q, fault)
    ce = _ce_sum(h, params["lnf_g"], params["head"], t1, m1, m, q)
    return ce, load, pairs, kl, at


def batch_loss(params, rows, m, q=None, fault=None):
    """``(loss, (l_main, l_index, load, pairs, l_select))`` of a batch
    ``rows [B, T]``, one row at a time: ``l_index`` is the indexers' loss
    summed over the layers and averaged over every position of the batch,
    ``l_select`` the selections' checksum summed over the layers and rows."""
    import jax
    import jax.numpy as jnp

    ce, load, pairs, kl, at = jax.lax.map(
        jax.checkpoint(lambda ids: row_sums(params, ids, m, q, fault)), rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    l_main = ce.sum() / jnp.maximum(((t1 > 0) & (rows > 0)).sum(), 1)
    l_index = kl.sum() / rows.size
    return l_main + l_index, (l_main, l_index, load.sum(axis=0),
                              pairs.sum(axis=0), at.sum())


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
    bits) -> (grads, (l_main, l_index, load, pairs, l_select,
    grad_norms))``;
    ``fault`` a number (:func:`_is`) and ``bits`` ``None`` or the traced
    number of mantissa bits."""
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
    device, Adam runs on the host. Returns numpy: ``l_main``, ``l_index``,
    ``l_select`` ``[steps]``, ``pairs`` ``[steps, layers]``, ``grad_norm`` ``[steps,
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
    grad = _grad_fn(_hashable(m))
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
            l_main, l_index, _load, pairs, l_select, norms = out
            trace.append((l_main, l_index, pairs, norms, l_select))
            list(pool.map(
                lambda k: _adam(params[k], mom[k], var[k], grads[k], i + 1,
                                m["learning_rate"]), list(params)))
            del grads
    return {
        "l_main": np.array([t[0] for t in trace], np.float64),
        "l_index": np.array([t[1] for t in trace], np.float64),
        "l_select": np.array([t[4] for t in trace], np.float64),
        "pairs": np.array([t[2] for t in trace], np.float64),
        "grad_norm": np.array([t[3] for t in trace], np.float64),
        "final": params, "init": jax.device_get(init_params(m, seed)),
    }


def next_item_logits(params: dict, history, m: dict):
    """Serving's forward: the last position's logits over the vocabulary
    slice for one history ``[T]`` (no padding)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        h = trunk(params, jnp.asarray(history, jnp.int32), m)[0]
        last = _norm(h[-1], params["lnf_g"], m["rms_norm_eps"])
        return np.asarray(_dot(last, params["head"].T, None))
