"""Operations and bytes one training call of the ``laguna`` block needs, from
its sizes and the reference's count of routed pairs.

Counts what the model needs, not what an implementation does, as ``seq_cost``
does for the mla/moe block: a matmul is 2 flop a multiply-add; a training
step is three forward passes' matmuls; nothing recomputed is counted; norms,
softmax, RoPE, the gate's sigmoid, the router's top-k and the optimizer's
elementwise work are left out. What differs here:

- a **full** layer's scores are the lower triangle, ``T (T + 1) / 2``
  query-key pairs a query head and row;
- a **window** layer's are its visible pairs, ``sum_t min(t + 1, W)``: the
  tiles an implementation computes beyond them (the masked halves of the two
  tiles a query block visits) are its own cost, not the model's;
- a layer's query-head count is its kind's, and **``k`` and ``v`` are counted
  once a KV head**, however many query heads share them.

``m`` is ``laguna_reference.model``'s dict; ``pairs`` is the (token, held
expert) pairs of the whole call, every expert layer, as the reference counted
them. Bytes at 2 B an operand unless said otherwise:

- ``attn_<kind>``: forward q, k, v read and o written; backward q, k, v, o, do
  read and dq, dk, dv written: six operands of ``H_kind x d`` and six of
  ``H_kv x d`` a token and layer;
- ``experts``: the held experts' three matrices read forward and twice
  backward and their float32 gradients written; each pair's input row read
  and output row written, forward, and the same twice over backward;
- the whole call: every parameter's weight, gradient and two Adam moments,
  float32, read and written once a step (28 B a parameter), its weight read in
  2 B forward and backward, and the attention's and experts' rows above.
"""

from __future__ import annotations


def visible_pairs(seq_len: int, window: int = 0) -> int:
    """Query-key pairs one head sees in one row: the lower triangle, or under
    a window ``sum_t min(t + 1, window)``."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def n_parameters(m: dict) -> int:
    import laguna_reference

    total = 0
    for shape in laguna_reference.shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def cost(m: dict, rows: int, seq_len: int, steps: int, pairs: float) -> dict:
    """``{"flops", "bytes", "kernels": {"attn_full", "attn_window", "experts"},
    "share"}`` of one call: ``steps`` optimizer steps of ``rows`` histories of
    ``seq_len`` events."""
    import laguna_reference as ref

    D, V, d, Hkv = m["hidden_size"], m["vocab_size"], m["head_dim"], m["kv_heads"]
    Fe, Fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    n_layers, n_dense = m["num_hidden_layers"], m["dense_layers"]
    n_expert = n_layers - n_dense
    kinds = [ref.kind_of(m, i) for i in range(n_layers)]
    n_of = {k: kinds.count(k) for k in ("full", "window")}
    heads = {k: ref.heads_of(m, k) for k in ("full", "window")}
    tokens = rows * seq_len * steps
    seen = {"full": rows * steps * visible_pairs(seq_len),
            "window": rows * steps * visible_pairs(seq_len, m["sliding_window"])}

    fwd = {
        # q, k, v, the gate's map and o
        "attn_proj": 2 * tokens * sum(
            n_of[k] * D * (2 * heads[k] * d + 2 * Hkv * d + heads[k])
            for k in n_of),
        "attn_full": 2 * seen["full"] * n_of["full"] * heads["full"] * 2 * d,
        "attn_window": 2 * seen["window"] * n_of["window"] * heads["window"] * 2 * d,
        "dense_mlp": 2 * tokens * n_dense * 3 * D * m["intermediate_size"],
        "shared_expert": 2 * tokens * n_expert * 3 * D * Fs,
        "router": 2 * tokens * n_expert * D * m["router_width"],
        "experts": 2 * pairs * 3 * D * Fe,
        "head": 2 * tokens * D * V,
    }
    flops = {k: 3.0 * v for k, v in fwd.items()}
    total = sum(flops.values())

    attn_bytes = {k: n_of[k] * tokens * 2 * 6 * (heads[k] + Hkv) * d for k in n_of}
    held = m["experts_held"]
    experts_bytes = (n_expert * steps * held * 3 * D * Fe * (3 * 2 + 4)
                     + pairs * 2 * D * 2 * 3)
    params = n_parameters(m)
    total_bytes = (steps * params * (28 + 2 * 2) + sum(attn_bytes.values())
                   + pairs * 2 * D * 2 * 3)
    return {
        "flops": float(total), "bytes": float(total_bytes),
        "kernels": {
            "attn_full": {"flops": float(flops["attn_full"]),
                          "bytes": float(attn_bytes["full"])},
            "attn_window": {"flops": float(flops["attn_window"]),
                            "bytes": float(attn_bytes["window"])},
            "experts": {"flops": float(flops["experts"]),
                        "bytes": float(experts_bytes)},
        },
        "share": {k: v / total for k, v in flops.items()},
        "forward_flops_per_event": sum(fwd.values()) / tokens,
        "parameters": params,
    }
