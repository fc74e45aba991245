"""Operations and bytes one training call of the ``keye`` block needs, from
its sizes and the reference's count of routed pairs.

Counts what the model needs, not what an implementation does, as
``laguna_cost`` does for the gqa/moe block: a matmul is 2 flop a
multiply-add; a training step is three forward passes' matmuls; nothing
recomputed is counted; norms, softmax, RoPE, the index scores' ReLU and
weighting, the selection, the indexer loss's target and KL, the router's
top-k and the optimizer's elementwise work are left out. What differs:

- the **indexer** scores every earlier key: ``T (T + 1) / 2`` query-key
  pairs a row and layer, ``2 Hi di`` flop a pair (its heads' dot products);
  its projections ``D (Hi di + di + Hi)`` a token;
- the **attention** sees the selected pairs only, ``sum_t min(t + 1,
  topk)`` a head and row: the tiles an implementation computes beyond them
  (masked keys of the blocks it visits) are its own cost, not the model's,
  so a masked-dense form reads at most the selected share of its roofline;
- ``k`` and ``v`` are counted once a KV head, however many query heads
  share them; no shared expert, no dense layer.

``m`` is ``keye_reference.model``'s dict; ``pairs`` is the (token, held
expert) pairs of the whole call, every expert layer, as the reference
counted them. Bytes at 2 B an operand unless said otherwise:

- ``index``: forward ``qi``, ``ki``, ``w`` read; backward those read again
  and their gradients written: six operands of ``Hi di + di + Hi`` a token
  and layer (the ``[T, T]`` scores stay in fast memory in the least form);
- ``sparse_attn``: forward q, k, v read and o written; backward q, k, v, o,
  do read and dq, dk, dv written: six operands of ``H d`` and six of ``H_kv
  d`` a token and layer (the selection's mask, ``T^2 / 8`` B a layer, read
  three times);
- ``experts``: the held experts' three matrices read forward and twice
  backward and their float32 gradients written; each pair's input row read
  and output row written, forward, and the same twice over backward;
- the whole call: every parameter's weight, gradient and two Adam moments,
  float32, read and written once a step (28 B a parameter), its weight read
  in 2 B forward and backward, and the rows above.
"""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    """Query-key pairs of a row's lower triangle."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the pairs a row's selection holds."""
    if topk >= seq_len:
        return causal_pairs(seq_len)
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def n_parameters(m: dict) -> int:
    import keye_reference

    total = 0
    for shape in keye_reference.shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def cost(m: dict, rows: int, seq_len: int, steps: int, pairs: float) -> dict:
    """``{"flops", "bytes", "kernels": {"index", "sparse_attn", "experts"},
    "share"}`` of one call: ``steps`` optimizer steps of ``rows`` histories
    of ``seq_len`` events."""
    D, V, d = m["hidden_size"], m["vocab_size"], m["head_dim"]
    H, Hkv, L = m["heads"], m["kv_heads"], m["num_hidden_layers"]
    Hi, di = m["index_heads"], m["index_head_dim"]
    Fe = m["moe_intermediate_size"]
    tokens = rows * seq_len * steps
    row_steps = rows * steps
    fwd = {
        "attn_proj": 2 * tokens * L * D * (2 * H * d + 2 * Hkv * d),
        "index_proj": 2 * tokens * L * D * (Hi * di + di + Hi),
        "index": 2 * row_steps * causal_pairs(seq_len) * L * Hi * di,
        "sparse_attn": 2 * row_steps * selected_pairs(
            seq_len, m["index_topk"]) * L * H * 2 * d,
        "router": 2 * tokens * L * D * m["router_width"],
        "experts": 2 * pairs * 3 * D * Fe,
        "head": 2 * tokens * D * V,
    }
    flops = {k: 3.0 * v for k, v in fwd.items()}
    total = sum(flops.values())

    index_bytes = L * tokens * 2 * 6 * (Hi * di + di + Hi)
    attn_bytes = (L * tokens * 2 * 6 * (H + Hkv) * d
                  + L * row_steps * 3 * seq_len * seq_len // 8)
    held = m["experts_held"]
    experts_bytes = (L * steps * held * 3 * D * Fe * (3 * 2 + 4)
                     + pairs * 2 * D * 2 * 3)
    params = n_parameters(m)
    total_bytes = (steps * params * (28 + 2 * 2) + index_bytes + attn_bytes
                   + pairs * 2 * D * 2 * 3)
    return {
        "flops": float(total), "bytes": float(total_bytes),
        "kernels": {
            "index": {"flops": float(flops["index"]),
                      "bytes": float(index_bytes)},
            "sparse_attn": {"flops": float(flops["sparse_attn"]),
                            "bytes": float(attn_bytes)},
            "experts": {"flops": float(flops["experts"]),
                        "bytes": float(experts_bytes)},
        },
        "share": {k: v / total for k, v in flops.items()},
        "forward_flops_per_event": sum(fwd.values()) / tokens,
        "parameters": params,
    }
