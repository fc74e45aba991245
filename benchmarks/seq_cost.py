"""Operations and bytes one training call of the sequence model needs, from
its sizes and the reference's count of routed pairs.

Counts what the model needs, not what an implementation does: a matmul is
2 flop a multiply-add, the causal scores are the lower triangle
(``T (T + 1) / 2`` query-key pairs a row and head), a training step is three
forward passes' matmuls (forward, and the two gradients of each), and
nothing recomputed is counted (no rematerialised layer, no score tile
computed again in the backward pass). Norms, softmax, RoPE, the router's
top-k and the optimizer's elementwise work are left out: they are under a
hundredth of the matmuls at these widths.

``m`` is the reference's model dict (``seq_reference.shapes``); ``pairs`` is
the (token, held expert) pairs of the whole call, every expert layer and the
MTP module's, as the reference counted them.

Bytes, at 2 B an operand (the configuration states bfloat16 operands) unless
said otherwise:

- ``attn``: q, k, v read and o written forward; q, k, v, o, do read and dq,
  dk, dv written backward;
- ``experts``: the held experts' three matrices read forward and twice
  backward and their float32 gradients written; each pair's input row read
  and output row written, forward, and the same twice over backward;
- the whole call: every parameter's weight, gradient and two Adam moments,
  float32, read and written once a step (28 B a parameter: the gradient is
  written once and read once), its weight read in 2 B forward and backward,
  and the attention's and experts' rows above.
"""

from __future__ import annotations


def _mla_proj_macs(m: dict) -> int:
    D, H = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (D * m["q_lora_rank"] + m["q_lora_rank"] * H * (dn + dr)
            + D * (m["kv_lora_rank"] + dr)
            + m["kv_lora_rank"] * H * (dn + dv) + H * dv * D)


def n_parameters(m: dict) -> int:
    import seq_reference

    total = 0
    for shape in seq_reference.shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def seq_cost(m: dict, rows: int, seq_len: int, steps: int, pairs: float) -> dict:
    """``{"flops", "bytes", "attn": {...}, "experts": {...}, "share": {...}}``
    of one call: ``steps`` optimizer steps of ``rows`` histories of
    ``seq_len`` events."""
    D, H, V = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    Fe = m["moe_intermediate_size"]
    Fs = Fe * m["n_shared_experts"]
    n_dense = m["first_k_dense_replace"]
    n_mtp = m["num_nextn_predict_layers"]
    n_expert = m["num_hidden_layers"] - n_dense + n_mtp  # the MTP module's too
    n_attn = n_dense + n_expert
    tokens = rows * seq_len * steps
    tri = rows * steps * seq_len * (seq_len + 1) // 2  # query-key pairs a head

    fwd = {
        "mla_proj": 2 * tokens * n_attn * _mla_proj_macs(m),
        "attn": 2 * tri * n_attn * H * ((dn + dr) + dv),
        "dense_mlp": 2 * tokens * n_dense * 3 * D * m["intermediate_size"],
        "shared_expert": 2 * tokens * n_expert * 3 * D * Fs,
        "router": 2 * tokens * n_expert * D * m["router_width"],
        "experts": 2 * pairs * 3 * D * Fe,
        "head": 2 * tokens * (1 + n_mtp) * D * V + 2 * tokens * n_mtp * 2 * D * D,
    }
    flops = {k: 3.0 * v for k, v in fwd.items()}
    total = sum(flops.values())

    # forward q, k, v, o; backward q, k, v, o, do and dq, dk, dv: six
    # operands of each width, [T, H, width] at 2 B
    attn_bytes = n_attn * tokens * H * 2 * 6 * ((dn + dr) + dv)
    held = m["n_routed_experts"]
    experts_bytes = (n_expert * steps * held * 3 * D * Fe * (3 * 2 + 4)
                     + pairs * 2 * D * 2 * 3)
    params = n_parameters(m)
    total_bytes = (steps * params * (28 + 2 * 2) + attn_bytes
                   + pairs * 2 * D * 2 * 3)
    return {
        "flops": float(total), "bytes": float(total_bytes),
        "attn": {"flops": float(flops["attn"]), "bytes": float(attn_bytes)},
        "experts": {"flops": float(flops["experts"]),
                    "bytes": float(experts_bytes)},
        "share": {k: v / total for k, v in flops.items()},
        "forward_flops_per_event": sum(fwd.values()) / tokens,
        "parameters": params,
    }
