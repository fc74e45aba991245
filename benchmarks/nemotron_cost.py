"""Operations and bytes one training call of the ``nemotron_h`` block needs,
from its sizes and the reference's count of routed pairs.

Counts what the model needs, not what an implementation does, as
``laguna_cost`` does: a matmul is 2 flop a multiply-add; a training step is
three forward passes' matmuls; nothing recomputed is counted; norms, softmax,
the convolution's four taps, the gates, the router's top-k and the optimizer's
elementwise work are left out. What differs here:

- a **Mamba-2** layer's recurrence is reckoned **for the chunked form at the
  published chunk** ``Q`` (clamped to a divisor of the length, as a program
  must), whatever implements it, so that a later kernel is judged by the same
  count: an event and layer, ``C B^T`` inside the chunk ``2 Q G N``, the
  weights against ``dt x`` ``2 Q H P``, the chunk's state ``2 H P N`` and ``C``
  against the carried state ``2 H P N``: 3.41 Mflop at the published sizes.
  Its bytes are ``x``, ``B``, ``C``, ``z`` (2 B a channel) and ``dt`` (4 B a head)
  read once and ``y`` written once forward, and three times that a training
  step (the backward pass reads them and ``dy``, and writes five gradients);
- the **attention** layers' scores are the lower triangle, ``k`` and ``v``
  counted once a KV head (``laguna_cost``'s count for a full layer);
- an **expert** is two matrices.

``m`` is ``nemotron_reference.model``'s dict; ``pairs`` is the (token, held
expert) pairs of the whole call, every expert layer, as the reference counted
them. The whole call's bytes: every parameter's weight, gradient and two Adam
moments, float32, read and written once a step (28 B a parameter), its weight
read in 2 B forward and backward, and the three kernels' rows above.
"""

from __future__ import annotations


def chunk_of(seq_len: int, chunk: int) -> int:
    """The largest divisor of ``seq_len`` that is at most ``chunk``."""
    q = max(1, min(chunk, seq_len))
    while seq_len % q:
        q -= 1
    return q


def ssm_chunks(m: dict, rows: int, seq_len: int, steps: int) -> int:
    """Chunks the mamba layers' carrying loops run in one call, forward."""
    n_mamba = m["mixer_pattern"].count("mamba")
    return steps * rows * n_mamba * (seq_len // chunk_of(seq_len, m["chunk_size"]))


def n_parameters(m: dict) -> int:
    import nemotron_reference

    total = 0
    for shape in nemotron_reference.shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def cost(m: dict, rows: int, seq_len: int, steps: int, pairs: float) -> dict:
    """``{"flops", "bytes", "kernels": {"ssm_scan", "attn", "moe_experts"},
    "share"}`` of one call: ``steps`` optimizer steps of ``rows`` histories of
    ``seq_len`` events."""
    D, V = m["hidden_size"], m["vocab_size"]
    H, P, G, N = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state_size"])
    inner, conv = H * P, H * P + 2 * G * N
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    Fe, Fs = m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]
    n_of = {k: m["mixer_pattern"].count(k) for k in ("mamba", "moe", "attn")}
    tokens = rows * seq_len * steps
    Q = chunk_of(seq_len, m["chunk_size"])
    seen = rows * steps * (seq_len * (seq_len + 1) // 2)

    fwd = {
        "ssm_proj": 2 * tokens * n_of["mamba"] * D * (2 * inner + conv + H),
        "ssm_scan": tokens * n_of["mamba"] * (
            2 * Q * (G * N + H * P) + 4 * H * P * N),
        "attn_proj": 2 * tokens * n_of["attn"] * D * 2 * (Hq + Hkv) * d,
        "attn": 2 * seen * n_of["attn"] * Hq * 2 * d,
        "shared_expert": 2 * tokens * n_of["moe"] * 2 * D * Fs,
        "router": 2 * tokens * n_of["moe"] * D * m["router_width"],
        "moe_experts": 2 * pairs * 2 * D * Fe,
        "head": 2 * tokens * D * V,
    }
    flops = {k: 3.0 * v for k, v in fwd.items()}
    total = sum(flops.values())

    scan_bytes = 3 * tokens * n_of["mamba"] * (2 * (3 * inner + 2 * G * N) + 4 * H)
    attn_bytes = n_of["attn"] * tokens * 2 * 6 * (Hq + Hkv) * d
    rows_bytes = pairs * 2 * D * 2 * 3
    experts_bytes = (n_of["moe"] * steps * m["experts_held"] * 2 * D * Fe
                     * (3 * 2 + 4) + rows_bytes)
    params = n_parameters(m)
    total_bytes = (steps * params * (28 + 2 * 2) + scan_bytes + attn_bytes
                   + rows_bytes)
    return {
        "flops": float(total), "bytes": float(total_bytes),
        "kernels": {
            "ssm_scan": {"flops": float(flops["ssm_scan"]),
                         "bytes": float(scan_bytes)},
            "attn": {"flops": float(flops["attn"]), "bytes": float(attn_bytes)},
            "moe_experts": {"flops": float(flops["moe_experts"]),
                            "bytes": float(experts_bytes)},
        },
        "share": {k: v / total for k, v in flops.items()},
        "forward_flops_per_event": sum(fwd.values()) / tokens,
        "ssm_chunks": ssm_chunks(m, rows, seq_len, steps),
        "parameters": params,
    }
