"""Operations and bytes one training call of the ``granitemoehybrid`` block
needs (no experts: a mixer and a dense MLP a layer), from its sizes.

Counts what the model needs, not what an implementation does, as
``nemotron_cost`` does: a matmul is 2 flop a multiply-add; a training step is
three forward passes' matmuls; nothing recomputed is counted; norms, softmax,
the convolution's four taps, the gates, the four multipliers and the
optimizer's elementwise work are left out.

- a **Mamba-2** mixer's recurrence is reckoned **for the chunked form at the
  published chunk** ``Q`` (clamped to a divisor of the length), whatever
  implements it and however its heads are blocked: an event and layer, ``C
  B^T`` inside the chunk **once a group** ``2 Q G N``, the weights against ``dt
  x`` ``2 Q H P``, the chunk's state ``2 H P N`` and ``C`` against the carried
  state ``2 H P N``: 4.26 Mflop at the published sizes (chunk 256, one group).
  Its bytes are ``x``, ``B``, ``C``, ``z`` (2 B a channel) and ``dt`` (4 B a
  head) read once and ``y`` written once forward, three times that a step;
- the **attention** mixer's scores are the lower triangle, ``k`` and ``v``
  counted once a KV head (``nemotron_cost``'s count);
- a **dense MLP** mixer is three matrices of ``D x F``; its bytes are its
  weights read in 2 B forward and twice backward and their float32 gradient
  written, and a token's ``x`` read and ``y`` written (2 B) three times;
- the **head** reads the tied table: counted as a head of its own, and the
  table's parameters once.

``m`` is ``granite_reference.model``'s dict; ``pairs`` is 0.0 (the driver hands
every cost function the reference's routed pairs). The whole call's bytes:
every parameter's weight, gradient and two Adam moments, float32, read and
written once a step (28 B a parameter), its weight read in 2 B forward and
backward, and the scan's and the attention's rows above.
"""

from __future__ import annotations

from nemotron_cost import chunk_of


def ssm_chunks(m: dict, rows: int, seq_len: int, steps: int) -> int:
    """Chunks the mamba mixers' carrying loops run in one call, forward."""
    n_mamba = m["mixer_pattern"].count("mamba")
    return steps * rows * n_mamba * (
        seq_len // chunk_of(seq_len, m["mamba_chunk_size"]))


def n_parameters(m: dict) -> int:
    import granite_reference

    total = 0
    for shape in granite_reference.shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def cost(m: dict, rows: int, seq_len: int, steps: int, pairs: float = 0.0) -> dict:
    """``{"flops", "bytes", "kernels": {"ssm_scan", "attn", "ffn"}, "share"}``
    of one call: ``steps`` optimizer steps of ``rows`` histories of ``seq_len``
    events."""
    D, V, F = m["hidden_size"], m["vocab_size"], m["shared_intermediate_size"]
    H, P, G, N = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"],
                  m["mamba_d_state"])
    inner, conv = H * P, H * P + 2 * G * N
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    n_of = {k: m["mixer_pattern"].count(k) for k in ("mamba", "attn", "mlp")}
    tokens = rows * seq_len * steps
    Q = chunk_of(seq_len, m["mamba_chunk_size"])
    seen = rows * steps * (seq_len * (seq_len + 1) // 2)

    fwd = {
        "ssm_proj": 2 * tokens * n_of["mamba"] * D * (2 * inner + conv + H),
        "ssm_scan": tokens * n_of["mamba"] * (
            2 * Q * (G * N + H * P) + 4 * H * P * N),
        "attn_proj": 2 * tokens * n_of["attn"] * D * 2 * (Hq + Hkv) * d,
        "attn": 2 * seen * n_of["attn"] * Hq * 2 * d,
        "dense_mlp": 2 * tokens * n_of["mlp"] * 3 * D * F,
        "head": 2 * tokens * D * V,
    }
    flops = {k: 3.0 * v for k, v in fwd.items()}
    total = sum(flops.values())

    scan_bytes = 3 * tokens * n_of["mamba"] * (2 * (3 * inner + 2 * G * N) + 4 * H)
    attn_bytes = n_of["attn"] * tokens * 2 * 6 * (Hq + Hkv) * d
    ffn_bytes = (n_of["mlp"] * steps * 3 * D * F * (3 * 2 + 4)
                 + 3 * tokens * n_of["mlp"] * 2 * D * 2)
    params = n_parameters(m)
    total_bytes = steps * params * (28 + 2 * 2) + scan_bytes + attn_bytes
    return {
        "flops": float(total), "bytes": float(total_bytes),
        "kernels": {
            "ssm_scan": {"flops": float(flops["ssm_scan"]),
                         "bytes": float(scan_bytes)},
            "attn": {"flops": float(flops["attn"]), "bytes": float(attn_bytes)},
            "ffn": {"flops": float(flops["dense_mlp"]),
                    "bytes": float(ffn_bytes)},
        },
        "share": {k: v / total for k, v in flops.items()},
        "forward_flops_per_event": sum(fwd.values()) / tokens,
        "ssm_chunks": ssm_chunks(m, rows, seq_len, steps),
        "parameters": params,
    }
