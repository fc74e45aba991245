"""What the two test files of the block without experts share
(``test_granite_block.py``, ``test_granite_layers.py``): one small model of
the ``granitemoehybrid`` family (a mixer and a dense MLP a layer, a tied
table, all four multipliers set to values that are not 1), as the program's
config and as the benchmark's reference reads it."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
for p in (BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import granite_reference as R  # noqa: E402

from pio_tpu.models import seq_layers, seqrec  # noqa: E402
from pio_tpu.models.seqrec import SeqRecConfig  # noqa: E402

V, T = 48, 32
#: three layers of the model: mamba, attention, mamba, each with its MLP
PATTERN = ("mamba", "mlp", "attn", "mlp", "mamba", "mlp")
CFG = SeqRecConfig(
    attention_kind="gqa", ffn_kind="moe", attn_rope=False, attn_gate=False,
    mixer_pattern=PATTERN, tied_head=True, embed_scale=12.0,
    residual_scale=0.22, attn_scale=0.015625, logit_scale=0.125,
    d_model=32, n_layers=6, ffn=48, head_dim=4, kv_heads=2, heads_full=8,
    ssm_heads=32, ssm_head_dim=2, ssm_groups=1, ssm_state=8, ssm_conv=4,
    ssm_chunk=8, norm_eps=1e-5, max_len=T, batch_size=2, steps=3,
    learning_rate=1e-3, compute_dtype="float32", stream="off", seed=11,
)
#: the same model as the benchmark's reference reads it
M = dict(
    vocab_size=V, hidden_size=32, num_hidden_layers=3, mixer_pattern=PATTERN,
    n_mixers=6, mamba_n_heads=32, mamba_d_head=2, mamba_n_groups=1,
    mamba_d_state=8, mamba_d_conv=4, mamba_chunk_size=8, time_step_min=1e-3,
    time_step_max=0.1, time_step_floor=1e-4, head_dim=4,
    num_attention_heads=8, num_key_value_heads=2, rope_theta=1e4,
    rms_norm_eps=1e-5, shared_intermediate_size=48, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logit_multiplier=0.125, init_std=seq_layers.INIT_STD, learning_rate=1e-3,
)


def histories(n=8, seed=0):
    return np.random.default_rng(seed).integers(1, V, (n, T)).astype(np.int32)


def flat(params):
    out = {}
    for group, value in params.items():
        if isinstance(value, dict):
            out.update({f"{group}/{k}": np.asarray(v) for k, v in value.items()})
        else:
            out[group] = np.asarray(value)
    return out


def group_errors(got: dict, want: dict, scale: dict) -> dict:
    """``||got - want|| / ||scale||`` per parameter group of the reference."""
    diff = dict.fromkeys(R.GROUPS, 0.0)
    size = dict.fromkeys(R.GROUPS, 0.0)
    for path, w in want.items():
        g = R.group_of(path, M)
        diff[g] += float(np.sum((np.asarray(got[path], np.float64) - w) ** 2))
        size[g] += float(np.sum(np.asarray(scale[path], np.float64) ** 2))
    return {g: np.sqrt(diff[g] / size[g]) for g in R.GROUPS if size[g] > 0}


def program_loss(params, rows, cfg=CFG, m_axis=None):
    import jax.numpy as jnp

    rows = jnp.asarray(rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    m1 = ((t1 > 0) & (rows > 0)).astype(jnp.float32)
    sums, counters = seqrec._latent_loss_sums(
        params, (rows, t1, m1, t1, m1), cfg, m_axis, None)
    return seqrec._latent_loss(sums, counters, cfg)[0]


def one_layer(leaves: dict, seed: int = 5) -> dict:
    """One layer's parameters (no layer dim) drawn as the block draws them."""
    return {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in leaves.items()}, seed)["b"].items()}
