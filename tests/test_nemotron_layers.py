"""The layers of the block of single mixers one by one, at a small size on
the CPU against ``benchmarks/nemotron_reference.py``: the chunked scan
against the time-step recurrence, a state that is not carried, the Mamba-2
mixer, the expert shares, the attention layer, the blocks ``check_block``
refuses (a ``seq`` axis under a mamba layer among them), and one batch's loss and gradients under the
float32 and the bfloat16 policy. The trained block, and the tolerances, are in
``test_nemotron_block.py``."""

import dataclasses

import numpy as np
import pytest

from nemotron_small import (CFG, M, R, T, V, flat, group_errors, histories,
                            one_layer, program_loss)

from pio_tpu.models import seq_layers, seqrec
from pio_tpu.models.seqrec import train_seqrec


# ------------------------------------------- one batch's loss and gradients
def _gradients(cfg, quantize):
    import jax
    import jax.numpy as jnp

    rows = histories(2, seed=3)
    ours = jax.jit(jax.value_and_grad(
        lambda p: program_loss(p, rows, cfg)))(seqrec.init_params(V, cfg))
    bits = None if quantize is None else jnp.int32(quantize)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            lambda p: R.batch_loss(p, jnp.asarray(rows), M, bits)[0]))(
                R.init_params(M, cfg.seed))
    return (float(ours[0]), flat(ours[1])), (float(want[0]),
                                             jax.device_get(want[1]))


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of one batch from the same weights: the program's
    under the float32 policy against the plain reference."""
    return _gradients(CFG, None)


@pytest.fixture(scope="module")
def gradients_bf16():
    """The same under the published bfloat16 policy, against the reference
    with bfloat16 operands."""
    return _gradients(dataclasses.replace(CFG, compute_dtype="bfloat16"), 7)


def test_the_loss_matches_the_reference(gradients, gradients_bf16):
    ours, want = gradients
    assert ours[0] == pytest.approx(want[0], rel=2e-5)
    ours, want = gradients_bf16
    assert ours[0] == pytest.approx(want[0], rel=2e-3)


@pytest.mark.parametrize("group", R.GROUPS)
def test_the_gradients_match_the_reference(gradients, group):
    ours, want = gradients
    assert group_errors(ours[1], want[1], want[1])[group] < 1e-4


@pytest.mark.parametrize("group", R.GROUPS)
def test_the_gradients_match_the_witness_under_bfloat16(gradients_bf16, group):
    ours, want = gradients_bf16
    assert group_errors(ours[1], want[1], want[1])[group] < 5e-2


# ------------------------------------------------------------ the chunked scan
def _scan_inputs(t, b=2, h=8, p=4, g=2, n=8, seed=0):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, t, h)) - 1.0),
            -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0, maxval=2.5)),
            jax.random.normal(keys[3], (b, t, g, n)),
            jax.random.normal(keys[4], (b, t, g, n)))


def _step_by_step(x, dt, a, b, c, reset_every=None):
    import jax
    import jax.numpy as jnp

    heads = jnp.arange(x.shape[2]) // (x.shape[2] // b.shape[2])
    return jax.vmap(lambda x, dt, b, c: R.recurrence(
        dt[:, :, None] * x, dt, a, b, c, heads, reset_every))(x, dt, b, c)


@pytest.mark.parametrize("t,chunk,runs", [
    (32, 8, 8),    # a multiple of the chunk
    (6, 8, 6),     # shorter than one chunk
    (36, 8, 6),    # the chunk clamped to a divisor of the length
    (32, 32, 32),  # one chunk: nothing is carried
])
def test_the_chunked_scan_equals_the_recurrence(t, chunk, runs):
    """Forward and backward, against the reference's time-step recurrence."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.parallel.ring import pick_block

    args = _scan_inputs(t)
    assert pick_block(t, chunk) == runs
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)

    def ours(*a):
        return seq_layers.ssd_scan(*a, chunk, jnp.float32)[0]

    y, chunks, absmax, _ = seq_layers.ssd_scan(*args, chunk, jnp.float32)
    np.testing.assert_allclose(y, _step_by_step(*args), atol=2e-5)
    assert float(chunks) == 2 * (t // runs)  # rows x chunks: the loop's length
    assert (float(absmax) > 0) == (t > runs)  # one chunk carries nothing
    got = jax.grad(lambda *a: (ours(*a) * weight).sum(), (0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (_step_by_step(*a) * weight).sum(),
                    (0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=2e-5)


def test_a_state_that_is_not_carried_is_seen(monkeypatch):
    """The planted fault of the benchmark, on the program's side: with the
    states dropped at every chunk boundary the scan is no longer the
    recurrence, and is the reference's recurrence with the same fault."""
    import jax.numpy as jnp

    args = _scan_inputs(32, seed=1)
    sound = seq_layers.ssd_scan(*args, 8, jnp.float32)[0]
    monkeypatch.setattr(seq_layers, "carried_states",
                        lambda own, decay: jnp.zeros_like(own))
    broken, _, absmax, _ = seq_layers.ssd_scan(*args, 8, jnp.float32)
    assert float(absmax) == 0.0
    assert float(jnp.abs(broken - sound).max()) > 1e-2
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(broken, _step_by_step(*args), atol=2e-5)
    np.testing.assert_allclose(broken, _step_by_step(*args, reset_every=8),
                               atol=2e-5)
    np.testing.assert_allclose(broken[:, :8], sound[:, :8], atol=2e-6)


def test_the_mixer_is_the_references_layer():
    """One mamba layer alone, padded history and all: the convolution sees
    zeros before the first event, heads read their group in blocks, the gate
    comes before the group norm."""
    import jax
    import jax.numpy as jnp

    blk = one_layer(seq_layers._mamba_leaves(1, CFG))
    blk["d_skip"] = blk["d_skip"] * 0.7  # a gain of 1 would hide a lost D
    h = jax.random.normal(jax.random.PRNGKey(5), (2, T, CFG.d_model))
    out, counters = seq_layers.mamba(blk, h, CFG)
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(lambda x, fault: R._mamba(blk, x, M, None, fault))
        for r in range(2):
            np.testing.assert_allclose(out[r], theirs(h[r], 0), atol=2e-5)
        for fault in ("conv_left_out", "head_group_mod", "norm_before_gate",
                      "norm_over_all_channels", "d_left_out", "a_log_for_a",
                      "dt_bias_ignored", "state_not_carried"):
            wrong = theirs(h[0], R.FAULTS.index(fault) + 1)
            assert float(jnp.abs(wrong - out[0]).max()) > 1e-3, fault
    assert float(counters["ssm_chunks"]) == 2 * (T // 8)


# ----------------------------------------------------------- the chip's share
def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the 16 expert shares' routed parts, with the
    router, the shared expert and everything replicated counted once, are
    the uncut 16-expert layer's result, the program's and the reference's."""
    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(CFG, experts_first=0, experts_held=16)
    m = dict(M, experts_first=0, experts_held=16)
    blk = one_layer({"ffn_norm": seq_layers.Leaf((1, 32), "ones"),
                     **seq_layers._moe_leaves(1, cfg),
                     "router_b": seq_layers.Leaf((1, 16), ("named", 0.02))})
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 32))
    with jax.default_matmul_precision("highest"):
        want, _load, ref_pairs = R._moe(blk, x[0], m, None, None)
    xn = seq_layers.rms_norm(x, blk["ffn_norm"], cfg.norm_eps)
    whole, counters = seq_layers.moe(blk, xn, cfg, None)
    # everything replicated, counted once: the router's choice and the shared
    # expert (a share that holds no routed weight computes just that)
    idx, gate, load = seq_layers.route(xn[0], blk["router_w"], blk["router_b"], cfg)
    np.testing.assert_array_equal(load, counters["load"])
    total = seq_layers.relu2_mlp(xn[0], blk["s_up"], blk["s_down"], jnp.float32)

    @jax.jit
    def share(first):
        part = {n: jax.lax.dynamic_slice_in_dim(blk[n], first, 1)
                for n in ("e_up", "e_down")}
        return seq_layers.routed_experts(part, xn[0], idx, gate, cfg, first, 1)

    pairs = 0.0
    for first in range(16):
        y, c = share(first)
        total, pairs = total + y, pairs + float(c["pairs"])
        assert float(c["dropped"]) == 0
        assert float(c["pairs"]) == float(load[first])
    total = total[None]
    np.testing.assert_allclose(total[0], want, atol=2e-6)
    np.testing.assert_allclose(whole[0], want, atol=2e-6)
    assert pairs == float(ref_pairs) == x.shape[1] * cfg.experts_per_token


def test_attention_has_no_position_encoding_and_folds_the_group():
    """Sixteen query heads a KV head at the cell's ratio: the layer equals
    the reference's dense masks, and shifting the history by a prefix of
    other events leaves the scores' weights where causality puts them (a
    rotary encoding would not: the planted fault reads otherwise)."""
    import jax

    cfg = dataclasses.replace(CFG, heads_full=32, kv_heads=2)
    m = dict(M, num_attention_heads=32)
    leaves = seq_layers._gqa_leaves(1, cfg, "full")
    assert "g_proj" not in leaves
    blk = one_layer(leaves)
    h = jax.random.normal(jax.random.PRNGKey(7), (1, T, cfg.d_model))
    out, _tiles = seq_layers.gqa(blk, h, cfg, None, "full")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            out[0], R._attention(blk, h[0], m, None, None), atol=2e-6)
        for fault in ("rope_applied", "kv_head_mod"):
            wrong = R._attention(blk, h[0], m, None, fault)
            assert float(np.abs(wrong - out[0]).max()) > 1e-4, fault


# ------------------------------------------- the routed experts' grouped matmul
@pytest.mark.parametrize("sizes", [
    (100, 0, 57, 130), (0, 0, 0, 0), (128, 128, 128, 128), (5, 300, 0, 1)])
def test_the_pallas_grouped_matmul_is_ragged_dot(sizes, monkeypatch):
    """Value and both gradients (interpret mode here; compiled for the chip
    in ``test_tpu_compile.py``), for groups that are empty, that end inside a
    row tile and that leave rows over; ``K`` and ``N`` no multiple of a tile."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(seq_layers, "GMM_TILES", dict.fromkeys(
        seq_layers.GMM_TILES, (128, 128, 128)))
    rng = np.random.default_rng(3)
    M, K, N = 512, 200, 136
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), K, N)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = (jnp.arange(M) < sizes.sum())[:, None]

    def loss(a, w, grouped):
        # as ``routed_experts`` calls it: rows past the groups selected away
        y = jnp.where(rows, grouped(jnp.where(rows, a, 0.0), w, sizes), 0.0)
        return jnp.sum(jnp.square(y)), y

    def pallas(a, w, sizes):
        return seq_layers.grouped_matmul(a, w, sizes, interpret=True)

    def xla(a, w, sizes):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    (_, y), (da, dw) = jax.jit(jax.value_and_grad(
        lambda a, w: loss(a, w, pallas), (0, 1), has_aux=True))(a, w)
    (_, y0), (da0, dw0) = jax.value_and_grad(
        lambda a, w: loss(a, w, xla), (0, 1), has_aux=True)(a, w)
    for got, want in ((y, y0), (da, da0), (dw, dw0)):
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(want).max() + 1))


def test_the_kernel_is_chosen_on_a_tpu_alone():
    gmm = dataclasses.replace(CFG, expert_matmul="gmm")
    assert seq_layers.experts_impl("tpu", gmm) == "gmm"
    assert seq_layers.experts_impl("cpu", gmm) == "ragged_dot"
    assert seq_layers.experts_impl("tpu", CFG) == "ragged_dot"


def test_an_expert_layer_is_the_same_through_either_grouped_matmul(monkeypatch):
    """An expert layer's output, counters and every gradient with the kernel
    in ``routed_experts`` (interpret mode) against XLA's, float32."""
    import functools

    import jax

    cfg = dataclasses.replace(CFG, expert_matmul="gmm")
    blk = one_layer({**seq_layers._moe_leaves(1, cfg),
                     "router_b": seq_layers.Leaf((1, 16), ("named", 0.02))})
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, 32))

    def layer(blk, x):
        y, counters = seq_layers.moe(blk, x, cfg, None)
        return (y * y).sum(), (counters["pairs"], counters["dropped"])

    want = jax.value_and_grad(layer, (0, 1), has_aux=True)(blk, x)
    monkeypatch.setattr(seq_layers, "experts_impl", lambda platform, c: "gmm")
    monkeypatch.setattr(seq_layers, "grouped_matmul", functools.partial(
        seq_layers.grouped_matmul, interpret=True))
    monkeypatch.setattr(seq_layers, "GMM_TILES", dict.fromkeys(
        seq_layers.GMM_TILES, (128, 128, 128)))
    got = jax.value_and_grad(layer, (0, 1), has_aux=True)(blk, x)
    assert float(want[0][1][0]) > 0 and float(got[0][1][1]) == 0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(w).max()))


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm"])
def test_a_planted_skew_costs_a_second_pass_not_a_pair(impl, monkeypatch):
    """Every token to the same three held experts of a quarter held: twice
    the rows the first pass stages, so a second runs; no pair dropped, the
    result the reference's, through XLA's grouped matmul and the kernel's
    oracle path (interpret mode)."""
    import functools

    import jax
    import jax.numpy as jnp

    blk = one_layer({"ffn_norm": seq_layers.Leaf((1, 32), "ones"),
                     **seq_layers._moe_leaves(1, CFG)})
    blk["router_b"] = jnp.zeros(16).at[jnp.array([4, 5, 6])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T, 32))
    if impl == "gmm":
        monkeypatch.setattr(seq_layers, "experts_impl", lambda platform, c: impl)
        monkeypatch.setattr(seq_layers, "grouped_matmul", functools.partial(
            seq_layers.grouped_matmul, interpret=True))
        monkeypatch.setattr(seq_layers, "GMM_TILES", dict.fromkeys(
            seq_layers.GMM_TILES, (128, 128, 128)))
    xn = seq_layers.rms_norm(x, blk["ffn_norm"], CFG.norm_eps)
    y, c = seq_layers.moe(blk, xn, CFG, None)
    n_pairs = 2 * T * CFG.experts_per_token
    assert seq_layers.pass_widths(n_pairs, 4, 16) == ((n_pairs // 2,),) * 2
    assert float(c["pairs"]) == n_pairs and float(c["dropped"]) == 0
    assert float(c["passes"]) == 2 and float(c["staged"]) == n_pairs
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([R._moe(blk, row, M, None, None)[0] for row in x])
    np.testing.assert_allclose(y, want, atol=2e-6)


# ------------------------------------------------------- what check_block refuses
@pytest.mark.parametrize("change,match", [
    (dict(mixer_pattern=("mamba", "ffn")), "mixer_pattern holds kinds"),
    (dict(n_layers=10), "names every layer"),
    (dict(residual_scale=0.0), "residual_scale and logit_scale are positive"),
    (dict(dense_layers=1), "no dense layers"),
    (dict(attention_kind="mla"), "attention_kind='gqa'"),
    (dict(heads_full=7), "multiple of"),
    (dict(ssm_groups=3), "multiple of ssm_groups"),
    (dict(expert_act="gelu"), "expert_act"),
    (dict(expert_matmul="dense"), "expert_matmul"),
    (dict(ffn_kind="relu", attention_kind="mha"), "needs ffn_kind='moe'"),
])
def test_a_block_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        seq_layers.check_block(dataclasses.replace(CFG, **change))


def test_a_seq_axis_is_refused_for_a_mamba_layer():
    """The recurrent state is not passed from shard to shard: a message that
    says so, before any device work; without a mamba layer the ring works."""
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, seq=2, model=2))
    with pytest.raises(ValueError, match="recurrent state.*shard"):
        train_seqrec(mesh, histories(), V - 1, CFG)
    with pytest.raises(ValueError, match="seq axis of 2"):
        seq_layers.check_block(CFG, 2)
    seq_layers.check_block(CFG, 1)
    cfg = dataclasses.replace(CFG, n_layers=2, mixer_pattern=("attn", "moe"), steps=1)
    single = train_seqrec(None, histories(), V - 1, cfg)
    ringed = train_seqrec(mesh, histories(), V - 1, cfg)
    np.testing.assert_allclose(ringed.trace["l_main"], single.trace["l_main"],
                               rtol=1e-5)
