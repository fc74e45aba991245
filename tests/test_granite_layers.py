"""The layers of the block without experts one by one, at a small size on the
CPU against ``benchmarks/granite_reference.py``: the chunked scan with the
heads of one group mapped a block at a time against the time-step recurrence
(and against the scan as it stood while a turn was a group), the ``mlp`` mixer,
a whole (mamba, mlp) and (attn, mlp) layer with all four multipliers set, the
tied table's gradient, the vocabulary's two halves under a ``model`` axis, and
what ``check_block`` now admits. The trained block is in
``test_granite_block.py``."""

import dataclasses

import numpy as np
import pytest

from granite_small import (CFG, M, R, T, V, flat, group_errors, histories,
                           one_layer, program_loss)

from pio_tpu.models import seq_layers, seqrec


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
    return _gradients(CFG, None)


@pytest.fixture(scope="module")
def gradients_bf16():
    """Under the published bfloat16 policy, against the reference with
    bfloat16 operands (its witness)."""
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


# ------------------------------------------------- the scan's blocks of heads
def _scan_inputs(t, b=2, h=16, p=4, g=1, n=8, seed=0):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, t, h)) - 1.0),
            -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0, maxval=2.5)),
            jax.random.normal(keys[3], (b, t, g, n)),
            jax.random.normal(keys[4], (b, t, g, n)))


def _step_by_step(x, dt, a, b, c):
    import jax
    import jax.numpy as jnp

    heads = jnp.arange(x.shape[2]) // (x.shape[2] // b.shape[2])
    return jax.vmap(lambda x, dt, b, c: R.recurrence(
        dt[:, :, None] * x, dt, a, b, c, heads))(x, dt, b, c)


def _scan_while_a_turn_was_a_group(x, dt, a, b, c, chunk, cd):
    """``ssd_scan`` as it stood before the heads were blocked (PR 35): the
    groups mapped one after another, ``C B^T`` inside the group's turn."""
    import jax
    import jax.numpy as jnp

    B, T, H, P = x.shape
    G, N = b.shape[2:]
    R_, Q = H // G, chunk
    C = T // Q
    f32 = jnp.float32
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    @jax.checkpoint
    def group(args):
        xg, dtg, ag, bg, cg = args
        cum = jnp.cumsum(dtg * ag, axis=2)
        dtx32 = dtg[..., None] * xg
        dtx = dtx32.astype(cd)
        bg, cg = bg.astype(cd), cg.astype(cd)
        cb = jnp.einsum("bcqn,bcsn->bcqs", cg, bg, preferred_element_type=f32)
        by_head = cum.transpose(0, 1, 3, 2)
        span = by_head[..., :, None] - by_head[..., None, :]
        weights = jnp.exp(jnp.where(seen, span, -jnp.inf)) * cb[:, :, None]
        y = jnp.einsum("bcrqs,bcsrp->bcqrp", weights.astype(cd), dtx,
                       preferred_element_type=f32)
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        own = jnp.einsum("bcsrp,bcsn->bcrpn",
                         (to_end[..., None] * dtx32).astype(cd), bg,
                         preferred_element_type=f32)
        entering = jnp.swapaxes(seq_layers.carried_states(
            jnp.swapaxes(own, 0, 1),
            jnp.swapaxes(jnp.exp(cum[:, :, -1]), 0, 1)), 0, 1)
        return y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bcqn,bcrpn->bcqrp", cg, entering.astype(cd),
            preferred_element_type=f32)

    y = jax.lax.map(group, (
        jnp.moveaxis(x.reshape(B, C, Q, G, R_, P), 3, 0),
        jnp.moveaxis(dt.reshape(B, C, Q, G, R_), 3, 0), a.reshape(G, R_),
        jnp.moveaxis(b.reshape(B, C, Q, G, N), 3, 0),
        jnp.moveaxis(c.reshape(B, C, Q, G, N), 3, 0)))
    return jnp.moveaxis(y, 0, 3).reshape(B, T, H, P)


@pytest.mark.parametrize("groups,head_block,turns", [
    (1, 1, 16), (1, 2, 8), (1, 16, 1),  # one group: a head, two, all heads
    (1, 0, 1),    # 0 = the group
    (1, 5, 4),    # clamped to a divisor of the group's heads: 4 a turn
    (2, 4, 4),    # two groups of 8 heads, two turns each
    (2, 8, 2),    # a block is a group: the map over groups
])
def test_the_blocked_scan_equals_the_recurrence(groups, head_block, turns):
    """Forward and all five gradients against the reference's time-step
    recurrence, whatever the block: ``C B^T`` computed once a group and read
    by every turn sums the heads' cotangents to the same ``dB`` and ``dC``."""
    import jax
    import jax.numpy as jnp

    args = _scan_inputs(32, g=groups)
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)

    def ours(*a):
        return seq_layers.ssd_scan(*a, 8, jnp.float32, head_block)[0]

    y, chunks, absmax, ran = seq_layers.ssd_scan(
        *args, 8, jnp.float32, head_block)
    assert float(ran) == turns  # the map's own length
    assert float(chunks) == 2 * 4 and float(absmax) > 0
    np.testing.assert_allclose(y, _step_by_step(*args), atol=2e-5)
    got = jax.grad(lambda *a: (ours(*a) * weight).sum(), (0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (_step_by_step(*a) * weight).sum(),
                    (0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=2e-5)


@pytest.mark.parametrize("head_block", [0, 8, 32])
@pytest.mark.parametrize("cd", ["float32", "bfloat16"])
def test_a_block_that_is_a_group_is_the_scan_of_before_to_the_bit(head_block, cd):
    """Where a group has 8 heads (the Nemotron cell's shape) every setting of
    the block at or above 8 leaves the map over groups as it was: the same
    numbers bit for bit, forward and backward, under either policy."""
    import jax
    import jax.numpy as jnp

    args = _scan_inputs(32, g=2, seed=2)
    cd = jnp.dtype(cd)
    weight = jnp.sin(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)

    def loss(scan):
        return lambda *a: (scan(*a) * weight).sum()

    def now(*a):
        return seq_layers.ssd_scan(*a, 8, cd, head_block)[0]

    def before(*a):
        return _scan_while_a_turn_was_a_group(*a, 8, cd)

    np.testing.assert_array_equal(jax.jit(now)(*args), jax.jit(before)(*args))
    got = jax.jit(jax.grad(loss(now), (0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(loss(before), (0, 1, 2, 3, 4)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------------------------ the mixers
def test_the_mlp_mixer_is_the_references_layer():
    """``h + s_r * swiglu(norm(h))`` with its own norm, against the reference's
    ``_mlp`` under the residual multiplier; exchanging gate and up is seen."""
    import jax

    blk = one_layer({k.partition("/")[2]: leaf for k, leaf in
                     seq_layers.describe_params(V, CFG).items()
                     if k.startswith("mlp/")})
    assert set(blk) == {"norm", "w_gate", "w_up", "w_down"}
    blk["norm"] = blk["norm"] * 1.3  # a gain of 1 would hide a lost norm
    h = jax.random.normal(jax.random.PRNGKey(5), (2, T, CFG.d_model))
    out, counters = seq_layers.mixer_layer(blk, h, CFG, None, None, "mlp")
    assert counters == {}
    with jax.default_matmul_precision("highest"):
        for r in range(2):
            want = h[r] + 0.22 * R._mlp(blk, h[r], M, None, None)
            np.testing.assert_allclose(out[r], want, atol=2e-6)
        wrong = h[0] + 0.22 * R._mlp(blk, h[0], M, None, "gate_up_exchanged")
    assert float(np.abs(wrong - out[0]).max()) > 1e-4


@pytest.mark.parametrize("kind,theirs,faults", [
    ("mamba", "_mamba", ("state_not_carried", "norm_per_8_heads",
                         "conv_taps_reversed")),
    ("attn", "_attention", ("scale_rsqrt_d", "rope_applied", "kv_head_mod")),
])
def test_a_whole_layer_is_the_references_with_every_multiplier_set(
        kind, theirs, faults):
    """A mixer and its MLP, each with its own norm and residual times 0.22
    (the attention's scores times 1/64, not 1/2): two entries of the pattern
    are one published layer. Each planted fault of the mixer's kind moves it."""
    import jax

    desc = seq_layers.describe_params(V, CFG)
    mixer = one_layer({k.partition("/")[2]: leaf for k, leaf in desc.items()
                       if k.startswith(kind + "/")})
    mlp = one_layer({k.partition("/")[2]: leaf for k, leaf in desc.items()
                     if k.startswith("mlp/")}, seed=6)
    if kind == "mamba":
        mixer["d_skip"] = mixer["d_skip"] * 0.7
    else:  # scores of weights of std 0.02 are all but 0 whatever scales them
        mixer["q_proj"], mixer["k_proj"] = mixer["q_proj"] * 8, mixer["k_proj"] * 8
    h = jax.random.normal(jax.random.PRNGKey(7), (1, T, CFG.d_model)) * 3.0
    mid, _ = seq_layers.mixer_layer(mixer, h, CFG, None, None, kind)
    out, _ = seq_layers.mixer_layer(mlp, mid, CFG, None, None, "mlp")
    with jax.default_matmul_precision("highest"):
        theirs = getattr(R, theirs)
        want = h[0] + 0.22 * theirs(mixer, h[0], M, None, None)
        np.testing.assert_allclose(mid[0], want, atol=2e-5)
        want = want + 0.22 * R._mlp(mlp, want, M, None, None)
        np.testing.assert_allclose(out[0], want, atol=2e-5)
        for fault in faults:
            wrong = h[0] + 0.22 * theirs(mixer, h[0], M, None, fault)
            assert float(np.abs(wrong - mid[0]).max()) > 1e-4, fault
    # with the multipliers left at their defaults it is another layer
    plain = dataclasses.replace(CFG, residual_scale=1.0, attn_scale=0.0)
    other, _ = seq_layers.mixer_layer(mixer, h, plain, None, None, kind)
    assert float(np.abs(other - mid).max()) > 1e-3


def test_the_four_multipliers_default_to_a_model_without_them():
    """1.0 (0.0 for the scores' scale) leaves every path as it was: the same
    loss to the bit as a config that never names them, and another loss for
    each one set."""
    import jax

    base = dataclasses.replace(CFG, embed_scale=1.0, residual_scale=1.0,
                               attn_scale=0.0, logit_scale=1.0)
    params = seqrec.init_params(V, base)
    rows = histories(2, seed=3)
    plain = float(jax.jit(lambda p: program_loss(p, rows, base))(params))
    assert plain == float(jax.jit(lambda p: program_loss(
        p, rows, dataclasses.replace(base, attn_scale=4 ** -0.5)))(params))
    for change in (dict(embed_scale=12.0), dict(residual_scale=0.22),
                   dict(attn_scale=0.015625), dict(logit_scale=0.125)):
        cfg = dataclasses.replace(base, **change)
        assert float(jax.jit(lambda p: program_loss(p, rows, cfg))(
            params)) != plain, change


# --------------------------------------------------------------- the tied table
def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    """One parameter read by the lookup and by the logits: its gradient is
    the reference's gradient as an embedding plus its gradient as a head,
    taken apart there by giving the two uses a table each."""
    import jax
    import jax.numpy as jnp

    rows = histories(2, seed=4)
    params = seqrec.init_params(V, CFG)
    assert "head" not in params and "head" not in seq_layers.describe_params(V, CFG)
    ours = jax.jit(jax.grad(lambda p: program_loss(p, rows)))(params)["emb"]

    def two_tables(lookup, head):
        def row(ids):
            t1 = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
            m1 = ((t1 > 0) & (ids > 0)).astype(jnp.float32)
            h = R.trunk(dict(ref, emb=lookup), ids, M)
            return R._ce_sum(h, ref["lnf_g"], head, t1, m1, M, None, None)

        return sum(row(ids) for ids in jnp.asarray(rows)) / (2 * (T - 1))

    ref = R.init_params(M, CFG.seed)
    with jax.default_matmul_precision("highest"):
        as_lookup, as_head = jax.jit(jax.grad(two_tables, (0, 1)))(
            ref["emb"], ref["emb"])
        left_out = jax.jit(jax.grad(lambda p: R.batch_loss(
            p, jnp.asarray(rows), M, None, "head_not_in_table_gradient")[0]))(
                ref)["emb"]
    assert float(jnp.abs(as_lookup).max()) > 0 and float(jnp.abs(as_head).max()) > 0
    np.testing.assert_allclose(ours, as_lookup + as_head, atol=2e-7, rtol=2e-5)
    np.testing.assert_allclose(left_out, as_lookup, atol=2e-7, rtol=2e-5)
    assert float(jnp.abs(ours - as_lookup).max()) > 1e-4


def test_a_tied_table_is_drawn_as_a_head_and_served_from():
    """Rows of std 0.02, not the untied cells' 1.0; serving reads the same
    table, times ``logit_scale``."""
    params = seqrec.init_params(V, CFG)
    assert np.std(np.asarray(params["emb"])) == pytest.approx(0.02, rel=0.1)
    untied = dataclasses.replace(CFG, tied_head=False)
    both = seqrec.init_params(V, untied)
    assert np.std(np.asarray(both["emb"])) == pytest.approx(1.0, rel=0.1)
    assert both["head"].shape == both["emb"].shape
    assert seq_layers.groups_of(untied)[:2] == ("embedding", "head")


def test_the_two_halves_of_the_vocabulary_add_up_to_the_uncut_model():
    """The guide's share test, for the tied table under a ``model`` axis of
    2: the same row shard serves the lookup and the logits, and the loss and
    the table's gradient over the two halves are the uncut reference's."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rows = histories(2, seed=5)
    params = seqrec.init_params(V, CFG)
    specs = seqrec.param_specs(CFG)
    assert specs["emb"] == P("model", None) and "head" not in specs
    assert all(spec == P() for k, v in specs.items() if k != "emb"
               for spec in (v.values() if isinstance(v, dict) else [v]))
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))

    def local(p, rows):
        assert p["emb"].shape == (V // 2, CFG.d_model)  # a half here
        return program_loss(p, rows, m_axis="model")

    def loss(p):
        return shard_map(local, mesh=mesh, in_specs=(specs, P()), out_specs=P(),
                         check_vma=False)(p, jnp.asarray(rows))

    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: R.batch_loss(p, jnp.asarray(rows), M)[0]))(
                R.init_params(M, CFG.seed))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    for half in (slice(0, V // 2), slice(V // 2, V)):
        np.testing.assert_allclose(got["emb"][half], want["emb"][half],
                                   atol=2e-7, rtol=2e-5)
    assert group_errors(flat(got), jax.device_get(want),
                        jax.device_get(want))["embedding"] < 1e-5


# ------------------------------------------------------- what check_block admits
@pytest.mark.parametrize("pattern", [
    ("mamba", "mlp"), ("attn", "mlp", "mamba", "mlp"), ("mlp",),
    ("mamba", "attn", "mamba"), ("mamba", "moe", "mlp"),
])
def test_a_pattern_needs_no_expert_layer(pattern):
    cfg = dataclasses.replace(CFG, mixer_pattern=pattern, n_layers=len(pattern))
    seq_layers.check_block(cfg)
    desc = seq_layers.describe_params(V, cfg)
    assert {k.partition("/")[0] for k in desc if "/" in k} == set(pattern)
    assert seq_layers.has_experts(cfg) == ("moe" in pattern)
    assert seq_layers.experts_impl("tpu", cfg) == (
        "ragged_dot" if "moe" in pattern else "none")
    groups = seq_layers.groups_of(cfg)
    if "mlp" not in pattern:  # the accepted cells' columns, by position
        assert groups == seq_layers.MIXER_GROUPS
    else:
        assert ("dense_mlp" in groups and "head" not in groups
                and ("router" in groups) == ("moe" in pattern)
                and ("ssm_scan" in groups) == ("mamba" in pattern))
    assert {seq_layers.group_of(path, cfg) for path in desc} <= set(groups)


@pytest.mark.parametrize("change,match", [
    (dict(mixer_pattern=("mamba", "ffn"), n_layers=2), "mixer_pattern holds kinds"),
    (dict(embed_scale=0.0), "embed_scale, residual_scale and logit_scale"),
    (dict(logit_scale=-1.0), "positive"),
    (dict(attn_scale=-0.5), "attn_scale positive or 0"),
    (dict(ssm_groups=3), "multiple of ssm_groups"),
    (dict(dense_layers=1), "no dense layers"),
])
def test_a_block_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        seq_layers.check_block(dataclasses.replace(CFG, **change))
