"""The gqa/moe block of the sequence template (Laguna-S-2.1's: window and
full layers mixed, grouped queries, a per-head gate, a softmax router) at a
small size on the CPU, against two plain references: the test-side forward
pass (``tests/laguna_forward.py``) and the benchmark's training reference
(``benchmarks/laguna_reference.py``).

Tolerances: both sides compute in float32 (``compute_dtype="float32"``); what
is left is the order of the additions (the blocked online softmax against a
dense one, grouped matmuls against a loop over experts, XLA's fusions), a few
float32 ulps a layer: 2e-5 relative on losses, logits and gradient norms, 1e-4
on a group's gradient as a whole, 1e-3 on three Adam steps (Adam divides by
the root of a squared gradient, which turns 1e-5 of a small entry into 1e-4
of its step).
"""

import dataclasses
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
for p in (BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import laguna_forward as F  # noqa: E402
import laguna_reference as R  # noqa: E402

from pio_tpu.models import seq_layers, seqrec  # noqa: E402
from pio_tpu.models.seqrec import SeqRecConfig, train_seqrec  # noqa: E402
from pio_tpu.parallel import ring  # noqa: E402

V, T = 48, 32
PATTERN = ("full", "window", "window", "window")
YARN = (4.0, 16, 4.0, 1.0, 1.2)  # factor, original length, fast, slow, factor on cos/sin
CFG = SeqRecConfig(
    attention_kind="gqa", ffn_kind="moe", router_kind="softmax", d_model=32,
    n_layers=5, dense_layers=1, ffn=64, layer_pattern=PATTERN, head_dim=8,
    kv_heads=2, heads_full=4, heads_window=6, window=8, rope_theta=5e5,
    window_rope_theta=1e4, rotary_dim=4, yarn_factor=YARN[0],
    yarn_original_len=YARN[1], yarn_beta_fast=YARN[2], yarn_beta_slow=YARN[3],
    yarn_attention_factor=YARN[4], norm_eps=1e-6, n_experts=16,
    experts_first=4, experts_held=4, experts_per_token=3, expert_ffn=24,
    routed_scale=2.5, max_len=T, batch_size=2, steps=3, learning_rate=1e-3,
    compute_dtype="float32", stream="off", seed=11,
)
#: the same model as the benchmark's reference reads it
M = dict(
    vocab_size=V, hidden_size=32, intermediate_size=64, num_hidden_layers=5,
    dense_layers=1, layer_pattern=PATTERN, head_dim=8, kv_heads=2,
    heads_full=4, heads_window=6, sliding_window=8, rms_norm_eps=1e-6,
    rope_theta_window=1e4, rope_theta_full=5e5, rotary_dim_full=4,
    yarn_factor=YARN[0], yarn_original_len=YARN[1], yarn_beta_fast=YARN[2],
    yarn_beta_slow=YARN[3], yarn_attention_factor=YARN[4], router_width=16,
    experts_first=4, experts_held=4, num_experts_per_tok=3,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    shared_experts=1, moe_routed_scaling_factor=2.5,
    init_std=seq_layers.INIT_STD, embed_init_std=seq_layers.EMBED_INIT_STD,
    learning_rate=1e-3,
)


def forward_model(cfg):
    """The same model as the test-side forward pass reads it."""
    return dict(
        D=cfg.d_model, d=cfg.head_dim, kv_heads=cfg.kv_heads,
        heads={"full": cfg.heads_full, "window": cfg.heads_window},
        kinds=tuple(seq_layers.layer_kind(cfg, i) for i in range(cfg.n_layers)),
        dense_layers=cfg.dense_layers, window=cfg.window, eps=cfg.norm_eps,
        theta={"full": cfg.rope_theta, "window": cfg.window_rope_theta},
        rotary_full=cfg.rotary_dim, yarn=YARN, router_width=cfg.n_experts,
        experts_first=cfg.experts_first, top_k=cfg.experts_per_token,
        scale=cfg.routed_scale)


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


def program_loss(params, rows, cfg=CFG):
    import jax.numpy as jnp

    rows = jnp.asarray(rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    m1 = ((t1 > 0) & (rows > 0)).astype(jnp.float32)
    sums, counters = seqrec._latent_loss_sums(
        params, (rows, t1, m1, t1, m1), cfg, None, None)
    return seqrec._latent_loss(sums, counters, cfg)[0]


@pytest.fixture(scope="module")
def trained():
    """Three Adam steps of the program and of the benchmark's reference."""
    seqs = histories()
    model = train_seqrec(None, seqs, V - 1, CFG)
    ref = R.train(M, seqs, seed=CFG.seed, steps=3, batch=2)
    return seqs, model, ref


def test_the_two_initialisers_agree_to_the_bit():
    ours = flat(seqrec.init_params(V, CFG))
    theirs = R.init_params(M, CFG.seed)
    assert set(ours) == set(theirs)
    for path, value in theirs.items():
        assert np.array_equal(ours[path], np.asarray(value)), path
    assert "window/router_b" not in ours  # a softmax router has no bias


def test_the_stacks_have_unlike_shapes_written_once():
    desc = seq_layers.describe_params(V, CFG)
    assert desc["window/q_proj"].shape == (3, 32, 6 * 8)
    assert desc["full/q_proj"].shape == (1, 32, 4 * 8)
    assert desc["dense/q_proj"].shape == (1, 32, 4 * 8)  # layer 0 is full
    assert desc["window/k_proj"].shape == (3, 32, 2 * 8)
    assert desc["window/g_proj"].shape == (3, 32, 6)
    assert {k: v.shape for k, v in flat(seqrec.init_params(V, CFG)).items()
            } == {k: leaf.shape for k, leaf in desc.items()}
    assert set(flat(seqrec.param_specs(CFG))) == set(desc)


def test_the_two_groupings_of_the_parameters_agree():
    assert seq_layers.groups_of(CFG) == R.GROUPS
    for path in seq_layers.describe_params(V, CFG):
        assert seq_layers.group_of(path, CFG) == R.group_of(path, M), path
    assert R.group_of("dense/q_proj", M) == "attn_full"
    assert R.group_of("window/g_proj", M) == "gate"


def test_the_cells_file_maps_onto_the_programs_fields():
    """The configuration's key map names fields ``SeqRecParams`` has, and the
    reference reads the file as the issue cut it."""
    from pio_tpu.templates.sequence import SeqRecParams

    with open(os.path.join(BENCH, "configs", "laguna-s21-ep32.json")) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(SeqRecParams)}
    m = R.model(config)
    assert set(config["harness"]["param_of"]) <= set(m)
    assert set(config["harness"]["param_of"].values()) <= fields
    assert set(config["harness"]["params"]) <= fields
    assert (m["heads_full"], m["heads_window"], m["kv_heads"]) == (24, 36, 4)
    assert m["layer_pattern"] == PATTERN and m["dense_layers"] == 1
    assert (config["init"]["init_std"], config["init"]["embed_init_std"]) == (
        seq_layers.INIT_STD, seq_layers.EMBED_INIT_STD)
    total = sum(int(np.prod(s)) for s in R.shapes(m).values())
    assert total == config["deployment"]["parameters_here"] == 672125952


@pytest.mark.parametrize("key", ["l_main", "pairs", "grad_norm"])
def test_the_per_step_trace_matches_the_reference(trained, key):
    _seqs, model, ref = trained
    np.testing.assert_allclose(model.trace[key], ref[key], rtol=2e-5)
    assert model.trace["dropped"].sum() == 0
    assert "bias_max" not in model.trace and "l_mtp" not in model.trace


@pytest.mark.parametrize("group", R.GROUPS)
def test_three_adam_steps_match_the_reference(trained, group):
    _seqs, model, ref = trained
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    assert group_errors(flat(model.params), ref["final"], update)[group] < 1e-3


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of one batch: the program's, the test-side
    reference's and the benchmark reference's, from the same weights."""
    import jax
    import jax.numpy as jnp

    rows = histories(2, seed=3)
    params = seqrec.init_params(V, CFG)
    ours = jax.value_and_grad(program_loss)(params, rows)
    test_side = jax.value_and_grad(F.loss)(
        {k: jnp.asarray(v) for k, v in flat(params).items()}, rows,
        forward_model(CFG))
    bench = jax.value_and_grad(
        lambda p: R.batch_loss(p, jnp.asarray(rows), M)[0])(
            R.init_params(M, CFG.seed))
    return ((float(ours[0]), flat(ours[1])),
            (float(test_side[0]), jax.device_get(test_side[1])),
            (float(bench[0]), jax.device_get(bench[1])))


def test_the_loss_matches_both_references(gradients):
    ours, test_side, bench = gradients
    assert ours[0] == pytest.approx(test_side[0], rel=2e-5)
    assert bench[0] == pytest.approx(test_side[0], rel=2e-5)


@pytest.mark.parametrize("group", R.GROUPS)
def test_the_gradients_match_the_test_side_reference(gradients, group):
    ours, test_side, bench = gradients
    assert group_errors(ours[1], test_side[1], test_side[1])[group] < 1e-4
    # and the benchmark's reference is held to the same equations
    assert group_errors(bench[1], test_side[1], test_side[1])[group] < 1e-4


def test_forward_logits_match_the_test_side_reference():
    import jax.numpy as jnp

    rows = histories(2, seed=4)
    params = seqrec.init_params(V, CFG)
    h, _ = seqrec._latent_trunk(params, jnp.asarray(rows), CFG, None, None)
    ours = seq_layers.mm(
        seq_layers.rms_norm(h, params["lnf_g"], CFG.norm_eps),
        params["head"].T, jnp.float32)
    weights = {k: jnp.asarray(v) for k, v in flat(params).items()}
    for r in range(2):
        want = F.logits(weights, jnp.asarray(rows[r]), forward_model(CFG))
        np.testing.assert_allclose(ours[r], want, atol=2e-5)


def test_serving_scores_are_the_references_last_logits(trained):
    import jax.numpy as jnp

    seqs, model, ref = trained
    scores = model.next_item_scores(seqs[:3])
    for r in range(3):
        want = R.next_item_logits(ref["final"], seqs[r], M)
        np.testing.assert_allclose(scores[r], want, atol=2e-5)
    # a padded history is scored from its last real position, and the
    # test-side forward pass says the same of the program's own weights
    short = seqs[:1].copy()
    short[0, 20:] = 0
    weights = {k: jnp.asarray(v) for k, v in flat(model.params).items()}
    np.testing.assert_allclose(
        model.next_item_scores(short)[0],
        F.logits(weights, jnp.asarray(short[0, :20]), forward_model(CFG))[-1],
        atol=2e-5)


# ----------------------------------------------------------- the chip's share
def _uncut_layer(kind, seed=5, n_tokens=48):
    """One expert layer of an uncut small model (4 KV heads, 16 experts),
    the residual stream before it, and the model both sides read."""
    import jax

    cfg = dataclasses.replace(CFG, kv_heads=4, heads_full=8, heads_window=12,
                              experts_first=0, experts_held=16)
    desc = {**seq_layers._gqa_leaves(1, cfg, kind), **seq_layers._moe_leaves(1, cfg)}
    blk = {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in desc.items()}, seed)["b"].items()}
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, n_tokens, cfg.d_model))
    return cfg, blk, h


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_two_head_shares_add_up_to_the_uncut_attention(kind):
    """Attention divided in pairs by KV head: each share holds 2 of the 4 KV
    heads with their query heads, gate columns and rows of ``W_o``; what the
    two shares add to the residual stream sums to the uncut layer's."""
    cfg, blk, h = _uncut_layer(kind)
    H, d = seq_layers.heads_of(cfg, kind), cfg.head_dim
    want = F.attention(blk, h[0], forward_model(cfg), kind)
    half = dataclasses.replace(cfg, kv_heads=2, heads_full=4, heads_window=6)
    total = 0.0
    for s in range(2):
        cols = slice(s * H // 2 * d, (s + 1) * H // 2 * d)
        kv = slice(s * 2 * d, (s + 1) * 2 * d)
        part = dict(blk, q_proj=blk["q_proj"][:, cols], k_proj=blk["k_proj"][:, kv],
                    v_proj=blk["v_proj"][:, kv],
                    g_proj=blk["g_proj"][:, s * H // 2:(s + 1) * H // 2],
                    o_proj=blk["o_proj"][cols])
        out, _tiles = seq_layers.gqa(part, h, half, None, kind)
        np.testing.assert_allclose(
            out[0], F.attention(part, h[0], forward_model(half), kind), atol=2e-6)
        total = total + out[0]
    np.testing.assert_allclose(total, want, atol=5e-6)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the four expert shares' parts, with the router
    and the shared expert counted once, are the uncut 16-expert layer's
    result, the program's and the test-side reference's alike."""
    import jax.numpy as jnp

    cfg, blk, x = _uncut_layer("window")
    want, ref_pairs = F.moe(blk, x[0], forward_model(cfg))
    zero_shared = dict(blk, s_down=jnp.zeros_like(blk["s_down"]))
    whole, counters = seq_layers.moe(blk, x, cfg, None)
    shared = whole - seq_layers.moe(zero_shared, x, cfg, None)[0]
    total, pairs = shared, 0.0
    for first in range(0, 16, 4):
        part = dict(zero_shared, **{
            n: blk[n][first:first + 4] for n in ("e_gate", "e_up", "e_down")})
        y, c = seq_layers.moe(part, x, dataclasses.replace(
            cfg, experts_first=first, experts_held=4), None)
        total, pairs = total + y, pairs + float(c["pairs"])
        np.testing.assert_array_equal(c["load"], counters["load"])
    np.testing.assert_allclose(total[0], want, atol=2e-6)
    np.testing.assert_allclose(whole[0], want, atol=2e-6)
    assert pairs == float(ref_pairs) == x.shape[1] * cfg.experts_per_token


def test_the_dropped_counter_reads_a_planted_dropped_expert(monkeypatch):
    cfg, blk, x = _uncut_layer("full")
    part = dict(blk, **{n: blk[n][4:8] for n in ("e_gate", "e_up", "e_down")})
    cfg = dataclasses.replace(cfg, experts_first=4, experts_held=4)
    sound, c = seq_layers.moe(part, x, cfg, None)
    assert float(c["dropped"]) == 0 and float(c["load"][7]) > 0
    plan = seq_layers.pass_plan
    monkeypatch.setattr(seq_layers, "pass_plan",
                        lambda *a: plan(*a).at[:, -1].set(0))
    y, dropped = seq_layers.moe(part, x, cfg, None)
    assert float(dropped["dropped"]) == float(c["load"][7])
    assert not np.allclose(y, sound, atol=1e-6)


# ------------------------------------------------------------ blocked attention
def _qkv(t, h=6, hk=2, d=8, b=2, seed=0):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, t, h, d)),
            jax.random.normal(keys[1], (b, t, hk, d)),
            jax.random.normal(keys[2], (b, t, hk, d)))


def _dense_attention(q, k, v, window):
    import jax
    import jax.numpy as jnp

    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    t = jnp.arange(q.shape[1])
    seen = t[:, None] >= t[None, :]
    if window:
        seen = seen & (t[:, None] - t[None, :] < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("t,block", [(48, 8), (48, 16), (40, 16), (36, 8)])
@pytest.mark.parametrize("window", [5, 8, 16, 100])
def test_a_window_equals_the_dense_mask_forward_and_backward(t, block, window):
    """At lengths that are and are not multiples of the block (the tile edge
    is then the largest divisor under it), windows under, at and over it."""
    import jax
    import jax.numpy as jnp

    q, k, v = _qkv(t)
    weight = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def ours(q, k, v):
        return ring.ring_attention(q, k, v, axis=None, block=block, window=window)

    np.testing.assert_allclose(ours(q, k, v), _dense_attention(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: (ours(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense_attention(*a, window) * weight).sum(),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("window,want", [(0, 21), (8, 11), (5, 11), (9, 11), (10, 15), (16, 15)])
def test_the_tiles_outside_the_window_are_skipped_not_masked(window, want, monkeypatch):
    """Every score tile that runs is counted, forward and in the recomputing
    backward, at 48 positions in blocks of 8: the causal 21, and 11 under a
    window of 8 (two a query block, one for the first). The program's own
    counter, summed from those loops' bounds, reads the same."""
    import jax

    ran = []
    scores = ring._scores

    def counted(*a, **kw):
        jax.debug.callback(lambda: ran.append(1))
        return scores(*a, **kw)

    monkeypatch.setattr(ring, "_scores", counted)
    q, k, v = _qkv(48, b=1)

    def ours(q, k, v):
        return ring.ring_attention(q, k, v, axis=None, block=8, window=window).sum()

    jax.block_until_ready(ours(q, k, v))
    jax.effects_barrier()
    assert len(ran) == want
    ran.clear()
    jax.block_until_ready(jax.grad(ours)(q, k, v))
    jax.effects_barrier()
    assert len(ran) == 2 * want  # the forward pass, then each tile again
    _, tiles = ring.ring_attention(q, k, v, axis=None, block=8, window=window,
                                   with_tiles=True)
    assert [int(t) for t in tiles] == [want, 21]


def test_the_tile_counter_reads_the_loops_own_bounds(monkeypatch):
    """The counter is no second computation of the bounds: the day the skip
    becomes a mask (the loops start at key block 0 again and ``_scores``
    masks what lies before the window) it reads visited == causal."""
    q, k, v = _qkv(48, b=1)
    want = ring.ring_attention(q, k, v, axis=None, block=8, window=8)
    monkeypatch.setattr(ring, "first_key_block", lambda *a: 0)
    masked, tiles = ring.ring_attention(q, k, v, axis=None, block=8, window=8,
                                        with_tiles=True)
    np.testing.assert_allclose(masked, want, atol=1e-6)
    assert [int(t) for t in tiles] == [21, 21]


def test_the_cells_window_visits_63_of_528_tiles():
    """Counted by the loops at the cell's length, on one narrow head."""
    import jax.numpy as jnp

    q = jnp.zeros((1, 16384, 1, 8), jnp.float32)
    _, tiles = ring.ring_attention(q, q, q, axis=None, block=512, window=512,
                                   with_tiles=True)
    assert [int(t) for t in tiles] == [63, 528]
    i = np.arange(32)
    first = ring.first_key_block(i, 0, 0, 512, 512, 32, 512)
    assert list(np.asarray(first)) == [0] + list(range(31))


def test_grouped_queries_equal_keys_and_values_repeated():
    """The group's query heads ride the query rows of one tile: the same
    numbers as ``k`` and ``v`` repeated a query head, which no path does."""
    import jax.numpy as jnp

    q, k, v = _qkv(48)
    grouped = ring.ring_attention(q, k, v, axis=None, block=16, window=8)
    repeated = ring.ring_attention(
        q, jnp.repeat(k, 3, axis=2), jnp.repeat(v, 3, axis=2), axis=None,
        block=16, window=8)
    np.testing.assert_allclose(grouped, repeated, atol=1e-6)
    folded = ring.fold_groups(q, 16, 3)  # and unfolding is its inverse
    assert folded.shape == (2, 2, 48 * 3, 8)
    assert jnp.array_equal(ring.unfold_groups(folded, 16, 3), q)


def test_yarns_table_is_the_closed_form():
    """At the published numbers ``low`` is 9 and ``high`` 18: the nine
    fastest frequencies are kept, those from the 18th are divided by 128,
    the ones between are blended; three writings of it agree."""
    args = (500000.0, 64, 128.0, 8192, 32.0, 1.0)
    table = seq_layers.yarn_inv_freq(*args)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(table[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(table[18:], plain[18:] / 128, rtol=1e-6)
    ramp = 4 / 9
    assert table[13] == pytest.approx(plain[13] * (1 - ramp) + plain[13] / 128 * ramp,
                                      rel=1e-6)
    np.testing.assert_allclose(table, F.yarn_table(*args), rtol=1e-6)
    np.testing.assert_allclose(table, R.yarn_inv_freq(*args), rtol=1e-6)


def test_rope_rotates_a_slice_with_a_table_and_a_factor():
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)
    table = seq_layers.yarn_inv_freq(5e5, 4, *YARN[:4])
    got = seq_layers.rope(x, pos, 5e5, rotary_dim=4, inv_freq=table, factor=1.2)
    assert jnp.array_equal(got[..., 4:], x[..., 4:])  # the rest passes through
    want = F.rotate(x[0, :, 1], table, 1.2)
    np.testing.assert_allclose(got[0, :, 1], want, atol=1e-6)
    # the mla/moe block's call is the whole head at the plain frequencies
    plain = seq_layers.rope(x, pos, 1e4)
    np.testing.assert_allclose(
        plain[0, :, 0], F.rotate(x[0, :, 0], 1e4 ** (-np.arange(4) / 4.0)), atol=1e-6)


# ---------------------------------------------------------- the pattern as data
def test_two_periods_scan_and_match_the_test_side_loss():
    """Nine layers: the dense one and two periods of window, window, window,
    full; the trunk scans the periods and the kinds' stacks are cut to them."""
    import jax.numpy as jnp

    cfg = dataclasses.replace(CFG, n_layers=9)
    desc = seq_layers.describe_params(V, cfg)
    assert desc["window/q_proj"].shape[0] == 6 and desc["full/q_proj"].shape[0] == 2
    params = seqrec.init_params(V, cfg)
    rows = histories(2, seed=6)
    want = F.loss({k: jnp.asarray(v) for k, v in flat(params).items()}, rows,
                  forward_model(cfg))
    assert float(program_loss(params, rows, cfg)) == pytest.approx(float(want), rel=2e-5)


@pytest.mark.parametrize("change,match", [
    (dict(layer_pattern=("full", "sparse")), "layer_pattern"),
    (dict(n_layers=6), "whole periods"),
    (dict(heads_window=5), "multiple of"),
    (dict(rotary_dim=3), "even"),
    (dict(router_kind="sigmoid_bias"), "softmax router"),
    (dict(mtp_depth=1), "no MTP"),
    (dict(router_kind="argmax"), "router_kind"),
])
def test_a_block_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        seq_layers.check_block(dataclasses.replace(CFG, **change))


def test_the_window_counters_reach_the_trace_and_the_stats(monkeypatch):
    """With tiles of 8 at 32 positions a window layer visits 7 of a causal
    layer's 10 tiles; three window layers a step, two steps."""
    monkeypatch.setattr(seq_layers, "ATTN_BLOCK", 8)
    stats = {}
    cfg = dataclasses.replace(CFG, steps=2, seed=3)
    model = train_seqrec(None, histories(4, seed=1), V - 1, cfg, stats=stats)
    np.testing.assert_array_equal(model.trace["window_tiles"], [21.0, 21.0])
    np.testing.assert_array_equal(model.trace["causal_tiles"], [30.0, 30.0])
    assert stats["counters"]["window_tiles"] == 42.0
    assert stats["counters"]["causal_tiles"] == 60.0
    assert "bias_max" not in stats["counters"]
    assert stats["counters"]["dropped_pairs"] == 0.0
    assert stats["attn_impl"] == {"full": "xla", "window": "xla"}


def test_the_attention_rule_answers_by_kind_of_layer():
    """Both kinds of layer at the published head width, bfloat16, ride the
    kernels on a TPU; this file's 8 wide head keeps XLA's loops."""
    wide = dataclasses.replace(CFG, head_dim=128, compute_dtype="bfloat16")
    assert seq_layers.attn_impls("tpu", wide, 16384) == {
        "full": "pallas", "window": "pallas"}
    assert seq_layers.attn_impls("cpu", wide, 16384) == {
        "full": "xla", "window": "xla"}
    assert seq_layers.attn_impls("tpu", CFG, 16384) == {
        "full": "xla", "window": "xla"}


def test_it_trains_and_serves_from_engine_json_params():
    """Through ``SeqRecAlgorithm`` from a JSON object, the pattern a list."""
    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.parallel.context import ComputeContext
    from pio_tpu.templates.sequence import (PreparedData, Query,
                                            SeqRecAlgorithm, SeqRecParams)

    with open(os.path.join(os.path.dirname(HERE), "examples",
                           "sequence-gqa-window", "engine.json")) as f:
        params = json.load(f)["algorithms"][0]["params"]
    assert isinstance(params["layer_pattern"], list)
    params.update(d_model=32, ffn=64, head_dim=8, rotary_dim=4, expert_ffn=24, max_len=T,
                  steps=2, batch_size=2, compute_dtype="float32")
    algo = SeqRecAlgorithm(params_from_dict(SeqRecParams, params))
    assert algo.params.layer_pattern == tuple(params["layer_pattern"])
    seqs = histories(4, seed=2)
    pd = PreparedData(item_index=BiMap({f"i{i}": i for i in range(V - 1)}),
                      sequences=seqs, user_rows={f"u{r}": r for r in range(4)})
    model = algo.train(ComputeContext(mesh=None), pd)
    result = algo.predict(model, Query(user="u1", num=3))
    assert len(result.item_scores) == 3


# ------------------------------------------------------- mesh and the scopes
@pytest.mark.parametrize("spec", ["dp-ep", "dp-sp-ep"])
def test_a_mesh_equals_the_single_device(trained, spec):
    """Experts and vocabulary sharded over ``model`` (heads are not), rows
    over ``data``, the sequence over ``seq``: the ring passes the window's
    bounds on, and a key block outside the window on another device is
    skipped there too."""
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    seqs, single, _ref = trained
    mesh = build_mesh({"dp-ep": MeshSpec(data=2, model=4),
                       "dp-sp-ep": MeshSpec(data=2, seq=2, model=2)}[spec])
    meshed = train_seqrec(mesh, seqs, V - 1, CFG)
    for key in ("l_main", "pairs"):
        np.testing.assert_allclose(meshed.trace[key], single.trace[key],
                                   rtol=1e-5)
    assert meshed.trace["dropped"].sum() == 0
    want = flat(single.params)
    init = flat(seqrec.init_params(V, CFG))
    update = {k: want[k] - init[k] for k in want}
    errors = group_errors(flat(meshed.params), want, update)
    assert max(errors.values()) < 2e-3, errors


def test_the_scopes_the_metrics_read_are_in_the_compiled_step():
    """Every scope a ``lag.*`` reader names reaches the compiled program's
    op names, the backward pass's too, and nothing else carries ``seq.``."""
    import re

    import jax
    import jax.numpy as jnp

    from pio_tpu.obs.profile import scope_path

    rows = histories(2)
    params = seqrec.init_params(V, CFG)
    text = jax.jit(jax.grad(lambda p: program_loss(p, rows))).lower(
        params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    paths = {scope_path(n, "seq.") for n in names} - {None}
    read = [("seq.gqa", "proj"), ("seq.gqa", "gate"), ("seq.gqa", "attn", "full"),
            ("seq.gqa", "attn", "window"), ("seq.moe", "route"),
            ("seq.moe", "experts"), ("seq.ffn",), ("seq.head",)]
    assert {"/".join(r) for r in read} <= paths, sorted(paths)
    # a custom_vjp's backward repeats its scope in the name; whatever the
    # path, exactly one reader's segments are in it, so the readers tile
    for path in paths:
        hits = [r for r in read if "/" + "/".join(r) + "/" in f"/{path}/"]
        assert len(hits) == 1, (path, hits)
    for kind in ("full", "window"):
        backward = [n for n in names
                    if "transpose(" in n and f"seq.gqa/attn/{kind}" in n]
        assert backward


# ------------------------------------------------- the accepted cell's contract
#: sha256 over the mla/moe block at a small size, recorded on the parent
#: commit (33d05b8): its parameter paths and shapes, its initial parameters,
#: and one training step's losses, gradient norms and routed pairs on the CPU
MLA_CONTRACT = {
    "paths": "2f14afe2cf977b29eeb692f8e52f1c5a05a3b2b43ba742c7972ebacb4dabb696",
    "init": "b351d6bae7b8891aa64359e4184a917dd7cea0a675ae1cffde77fd0c5a176916",
    "step": "793bbe090f5a1e93b2aa9489e0a774585bb696e846f87f4f4043952ab8454614",
}


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def test_the_mla_moe_block_is_the_parents_to_the_bit():
    """``glm47flash-ep8.train-seq`` zips ``grad_norm`` against its reference's
    groups by position and looks parameters up by path: the mla/moe block's
    paths, ``GROUPS``, initial parameters and one step's numbers are the
    parent commit's, bit for bit."""
    cfg = SeqRecConfig(
        attention_kind="mla", ffn_kind="moe", d_model=32, n_heads=2, n_layers=3,
        dense_layers=1, ffn=64, q_lora_rank=16, kv_lora_rank=8, qk_nope_dim=8,
        qk_rope_dim=4, v_head_dim=12, n_experts=16, experts_first=4,
        experts_held=4, experts_per_token=2, expert_ffn=24, mtp_depth=1,
        max_len=32, batch_size=2, steps=1, learning_rate=1e-3,
        compute_dtype="float32", stream="off", seed=11)
    assert seq_layers.GROUPS == seq_layers.groups_of(cfg) == (
        "embedding", "head", "mla", "router", "routed_experts",
        "shared_expert", "dense_mlp", "mtp")
    desc = seq_layers.describe_params(60, cfg)
    paths = sorted(desc)
    assert {"blocks/q_a", "dense/w_gate", "mtp/eh_proj", "blocks/router_b"} <= set(paths)
    assert hashlib.sha256("\n".join(
        f"{p} {desc[p].shape}" for p in paths).encode()).hexdigest() == MLA_CONTRACT["paths"]
    init = flat(seqrec.init_params(60, cfg))
    assert _digest(init[k] for k in paths) == MLA_CONTRACT["init"]
    seqs = np.random.default_rng(5).integers(1, 60, size=(4, 32)).astype(np.int32)
    trace = train_seqrec(None, seqs, 59, cfg).trace
    assert _digest([trace["l_main"], trace["l_mtp"], trace["grad_norm"],
                    trace["pairs"]]) == MLA_CONTRACT["step"]
