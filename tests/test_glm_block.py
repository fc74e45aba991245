"""The mla/moe block of the sequence template (GLM-4.7-Flash's,
``glm4_moe_lite``) against the plain reference the benchmark keeps
(``benchmarks/seq_reference.py``), at a small size on the CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import seq_reference as R  # noqa: E402

from pio_tpu.models import seq_layers, seqrec  # noqa: E402
from pio_tpu.models.seqrec import SeqRecConfig, train_seqrec  # noqa: E402
from pio_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402

V, T = 48, 32  # V divides by every model axis the tests use
#: the reference's model dict and the same model as the program's config
M = dict(
    hidden_size=32, num_attention_heads=2, num_hidden_layers=3,
    intermediate_size=64, first_k_dense_replace=1, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12,
    rope_theta=1e6, rms_norm_eps=1e-5, router_width=16, experts_first=4,
    n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=24,
    n_shared_experts=1, routed_scaling_factor=1.8, num_nextn_predict_layers=1,
    vocab_size=V, bias_update_rate=1e-3, mtp_weight=0.3, learning_rate=1e-3,
    init_std=seq_layers.INIT_STD, embed_init_std=seq_layers.EMBED_INIT_STD,
    bias_init_std=seq_layers.BIAS_INIT_STD,
)
CFG = SeqRecConfig(
    d_model=32, n_heads=2, n_layers=3, ffn=64, max_len=T, steps=3,
    batch_size=2, stream="off", seed=11, attention_kind="mla", ffn_kind="moe",
    dense_layers=1, q_lora_rank=16, kv_lora_rank=8, qk_nope_dim=8,
    qk_rope_dim=4, v_head_dim=12, n_experts=16, experts_first=4,
    experts_held=4, experts_per_token=2, expert_ffn=24, mtp_depth=1,
    compute_dtype="float32",
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
    norm = dict.fromkeys(R.GROUPS, 0.0)
    for path, w in want.items():
        g = R.group_of(path)
        diff[g] += float(np.sum((np.asarray(got[path], np.float64) - w) ** 2))
        norm[g] += float(np.sum(np.asarray(scale[path], np.float64) ** 2))
    return {g: np.sqrt(diff[g] / norm[g]) for g in R.GROUPS if norm[g] > 0}


@pytest.fixture(scope="module")
def trained():
    """Three Adam steps of the program and of the reference, same weights."""
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
    # the selection bias is drawn too, so selection is not the plain top-k
    assert np.abs(ours["blocks/router_b"]).max() > 0.02


def test_the_cells_file_states_the_initialisers_rule():
    """The configuration's ``init`` block, which the reference follows, holds
    the block's own constants: the example and the cell run one model."""
    import json

    with open(os.path.join(BENCH, "configs", "glm47flash-ep8.json")) as f:
        init = json.load(f)["init"]
    assert (init["init_std"], init["embed_init_std"], init["bias_init_std"]) == (
        seq_layers.INIT_STD, seq_layers.EMBED_INIT_STD, seq_layers.BIAS_INIT_STD)


def test_the_shapes_are_written_once():
    """``init_params``, ``param_specs`` and the placement skeleton all come
    from ``describe_params``, for the old block and the new."""
    for cfg in (CFG, SeqRecConfig(d_model=8, n_heads=2, n_layers=2, ffn=16)):
        desc = seq_layers.describe_params(V, cfg)
        assert {k: v.shape for k, v in flat(seqrec.init_params(V, cfg)).items()
                } == {k: leaf.shape for k, leaf in desc.items()}
        assert set(flat(seqrec.param_specs(cfg))) == set(desc)


@pytest.mark.parametrize("key", ["l_main", "l_mtp", "pairs", "grad_norm"])
def test_the_per_step_trace_matches_the_reference(trained, key):
    _seqs, model, ref = trained
    np.testing.assert_allclose(model.trace[key], ref[key], rtol=2e-5)
    assert model.trace["dropped"].sum() == 0


def test_the_two_groupings_of_the_parameters_agree():
    assert seq_layers.GROUPS == R.GROUPS
    for path in seq_layers.describe_params(V, CFG):
        assert seq_layers.group_of(path) == R.group_of(path), path


@pytest.mark.parametrize("group", R.GROUPS)
def test_three_adam_steps_match_the_reference(trained, group):
    _seqs, model, ref = trained
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    assert group_errors(flat(model.params), ref["final"], update)[group] < 1e-3


@pytest.mark.parametrize("group", R.GROUPS)
def test_the_gradients_match_the_reference(group):
    import jax
    import jax.numpy as jnp

    rows = histories(2, seed=3)
    params = seqrec.init_params(V, CFG)

    def loss(p):
        t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
        t2 = jnp.pad(rows[:, 2:], ((0, 0), (0, 2)))
        m1 = ((t1 > 0) & (rows > 0)).astype(jnp.float32)
        batch = (jnp.asarray(rows), t1, m1, t2, m1 * (t2 > 0))
        sums, counters = seqrec._latent_loss_sums(p, batch, CFG, None, None)
        return seqrec._latent_loss(sums, counters, CFG)[0]

    ours = flat(jax.grad(loss)(params))
    theirs = jax.device_get(jax.grad(
        lambda p: R.batch_loss(p, jnp.asarray(rows), M)[0]
    )(R.init_params(M, CFG.seed)))
    assert group_errors(ours, theirs, theirs)[group] < 1e-4


def test_serving_scores_are_the_references_logits(trained):
    seqs, model, ref = trained
    scores = model.next_item_scores(seqs[:3])
    for r in range(3):
        want = R.next_item_logits(ref["final"], seqs[r], M)
        np.testing.assert_allclose(scores[r], want, atol=2e-5)
    # a padded history is scored from its last real position
    short = seqs[:1].copy()
    short[0, 20:] = 0
    np.testing.assert_allclose(
        model.next_item_scores(short)[0],
        R.next_item_logits(ref["final"], short[0, :20], M), atol=2e-5)


def _layer(seed=5, n_tokens=48):
    """One expert layer's weights for all 16 experts, and tokens."""
    import jax

    cfg = dataclasses.replace(CFG, experts_first=0, experts_held=16)
    desc = seq_layers._expert_layer_leaves(1, cfg)
    blk = {k: v[0] for k, v in seq_layers.unflatten({
        "b/" + k: v for k, v in seq_layers.init_from(
            {"b/" + k: leaf for k, leaf in desc.items()}, seed)["b"].items()
    })["b"].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, n_tokens, cfg.d_model))
    return cfg, blk, x


def _share(cfg, blk, first, held):
    part = dict(blk)
    for name in ("e_gate", "e_up", "e_down"):
        part[name] = blk[name][first:first + held]
    return dataclasses.replace(cfg, experts_first=first, experts_held=held), part


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts of the result the eight shares give,
    the shared expert counted once, are the uncut 16-expert layer's result,
    the program's and the reference's alike."""
    import jax.numpy as jnp

    cfg, blk, x = _layer()
    whole, counters = seq_layers.moe(blk, x, cfg, None)
    zero_shared = dict(blk, s_down=jnp.zeros_like(blk["s_down"]))
    shared = whole - seq_layers.moe(zero_shared, x, cfg, None)[0]
    total = shared
    pairs = 0.0
    for share in range(8):
        scfg, part = _share(cfg, zero_shared, 2 * share, 2)
        y, c = seq_layers.moe(part, x, scfg, None)
        total = total + y
        pairs += float(c["pairs"])
        np.testing.assert_array_equal(c["load"], counters["load"])
    np.testing.assert_allclose(total, whole, atol=1e-6)
    assert pairs == float(counters["pairs"]) == x.shape[1] * cfg.experts_per_token
    m = dict(M, experts_first=0, n_routed_experts=16)
    want, _load, ref_pairs = R._moe(blk, x[0], m, None, None)
    np.testing.assert_allclose(whole[0], want, atol=1e-6)
    assert float(ref_pairs) == pairs


@pytest.mark.parametrize("first, held, passes", [
    (4, 4, 2),    # a quarter held: the first pass stages 64 of the 128 pairs
    (5, 2, 3),    # an eighth held: 32, 32, then the rest in two chunks
    (0, 16, 1),   # every expert held: one pass of all pairs
])
def test_dropless_under_a_planted_skew(first, held, passes):
    """Every token to the same two held experts: more pairs than the first
    pass of the grouped matmuls stages (twice a balanced router's), so
    further passes run, none dropped, the result the reference's."""
    import jax.numpy as jnp

    cfg, blk, x = _layer(n_tokens=64)
    cfg, part = _share(cfg, blk, first, held)
    bias = jnp.zeros(16).at[jnp.array([5, 6])].set(10.0)
    part["router_b"] = bias
    y, c = seq_layers.moe(part, x, cfg, None)
    assert float(c["pairs"]) == 2 * 64 and float(c["dropped"]) == 0
    assert float(c["load"][5]) == float(c["load"][6]) == 64
    assert float(c["passes"]) == passes and float(c["staged"]) == 2 * 64
    m = dict(M, experts_first=first, n_routed_experts=held)
    want, _load, _pairs = R._moe(part, x[0], m, None, None)
    np.testing.assert_allclose(y[0], want, atol=1e-6)


def test_the_dropped_counter_counts_an_expert_the_matmuls_never_got(
        monkeypatch):
    """The issue's sixth fault planted in the program: the pass plan gives
    the last held expert's group no rows. ``dropped`` reads that expert's
    pairs (it is counted from what the grouped matmuls are given, not from
    the routing), and the layer's result lacks that expert's part."""
    cfg, blk, x = _layer(n_tokens=64)
    cfg, part = _share(cfg, blk, 4, 4)
    sound, c = seq_layers.moe(part, x, cfg, None)
    assert float(c["dropped"]) == 0 and float(c["load"][7]) > 0
    plan = seq_layers.pass_plan
    monkeypatch.setattr(seq_layers, "pass_plan",
                        lambda *a: plan(*a).at[:, -1].set(0))
    y, dropped = seq_layers.moe(part, x, cfg, None)
    assert float(dropped["dropped"]) == float(c["load"][7])
    assert float(dropped["pairs"]) == float(c["pairs"])
    assert not np.allclose(y, sound, atol=1e-6)


def test_attention_and_the_head_are_cut_in_blocks(monkeypatch):
    """Tiles and chunks smaller than the row give the numbers one tile
    gives (the constants are clamped to the row at this size)."""
    import jax.numpy as jnp

    rows = histories(2, seed=3)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    m1 = (t1 > 0).astype(jnp.float32)
    batch = (jnp.asarray(rows), t1, m1, t1, m1)
    params = seqrec.init_params(V, CFG)

    def sums():
        got, _ = seqrec._latent_loss_sums(params, batch, CFG, None, None)
        return {k: float(v) for k, v in got.items()}

    whole = sums()
    monkeypatch.setattr(seq_layers, "ATTN_BLOCK", 8)
    monkeypatch.setattr(seq_layers, "TOKEN_CHUNK", 16)
    monkeypatch.setattr(seqrec, "TOKEN_CHUNK", 16)
    cut = sums()
    assert cut == pytest.approx(whole, rel=1e-5)


@pytest.mark.parametrize(
    "spec", [MeshSpec(data=2, model=4), MeshSpec(data=2, seq=2, model=2)],
    ids=["dp-ep", "dp-sp-ep"])
def test_experts_over_model_equal_the_single_device_layer(trained, spec):
    """Experts and vocabulary sharded over ``model`` (tokens replicated, a
    psum closing the sum), rows over ``data``, the sequence over ``seq``."""
    seqs, single, _ref = trained
    meshed = train_seqrec(build_mesh(spec), seqs, V - 1, CFG)
    for key in ("l_main", "l_mtp", "pairs"):
        np.testing.assert_allclose(meshed.trace[key], single.trace[key],
                                   rtol=1e-5)
    assert meshed.trace["dropped"].sum() == 0
    want = flat(single.params)
    init = flat(seqrec.init_params(V, CFG))
    update = {k: want[k] - init[k] for k in want}
    errors = group_errors(flat(meshed.params), want, update)
    assert max(errors.values()) < 2e-3, errors


def test_the_stats_call_reports_spans_and_counters():
    stats = {}
    train_seqrec(None, histories(4), V - 1,
                 dataclasses.replace(CFG, steps=2), stats=stats)
    assert {"pack_s", "place_s", "steps_s", "readback_s"} <= set(stats)
    counters = stats["counters"]
    assert counters["dropped_pairs"] == 0 and counters["pairs_held"] > 0
    assert counters["load_max_over_mean"] >= 1 and counters["bias_max"] > 0
    # two steps of two expert layers and the MTP module's: a pass each at
    # least, and no pair outside the rows they staged
    assert counters["moe_passes"] >= 2 * 3
    assert counters["moe_staged_rows"] >= counters["pairs_held"]
    assert "device_scope_s" not in stats  # no chip, no device scopes
    assert stats["attn_impl"] == {"mla": "xla"}  # the CPU keeps XLA's loops


def test_the_attention_rule_takes_the_cells_heads_on_a_tpu():
    """``stats["attn_impl"]`` is ``ring.attention_impl`` at the shapes ``mla``
    hands it: the published head (192 + 64 wide keys, 256 wide values) in
    bfloat16 rides the kernels on a TPU; this test's 12 wide head, float32
    operands and every CPU run keep XLA's loops."""
    from pio_tpu.models.seq_layers import attn_impls

    wide = dataclasses.replace(CFG, qk_nope_dim=192, qk_rope_dim=64,
                               v_head_dim=256, compute_dtype="bfloat16")
    assert attn_impls("tpu", wide, 8192) == {"mla": "pallas"}
    assert attn_impls("cpu", wide, 8192) == {"mla": "xla"}
    assert attn_impls("tpu", CFG, 8192) == {"mla": "xla"}
    assert attn_impls("tpu", dataclasses.replace(wide, compute_dtype="float32"),
                      8192) == {"mla": "xla"}
    # a row too short for a lane-wide block
    assert attn_impls("tpu", wide, 64) == {"mla": "xla"}


def test_the_scopes_the_metrics_read_are_in_the_compiled_step():
    """Every ``seq.*`` scope reaches the compiled program's op names, the
    backward pass's too, and the MTP module's layers sit under ``seq.mtp``."""
    import re

    import jax
    import jax.numpy as jnp
    import optax

    from pio_tpu.obs.profile import scope_path

    rows = jnp.asarray(histories(2))
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    m1 = (t1 > 0).astype(jnp.float32)
    batch = (rows, t1, m1, t1, m1)
    tx = optax.adam(1e-3)

    def step(params, opt_state):
        def loss(p):
            return seqrec._latent_loss(
                *seqrec._latent_loss_sums(p, batch, CFG, None, None), CFG)
        (_l, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
        with jax.named_scope("seq.opt"):
            updates, opt_state = tx.update(grads, opt_state, params)
            return seqrec._after_step(
                optax.apply_updates(params, updates), aux, CFG)

    params = seqrec.init_params(V, CFG)
    text = jax.jit(step).lower(params, tx.init(params)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    paths = {scope_path(n, "seq.") for n in names} - {None}
    for want in ("seq.mla/proj", "seq.mla/attn", "seq.moe/route",
                 "seq.moe/experts", "seq.ffn", "seq.head", "seq.opt",
                 "seq.mtp/seq.mla/attn", "seq.mtp/seq.moe/experts",
                 "seq.mtp/seq.head"):
        assert want in paths, (want, sorted(paths))
    backward = [n for n in names if "transpose(" in n and "seq.mla/attn" in n]
    assert backward and all(
        scope_path(n, "seq.").endswith("seq.mla/attn") for n in backward)
    atoms = {seg for p in paths for seg in p.split("/")}
    assert atoms <= {"seq.mla", "proj", "attn", "seq.moe", "route", "experts",
                     "seq.ffn", "seq.head", "seq.opt", "seq.mtp"}, atoms


@pytest.mark.parametrize("kw, message", [
    (dict(ffn_kind="relu"), "unsupported block"),
    (dict(dense_layers=3), "dense_layers"),
    (dict(experts_first=14), "held experts"),
    (dict(attention="ulysses"), "ring attention"),
])
def test_config_validation(kw, message):
    with pytest.raises(ValueError, match=message):
        train_seqrec(None, histories(2), V - 1, dataclasses.replace(CFG, **kw))


def test_the_block_has_no_pipe_split():
    mesh = build_mesh(MeshSpec(data=4, pipe=2))
    with pytest.raises(ValueError, match="no pipe split"):
        train_seqrec(mesh, histories(4), V - 1, CFG)
