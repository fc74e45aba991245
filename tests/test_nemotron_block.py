"""The block of single mixers of the sequence template (Nemotron-3-Nano's:
Mamba-2 layers, relu2 experts behind the sigmoid-bias router and NoPE
grouped-query attention, one mixer a layer) at a small size on the CPU,
trained and served, against the benchmark's plain reference
(``benchmarks/nemotron_reference.py``), whose state-space layer is the
recurrence itself, one time step after another. The layers one by one are in
``test_nemotron_layers.py``.

Tolerances: both sides compute in float32 (``compute_dtype="float32"``); what
is left is the order of the additions (chunk products against a step-by-step
state, the blocked online softmax against a dense one, grouped matmuls
against a loop over experts), a few float32 ulps a layer: 2e-5 relative on
losses, logits and gradient norms, 1e-4 on a group's gradient as a whole,
1e-3 on three Adam steps. Under the bfloat16 policy the program is held to
the reference with bfloat16 operands (its witness) at 5e-2 a group.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from nemotron_small import (BENCH, CFG, HERE, M, PATTERN, R, T, V, flat,
                            group_errors, histories, program_loss)

import nemotron_cost  # benchmarks/ is on the path since nemotron_small

from pio_tpu.models import seq_layers, seqrec
from pio_tpu.models.seqrec import train_seqrec


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
    # the draws the config gives no rule for are what the file's ``assumed`` says
    a = np.exp(ours["mamba/a_log"])
    assert a.min() >= 1.0 and a.max() < 16.0
    dt = np.log1p(np.exp(ours["mamba/dt_bias"]))  # softplus undoes the inverse
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.abs(ours["mamba/conv_w"]).max() <= 0.5
    assert (ours["mamba/d_skip"] == 1).all() and (ours["mamba/gate_g"] == 1).all()


def test_the_stacks_have_unlike_shapes_written_once():
    desc = seq_layers.describe_params(V, CFG)
    inner, conv = seq_layers.ssm_widths(CFG)
    assert (inner, conv) == (32, 32 + 2 * 2 * 8)
    assert desc["mamba/in_proj"].shape == (4, 32, inner + conv + 8)
    assert desc["mamba/conv_w"].shape == (4, 4, conv)
    assert desc["mamba/out_proj"].shape == (4, inner, 32)
    assert desc["moe/e_up"].shape == (4, 4, 32, 24)
    assert desc["moe/s_up"].shape == (4, 32, 48)  # the shared expert, twice as wide
    assert desc["moe/router_b"].shape == (4, 16)
    assert desc["attn/q_proj"].shape == (1, 32, 8 * 8)
    assert desc["attn/k_proj"].shape == (1, 32, 2 * 8)
    # two matrices an expert, no gate on the attention, one norm a layer
    assert not {"moe/e_gate", "moe/s_gate", "attn/g_proj", "attn/ffn_norm"} & set(desc)
    assert {k: v.shape for k, v in flat(seqrec.init_params(V, CFG)).items()
            } == {k: leaf.shape for k, leaf in desc.items()}
    assert set(flat(seqrec.param_specs(CFG))) == set(desc)
    assert {k: leaf.shape for k, leaf in desc.items()} == R.shapes(M)


def test_the_two_groupings_of_the_parameters_agree():
    """``grad_norm``'s columns stand in the reference's order: the
    comparison zips them by position."""
    assert seq_layers.groups_of(CFG) == R.GROUPS
    for path in seq_layers.describe_params(V, CFG):
        assert seq_layers.group_of(path, CFG) == R.group_of(path, M), path
    assert R.group_of("mamba/in_proj") == "ssm_proj"
    assert {R.group_of("mamba/" + n) for n in (
        "conv_w", "conv_b", "a_log", "d_skip", "dt_bias", "gate_g")} == {"ssm_scan"}
    assert R.group_of("mamba/norm") == R.group_of("lnf_g") == "norms"


def test_the_cells_file_maps_onto_the_programs_fields():
    """The configuration's key map names fields ``SeqRecParams`` has, and the
    reference reads the file as the issue cut it."""
    from pio_tpu.templates.sequence import SeqRecParams

    with open(os.path.join(BENCH, "configs", "nemotron3nano-ep16.json")) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(SeqRecParams)}
    m = R.model(config)
    assert set(config["harness"]["param_of"]) <= set(m)
    assert set(config["harness"]["param_of"].values()) <= fields
    assert set(config["harness"]["params"]) <= fields
    assert m["mixer_pattern"] == PATTERN
    assert (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["chunk_size"]) == (64, 64, 8, 128, 128)
    assert (config["init"]["init_std"], config["init"]["embed_init_std"],
            config["init"]["bias_init_std"]) == (
        seq_layers.INIT_STD, seq_layers.EMBED_INIT_STD, seq_layers.BIAS_INIT_STD)
    total = sum(int(np.prod(s)) for s in R.shapes(m).values())
    assert total == config["deployment"]["parameters_here"] == 666963456


@pytest.mark.parametrize("key", ["l_main", "pairs", "grad_norm"])
def test_the_per_step_trace_matches_the_reference(trained, key):
    _seqs, model, ref = trained
    np.testing.assert_allclose(model.trace[key], ref[key], rtol=2e-5)
    assert model.trace["dropped"].sum() == 0
    assert model.trace["pairs"].shape == (3, 4)  # a column an expert layer
    assert "l_mtp" not in model.trace and "window_tiles" not in model.trace


@pytest.mark.parametrize("group", R.GROUPS)
def test_three_adam_steps_match_the_reference(trained, group):
    _seqs, model, ref = trained
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    assert group_errors(flat(model.params), ref["final"], update)[group] < 1e-3


def test_the_selection_bias_moves_by_the_rule(trained):
    _seqs, model, ref = trained
    np.testing.assert_allclose(model.params["moe"]["router_b"],
                               ref["final"]["moe/router_b"], atol=1e-7)
    moved = ref["final"]["moe/router_b"] - ref["init"]["moe/router_b"]
    assert np.abs(moved).max() == pytest.approx(3e-3, rel=1e-3)


def test_serving_scores_are_the_references_last_logits(trained):
    """A whole history is scored from its last position; a padded one from
    its last real position: the padding lies after every real event, and
    causality keeps it out (the reference's row is the whole history, read
    at both positions)."""
    import jax

    seqs, model, ref = trained
    short = seqs[0].copy()
    short[20:] = 0
    scores = model.next_item_scores(np.stack([seqs[0], short]))
    with jax.default_matmul_precision("highest"):
        h, _, _ = R.trunk(ref["final"], seqs[0], M)
        for got, at in zip(scores, (T - 1, 19)):
            last = R._norm(h[at], ref["final"]["lnf_g"], M["rms_norm_eps"])
            np.testing.assert_allclose(
                got, R._dot(last, ref["final"]["head"].T, None), atol=2e-5)
    np.testing.assert_allclose(
        scores[0], R.next_item_logits(ref["final"], seqs[0], M), atol=2e-5)


def test_the_chunk_counter_is_the_cost_functions_count(trained):
    _seqs, model, _ref = trained
    want = nemotron_cost.ssm_chunks(M, rows=2, seq_len=T, steps=3)
    assert want == 3 * 2 * 4 * (T // 8)
    assert model.trace["ssm_chunks"].sum() == want
    assert nemotron_cost.chunk_of(36, 8) == 6 and nemotron_cost.chunk_of(6, 8) == 6


def test_the_counters_reach_the_trace_and_the_stats():
    stats = {}  # the kept program of ``trained``: another seed, other rows
    cfg = dataclasses.replace(CFG, seed=3)
    model = train_seqrec(None, histories(8, seed=1), V - 1, cfg, stats=stats)
    np.testing.assert_array_equal(model.trace["ssm_chunks"], [32.0] * 3)
    counters = stats["counters"]
    assert counters["ssm_chunks"] == 96.0
    assert counters["ssm_state_absmax"] == model.trace["ssm_state_absmax"].max() > 0
    assert counters["dropped_pairs"] == 0.0 and counters["bias_max"] > 0
    assert counters["pairs_held"] == model.trace["pairs"].sum()
    # three steps of four expert layers: a pass each at least, and the held
    # pairs lie in the rows the passes staged
    assert counters["moe_passes"] == model.trace["passes"].sum() >= 3 * 4
    assert (model.trace["staged"] >= model.trace["pairs"]).all()
    assert counters["moe_staged_rows"] == model.trace["staged"].sum()
    assert stats["attn_impl"] == {"full": "xla"}
    wide = dataclasses.replace(CFG, head_dim=128, compute_dtype="bfloat16")
    assert seq_layers.attn_impls("tpu", wide, 16384) == {"full": "pallas"}


def test_it_trains_and_serves_from_engine_json_params():
    """Through ``SeqRecAlgorithm`` from a JSON object, the pattern a list."""
    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.parallel.context import ComputeContext
    from pio_tpu.templates.sequence import (PreparedData, Query,
                                            SeqRecAlgorithm, SeqRecParams)

    with open(os.path.join(os.path.dirname(HERE), "examples",
                           "sequence-mamba-moe", "engine.json")) as f:
        params = json.load(f)["algorithms"][0]["params"]
    assert isinstance(params["mixer_pattern"], list)
    params.update(d_model=32, head_dim=8, ssm_heads=8, ssm_head_dim=4,
                  ssm_groups=2, ssm_state=8, ssm_chunk=8, expert_ffn=24,
                  max_len=T, steps=2, batch_size=2, compute_dtype="float32")
    algo = SeqRecAlgorithm(params_from_dict(SeqRecParams, params))
    assert algo.params.mixer_pattern == tuple(params["mixer_pattern"])
    seqs = histories(4, seed=2)
    pd = PreparedData(item_index=BiMap({f"i{i}": i for i in range(V - 1)}),
                      sequences=seqs, user_rows={f"u{r}": r for r in range(4)})
    model = algo.train(ComputeContext(mesh=None), pd)
    result = algo.predict(model, Query(user="u1", num=3))
    assert len(result.item_scores) == 3


# ------------------------------------------------------- mesh and the scopes
def test_a_mesh_with_an_expert_axis_equals_the_single_device(trained):
    """Experts and vocabulary sharded over ``model``, rows over ``data``:
    the mamba and attention layers are whole on every device, and the
    largest carried state is a maximum over the rows, not a sum."""
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    seqs, single, _ref = trained
    meshed = train_seqrec(build_mesh(MeshSpec(data=2, model=4)), seqs, V - 1, CFG)
    for key in ("l_main", "pairs", "ssm_chunks", "ssm_state_absmax"):
        np.testing.assert_allclose(meshed.trace[key], single.trace[key], rtol=1e-5)
    assert meshed.trace["dropped"].sum() == 0
    want = flat(single.params)
    init = flat(seqrec.init_params(V, CFG))
    update = {k: want[k] - init[k] for k in want}
    errors = group_errors(flat(meshed.params), want, update)
    assert max(errors.values()) < 2e-3, errors


def test_the_scopes_the_metrics_read_are_in_the_compiled_step():
    """Every scope a ``nem.*`` reader names reaches the compiled program's
    op names, the backward pass's too; whatever the path, at most one
    reader's segments are in it, so no second is counted twice."""
    import re

    import jax

    from pio_tpu.obs.profile import scope_path

    rows = histories(2)
    params = seqrec.init_params(V, CFG)
    text = jax.jit(jax.grad(lambda p: program_loss(p, rows))).lower(
        params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    paths = {scope_path(n, "seq.") for n in names} - {None}
    read = [("seq.ssm", "proj"), ("seq.ssm", "conv"), ("seq.ssm", "ssd"),
            ("seq.ssm", "norm"), ("seq.gqa", "attn", "full"),
            ("seq.moe", "route"), ("seq.moe", "experts")]
    others = {"seq.gqa/proj", "seq.ffn", "seq.head"}
    assert {"/".join(r) for r in read} | others <= paths, sorted(paths)
    for path in paths:  # no path is read by two of the cell's metrics
        hits = [r for r in read if "/" + "/".join(r) + "/" in f"/{path}/"]
        assert len(hits) <= 1, (path, hits)
    assert not [p for p in paths if "seq.gqa/gate" in p]  # no gate, no scope
    assert [n for n in names if "transpose(" in n and "seq.ssm/ssd" in n]
