"""The block without experts of the sequence template (Granite 4.0-H Micro's:
a Mamba-2 or NoPE grouped-query mixer and a dense SwiGLU mixer a layer, a tied
table, four multipliers) at a small size on the CPU, trained and served
through the normal path, against the benchmark's plain reference
(``benchmarks/granite_reference.py``). The layers one by one are in
``test_granite_layers.py``.

Tolerances as ``test_nemotron_block.py``'s: both sides compute in float32; what
is left is the order of the additions: 2e-5 relative on losses, logits and
gradient norms, 1e-3 on three Adam steps.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from granite_small import (BENCH, CFG, M, PATTERN, R, T, V, flat, group_errors,
                           histories)

import granite_cost  # benchmarks/ is on the path since granite_small
import run

from pio_tpu.models import seq_layers, seqrec
from pio_tpu.models.seqrec import train_seqrec

CONFIG = os.path.join(BENCH, "configs", "granite4hmicro-vp8.json")


@pytest.fixture(scope="module")
def trained():
    """Three Adam steps of the program and of the benchmark's reference."""
    seqs = histories()
    stats = {}
    model = train_seqrec(None, seqs, V - 1, CFG, stats=stats)
    ref = R.train(M, seqs, seed=CFG.seed, steps=3, batch=2)
    return seqs, model, ref, stats


def test_the_two_initialisers_agree_to_the_bit():
    ours = flat(seqrec.init_params(V, CFG))
    theirs = R.init_params(M, CFG.seed)
    assert set(ours) == set(theirs) and "head" not in ours
    for path, value in theirs.items():
        assert np.array_equal(ours[path], np.asarray(value)), path


def test_the_stacks_are_written_once_and_there_is_no_head():
    desc = seq_layers.describe_params(V, CFG)
    assert desc["mlp/w_gate"].shape == desc["mlp/w_up"].shape == (3, 32, 48)
    assert desc["mlp/w_down"].shape == (3, 48, 32) and desc["mlp/norm"].shape == (3, 32)
    assert desc["mamba/in_proj"].shape == (2, 32, 64 + 64 + 2 * 8 + 32)
    assert desc["attn/q_proj"].shape == (1, 32, 8 * 4)
    assert not {"head", "moe/e_up", "moe/router_w", "attn/ffn_norm"} & set(desc)
    assert {k: leaf.shape for k, leaf in desc.items()} == R.shapes(M)
    assert set(flat(seqrec.param_specs(CFG))) == set(desc)


def test_the_two_groupings_of_the_parameters_agree():
    """``grad_norm``'s columns stand in the reference's order, for this block
    and, unchanged, for the accepted block of single mixers: the comparison
    zips them by position against ``nemotron_reference.GROUPS``."""
    import nemotron_reference

    assert seq_layers.groups_of(CFG) == R.GROUPS
    for path in seq_layers.describe_params(V, CFG):
        assert seq_layers.group_of(path, CFG) == R.group_of(path, M), path
    assert R.group_of("mlp/w_gate") == R.group_of("mlp/w_down") == "dense_mlp"
    assert R.group_of("mlp/norm") == R.group_of("mamba/norm") == "norms"
    nemotron = dataclasses.replace(
        CFG, tied_head=False, n_layers=3, mixer_pattern=("mamba", "moe", "attn"))
    assert seq_layers.groups_of(nemotron) == seq_layers.MIXER_GROUPS == (
        nemotron_reference.GROUPS)


def test_the_cells_file_maps_onto_the_programs_fields():
    """The configuration's key map names fields ``SeqRecParams`` has, the
    reference reads the file as the issue cut it, and the program's own count
    of the parameters is the file's hand count."""
    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    with open(CONFIG) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(SeqRecParams)}
    m = R.model(config)
    assert set(config["harness"]["param_of"]) <= set(m)
    assert set(config["harness"]["param_of"].values()) <= fields
    assert set(config["harness"]["params"]) <= fields
    assert m["mixer_pattern"] == ("mamba", "mlp") * 5 + ("attn", "mlp") + (
        "mamba", "mlp") * 4 and m["n_mixers"] == 20
    assert (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"],
            m["mamba_d_state"], m["mamba_chunk_size"], m["head_dim"]) == (
        64, 64, 1, 128, 256, 64)
    assert config["init"]["init_std"] == seq_layers.INIT_STD
    driver = run.load_module("drivers", "train_seq_cfg")
    p = params_from_dict(SeqRecParams, driver.algorithm_params(config, m, 1))
    assert (p.tied_head, p.embed_scale, p.residual_scale, p.attn_scale,
            p.logit_scale, p.ffn, p.ssm_groups, p.ssm_chunk) == (
        True, 12.0, 0.22, 0.015625, 0.125, 8192, 1, 256)
    ours = sum(int(np.prod(leaf.shape)) for leaf in
               seq_layers.describe_params(config["vocab_size"], p).values())
    mamba = 2048 * (4096 + 4096 + 2 * 128 + 64) + 4096 * 2048 + 5 * 4352 + (
        3 * 64 + 4096 + 2048)
    mlp = 2048 * 16384 + 8192 * 2048 + 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2048
    by_hand = 9 * mamba + attn + 10 * mlp + 12544 * 2048 + 2048
    assert (mamba, mlp, attn) == (25849280, 50333696, 10487808)
    assert ours == by_hand == config["deployment"]["parameters_here"] == (
        772160448) == granite_cost.n_parameters(m)


@pytest.mark.parametrize("key", ["l_main", "grad_norm"])
def test_the_per_step_trace_matches_the_reference(trained, key):
    _seqs, model, ref, _stats = trained
    np.testing.assert_allclose(model.trace[key], ref[key], rtol=2e-5)


def test_a_block_without_experts_carries_the_expert_columns_empty(trained):
    """Trained three steps through ``train_seqrec``: the trace holds the
    experts' counters as ``[steps, 0]`` arrays, as the reference's ``pairs``,
    and no selection bias."""
    _seqs, model, ref, stats = trained
    for key in ("pairs", "dropped", "passes", "staged", "load_max_over_mean"):
        assert model.trace[key].shape == (3, 0), key
    assert ref["pairs"].shape == (3, 0)
    assert model.trace["grad_norm"].shape == (3, len(R.GROUPS))
    assert not {"bias_max", "l_mtp", "window_tiles"} & set(model.trace)
    assert stats["experts_impl"] == "none" and stats["attn_impl"] == {"full": "xla"}
    counters = stats["counters"]
    assert (counters["pairs_held"], counters["dropped_pairs"],
            counters["moe_passes"]) == (0.0, 0.0, 0.0)
    assert not {"load_max_over_mean", "bias_max"} & set(counters)


@pytest.mark.parametrize("group", R.GROUPS)
def test_three_adam_steps_match_the_reference(trained, group):
    _seqs, model, ref, _stats = trained
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    assert group_errors(flat(model.params), ref["final"], update)[group] < 1e-3


def test_the_accepted_driver_reads_a_trace_without_experts(trained):
    """``drivers/train_seq_cfg.compare_call`` on the trained call and on a
    made-up one: ``pairs`` and ``dropped_pairs`` read 0.0, every key it reads
    has a limit or is reported (it raises for none), and a call whose columns
    are not empty where the reference's are is not ``correct``."""
    _seqs, model, ref, _stats = trained
    driver = run.load_module("drivers", "train_seq_cfg")
    with open(CONFIG) as f:
        config = json.load(f)
    limits = config["rehearse"]["limits"]
    reported = dict.fromkeys(config["reported"], np.inf)
    got = {"trace": model.trace, "params": model.params}
    compared = driver.compare_call(got, ref, {**limits, **reported}, R, M)
    assert compared["pairs"]["value"] == compared["dropped_pairs"]["value"] == 0.0
    assert compared["later.pairs"]["value"] == 0.0
    assert set(compared) == set(limits) | set(reported)
    assert all(c["value"] <= c["limit"] for c in compared.values())
    # without the ``reported`` keys the later steps' numbers have no limit
    with pytest.raises(KeyError, match="no limit for later"):
        driver.compare_call(got, ref, limits, R, M)
    made_up = {"trace": {"l_main": ref["l_main"], "grad_norm": ref["grad_norm"],
                         "pairs": np.zeros((3, 0)), "dropped": np.zeros((3, 0))},
               "params": {"emb": ref["final"]["emb"]}}
    values = {k: c["value"] for k, c in driver.compare_call(
        made_up, ref, {**limits, **reported}, R, M).items()}
    assert values["pairs"] == values["dropped_pairs"] == values["loss.main"] == 0.0
    assert values["update.embedding"] == 0.0 and values["update.attn"] == np.inf
    with_experts = dict(made_up, trace=dict(made_up["trace"],
                                            pairs=np.ones((3, 4))))
    assert driver.compare_call(with_experts, ref, {**limits, **reported}, R, M)[
        "pairs"]["value"] == np.inf


def test_serving_scores_are_the_references_last_logits(trained):
    """The tied table serves, divided as the loss's logits are; a padded
    history is scored from its last real position."""
    import jax

    seqs, model, ref, _stats = trained
    short = seqs[0].copy()
    short[20:] = 0
    scores = model.next_item_scores(np.stack([seqs[0], short]))
    np.testing.assert_allclose(
        scores[0], R.next_item_logits(ref["final"], seqs[0], M), atol=2e-5)
    with jax.default_matmul_precision("highest"):
        h = R.trunk(ref["final"], seqs[0], M)
        last = R._norm(h[19], ref["final"]["lnf_g"], M["rms_norm_eps"])
        np.testing.assert_allclose(
            scores[1], R._dot(last, ref["final"]["emb"].T, None) * 0.125, atol=2e-5)


def test_the_counters_reach_the_trace_the_stats_and_train_json(trained):
    """``ssm_head_blocks`` is the turns of the scans' maps: 32 heads in one
    group at 16 a turn are 2 a mamba mixer; ``ssm_chunks`` is the cost
    function's count. Both stand in ``/train.json`` once a recorder listens."""
    from pio_tpu.obs import trainwatch

    _seqs, model, _ref, stats = trained
    assert seq_layers.SSM_HEAD_BLOCK == 16
    np.testing.assert_array_equal(model.trace["ssm_head_blocks"], [2 * 2.0] * 3)
    want = granite_cost.ssm_chunks(M, rows=2, seq_len=T, steps=3)
    assert want == 3 * 2 * 2 * (T // 8) == model.trace["ssm_chunks"].sum()
    counters = stats["counters"]
    assert counters["ssm_head_blocks"] == 12.0 and counters["ssm_chunks"] == want
    assert counters["ssm_state_absmax"] == model.trace["ssm_state_absmax"].max() > 0
    rec = trainwatch.StepRecorder(run_id="r", engine_id="e")
    trainwatch.activate(rec)
    try:
        assert rec.payload()["counters"] is None
        train_seqrec(None, histories(8, seed=1), V - 1,
                     dataclasses.replace(CFG, seed=3))
        assert rec.payload()["counters"]["ssm_head_blocks"] == 12.0
        assert rec.payload()["counters"]["dropped_pairs"] == 0.0
    finally:
        trainwatch.deactivate()


@pytest.mark.parametrize("heads,groups,block,turns", [
    (64, 8, 8, 8), (64, 8, 16, 8),  # the Nemotron cell's shape: a turn is a group
    (64, 1, 8, 8),    # this cell's: one group of 64 heads, 8 turns a layer
    (64, 1, 16, 4), (64, 1, 32, 2),
])
def test_the_head_blocks_at_the_cells_shapes(heads, groups, block, turns, monkeypatch):
    """The counter at the two cells' head and group counts (small widths): 32
    a step over the Nemotron cell's 4 layers whatever the block (a turn is a
    group), 72 over this cell's 9 at 8 heads a turn, 36 at the 16 chosen."""
    import jax

    monkeypatch.setattr(seq_layers, "SSM_HEAD_BLOCK", block)
    cfg = dataclasses.replace(CFG, ssm_heads=heads, ssm_head_dim=2,
                              ssm_groups=groups, ssm_state=4)
    blk = {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in seq_layers._mamba_leaves(1, cfg).items()},
        5)["b"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, cfg.d_model))
    _out, counters = seq_layers.mamba(blk, h, cfg)
    assert float(counters["ssm_head_blocks"]) == turns
    assert groups != 8 or turns * 4 == 32
    assert groups != 1 or turns * 9 == {8: 72, 16: 36, 32: 18}[block]


def test_it_trains_and_serves_from_engine_json_params():
    """Through ``SeqRecAlgorithm`` from a JSON object: the pattern a list with
    no ``moe`` entry, the five new fields as engine.json gives them."""
    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.parallel.context import ComputeContext
    from pio_tpu.templates.sequence import (PreparedData, Query,
                                            SeqRecAlgorithm, SeqRecParams)

    params = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
    params = json.loads(json.dumps(dict(params, steps=2)))
    assert isinstance(params["mixer_pattern"], list) and "moe" not in params[
        "mixer_pattern"]
    algo = SeqRecAlgorithm(params_from_dict(SeqRecParams, params))
    assert algo.params.mixer_pattern == PATTERN and algo.params.tied_head is True
    seqs = histories(4, seed=2)
    pd = PreparedData(item_index=BiMap({f"i{i}": i for i in range(V - 1)}),
                      sequences=seqs, user_rows={f"u{r}": r for r in range(4)})
    model = algo.train(ComputeContext(mesh=None), pd)
    assert model.model.trace["pairs"].shape == (2, 0)
    assert set(model.shard_arrays()) == set(seq_layers.describe_params(V, CFG))
    result = algo.predict(model, Query(user="u1", num=3))
    assert len(result.item_scores) == 3


# ------------------------------------------------------- mesh and the scopes
def test_a_mesh_with_a_vocabulary_axis_equals_the_single_device(trained):
    """The tied table's rows sharded over ``model``, rows of the batch over
    ``data``; every mixer whole on every device. ``ssm_head_blocks`` counts
    the turns of every device's maps."""
    from pio_tpu.parallel.mesh import MeshSpec, build_mesh

    seqs, single, ref, _stats = trained
    meshed = train_seqrec(build_mesh(MeshSpec(data=2, model=4)), seqs, V - 1, CFG)
    for key in ("l_main", "grad_norm", "ssm_chunks", "ssm_state_absmax"):
        np.testing.assert_allclose(meshed.trace[key], single.trace[key], rtol=1e-5)
    np.testing.assert_allclose(meshed.trace["l_main"], ref["l_main"], rtol=2e-5)
    assert meshed.trace["pairs"].shape == (3, 0)
    np.testing.assert_array_equal(meshed.trace["ssm_head_blocks"],
                                  2 * single.trace["ssm_head_blocks"])
    assert meshed.params["emb"].shape == (V, CFG.d_model)
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    errors = group_errors(flat(meshed.params), ref["final"], update)
    assert max(errors.values()) < 2e-3, errors


def test_the_scopes_the_metrics_read_are_in_the_compiled_step():
    """Every scope a ``gra.*`` reader names reaches the compiled program's op
    names, the backward pass's too; the ``mlp`` mixers stand under
    ``seq.ffn``; no expert scope is left."""
    import re

    import jax

    from granite_small import program_loss
    from pio_tpu.obs.profile import scope_path

    rows = histories(2)
    params = seqrec.init_params(V, CFG)
    text = jax.jit(jax.grad(lambda p: program_loss(p, rows))).lower(
        params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    paths = {scope_path(n, "seq.") for n in names} - {None}
    read = {"seq.ssm/proj", "seq.ssm/conv", "seq.ssm/ssd", "seq.ssm/norm",
            "seq.gqa/attn/full", "seq.gqa/proj", "seq.ffn", "seq.head"}
    assert read <= paths, sorted(paths)
    assert not [p for p in paths if "seq.moe" in p or "seq.gqa/gate" in p]
    assert [n for n in names if "transpose(" in n and "seq.ffn" in n]
