"""The Laguna-S-2.1 cell (PR 33): ``run.py --rehearse`` end to end through
``drivers/train_seq_cfg.py``, the configuration's file against the catalog's
numbers and the issue's traffic, ``laguna_cost`` against a hand count, and
the control and the planted faults at the rehearsal's size."""

import functools
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

from conftest import BENCH, ROOT

import run

CELL = "laguna-s21-ep32.train-seq-16k"
CONFIG = os.path.join(BENCH, "configs", "laguna-s21-ep32.json")
GROUPS = ("embedding", "head", "attn_window", "attn_full", "gate", "router",
          "routed_experts", "shared_expert", "dense_mlp", "norms")


def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_ends_correct_and_labelled(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 9), "--seconds", "0.2", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    cfg = config()
    assert set(line["compared"]) == set(cfg["limits"])
    assert set(line["compared"]) == {"loss.main", "pairs", "dropped_pairs"} | {
        f"{kind}.{g}" for kind in ("grad", "update") for g in GROUPS}
    assert set(line["info"]["reported_without_limit"]) == set(cfg["reported"])
    ours, theirs = (line["info"][k] for k in ("trace_of_last_call",
                                              "trace_of_reference"))
    assert all(np.shape(ours[k]) == np.shape(theirs[k]) for k in theirs)
    if trace:  # no chip: no scopes and no peak, so the counter's reader alone
        counters = line["info"]["stats"]["counters"]
        assert counters["dropped_pairs"] == 0.0
        assert counters["window_tiles"] == counters["causal_tiles"] > 0
        assert line["metrics"] == {"rehearsal.lag.window_tiles_pct": {
            "value": 100.0, "unit": "%"}}  # the rehearsal's window is no cut
    else:
        assert set(line["metrics"]) == {"rehearsal.train_edges_per_s",
                                        "rehearsal.setup_s"}


def test_the_configuration_copies_the_catalogs_numbers():
    """Every published width stands uncut; the five cut keys are the ones
    ``reduced`` lists, with the published counts and the deployment beside."""
    cfg = config()
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["laguna-s21-ep32"]
    assert entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_key_value_heads",
        "num_attention_heads_per_layer"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352,
        "num_key_value_heads": 8,
        "num_attention_heads_per_layer": {"full_attention": 48,
                                          "sliding_attention": 72}}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_key_value_heads"]) == (5, 8, 12544, 4)
    assert cfg["num_attention_heads_per_layer"][:5] == [24, 36, 36, 36, 24]
    widths = {"hidden_size": 3072, "head_dim": 128, "intermediate_size": 12288,
              "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
              "num_experts_per_tok": 10, "sliding_window": 512,
              "num_attention_heads": 48, "rms_norm_eps": 1e-06,
              "moe_routed_scaling_factor": 2.5, "max_position_embeddings": 1048576}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 48
    d = cfg["deployment"]
    assert (d["chips_per_layer"], d["router_width"], d["experts_first"],
            d["kv_heads_first"], d["bytes_a_parameter"]) == (32, 256, 0, 0, 16)
    assert (d["experts"], d["heads"], d["vocabulary"]) == ("32-way", "2-way", "8-way")
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert len(cfg["assumed"]) >= 11
    # groups of 6 and 9 query heads a KV head, as published
    assert (24 // 4, 36 // 4) == (48 // 8, 72 // 8) == (6, 9)


def test_the_traffic_is_the_issues():
    cfg = config()
    a, data = cfg["algorithm_params"], cfg["data"]
    assert (a["steps"], a["batch_size"], a["max_len"], a["stream"]) == (
        8, 1, 16384, "off")
    assert (data["n_histories"], data["history_len"], data["n_items"],
            data["zipf_exponent"]) == (32, 16384, 12543, 1.0)
    traffic = run.load_json(BENCH, "traffic", "train-seq-16k.json")
    assert traffic["driver"] == "train_seq_cfg"
    driver = run.load_module("drivers", "train_seq_cfg")
    rows = driver.histories(dict(data, n_histories=2, history_len=4096), 2 ** 31 + 5)
    assert rows.min() >= 1 and rows.max() <= 12543
    job_params = driver.algorithm_params(
        cfg, driver.reference_module(cfg).model(cfg), 7)
    assert job_params["layer_pattern"] == ("full", "window", "window", "window")
    assert (job_params["heads_full"], job_params["heads_window"],
            job_params["kv_heads"], job_params["n_experts"],
            job_params["experts_held"]) == (24, 36, 4, 256, 8)


def test_the_cost_is_the_hand_count():
    """At a tiny size by hand, then the cell's own 45.0 Tflop a step."""
    import laguna_cost
    import laguna_reference

    assert laguna_cost.visible_pairs(6) == 21
    assert laguna_cost.visible_pairs(6, 4) == 1 + 2 + 3 + 4 + 4 + 4
    assert laguna_cost.visible_pairs(16384, 512) == sum(
        min(t + 1, 512) for t in range(16384))
    m = dict(vocab_size=10, hidden_size=4, intermediate_size=6,
             num_hidden_layers=3, dense_layers=1, layer_pattern=("full", "window"),
             head_dim=2, kv_heads=1, heads_full=2, heads_window=3, sliding_window=4,
             router_width=8, experts_first=0, experts_held=2, num_experts_per_tok=2,
             moe_intermediate_size=5, shared_expert_intermediate_size=5)
    # layers: 0 full dense, 1 window expert, 2 full expert; 2 steps x 1 row x 6
    c = laguna_cost.cost(m, 1, 6, 2, pairs=7.0)
    tokens = 12
    proj = 2 * tokens * (2 * 4 * (2 * 2 * 2 + 2 * 1 * 2 + 2) + 4 * (2 * 3 * 2 + 2 * 1 * 2 + 3))
    full = 2 * (2 * 21) * 2 * 2 * (2 + 2)  # pairs x layers x heads x (qk + pv)
    window = 2 * (2 * 18) * 1 * 3 * (2 + 2)
    fwd = (proj + full + window + 2 * tokens * 3 * 4 * 6 + 2 * tokens * 2 * 3 * 4 * 5
           + 2 * tokens * 2 * 4 * 8 + 2 * 7 * 3 * 4 * 5 + 2 * tokens * 4 * 10)
    assert c["flops"] == 3.0 * fwd
    assert c["kernels"]["attn_full"]["flops"] == 3.0 * full
    assert c["kernels"]["attn_window"]["flops"] == 3.0 * window
    # k and v once a KV head: six operands of H x d and six of H_kv x d
    assert c["kernels"]["attn_full"]["bytes"] == 2 * tokens * 2 * 6 * (2 + 1) * 2
    assert c["kernels"]["attn_window"]["bytes"] == 1 * tokens * 2 * 6 * (3 + 1) * 2
    assert sum(c["share"].values()) == pytest.approx(1.0)
    real = laguna_reference.model(config())
    cell = laguna_cost.cost(real, 1, 16384, 8, 8 * 4 * 5120.0)
    assert cell["parameters"] == 672125952
    assert cell["flops"] / 8 / 1e12 == pytest.approx(45.02, abs=0.01)
    share = cell["share"]
    assert share["attn_full"] == pytest.approx(0.220, abs=0.001)
    assert share["attn_window"] == pytest.approx(0.030, abs=0.001)
    assert share["dense_mlp"] == pytest.approx(0.247, abs=0.001)


LAYER_METRICS = {  # name -> (layer, source)
    "lag.attn_full_s": ("sequence program", "program_span"),
    "lag.attn_window_s": ("sequence program", "program_span"),
    "lag.attn_proj_s": ("sequence program", "program_span"),
    "lag.attn_full_roofline": ("sequence kernels", "program_span"),
    "lag.attn_window_roofline": ("sequence kernels", "program_span"),
    "lag.moe_experts_roofline": ("sequence kernels", "program_span"),
    "lag_program_roofline": ("sequence program", "device_trace"),
    "lag.mfu_train": ("whole step", "device_trace"),
    "lag.window_tiles_pct": ("sequence kernels", "program_counter"),
}


def test_the_manifest_holds_the_cell_and_its_layers_metrics():
    """One cell, two end-to-end metrics, and nine per-layer metrics that list
    this cell and no other; no accepted metric lists it."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s21-ep32", "train-seq-16k", 1)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert {m["name"] for m in manifest["end_to_end"]
            if run.reports(m, cell, set())} == {"train_edges_per_s", "setup_s"}
    ours = [m for m in manifest["per_layer"] if run.reports(m, cell, e2e)]
    assert {m["name"]: (m["layer"], m["source"]) for m in ours} == LAYER_METRICS
    assert manifest["per_layer"][-len(ours):] == ours  # at the end of the list
    for m in ours:
        assert m["workloads"] == [CELL] and m["moves"] == "train_edges_per_s"
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "%")
        assert m["better"] == ("higher" if "roofline" in m["name"]
                               or "mfu" in m["name"] else "lower")


# stats and least seconds of a traced run of the cell on a v5e (my chip run,
# PR 33, seed 2147485003), the seconds rounded
RECORDED = {
    "stats": {
        "device_scope_s": {
            "seq.moe/route": 0.9213, "seq.gqa/proj": 0.9760,
            "seq.gqa/attn/full": 1.0685, "seq.gqa/attn/window": 0.4994,
            "seq.head": 0.3112, "seq.ffn": 0.9598, "seq.gqa/gate": 0.2730,
            "seq.moe/experts": 0.4501, "seq.opt": 0.2181},
        "device_unscoped_s": 0.7388, "device_busy_s": 6.4162,
        "counters": {"pairs_held": 145347.0, "dropped_pairs": 0.0,
                     "window_tiles": 1512.0, "causal_tiles": 12672.0},
    },
    "trace": {"window_s": 7.0871, "busy_s": 6.4456,
              "module_s": {"jit_init_all": 0.0210, "jit_chunk_staged": 6.4165}},
    "config": {"device_programs": ["jit_chunk_staged"]},
    "peak": {"flops_per_s": 197e12}, "chips": 1,
    "cost": {"flops": 359078717030400.0}, "least": {"seconds": 1.8227},
    "least_attn_full": {"seconds": 0.40188},
    "least_attn_window": {"seconds": 0.055629},
    "least_experts": {"seconds": 0.0416},
}
RECORDED_READS = {
    "lag.attn_full_s": 1.0685, "lag.attn_window_s": 0.4994,
    "lag.attn_proj_s": 0.9760 + 0.2730,
    "lag.attn_full_roofline": 100 * 0.40188 / 1.0685,
    "lag.attn_window_roofline": 100 * 0.055629 / 0.4994,
    "lag.moe_experts_roofline": 100 * 0.0416 / 0.4501,
    "lag_program_roofline": 100 * 1.8227 / 6.4165,
    "lag.mfu_train": 100 * 359078717030400.0 / (7.0871 * 197e12),
    "lag.window_tiles_pct": 100 * 63 / 528,
}


def reader(name):
    return run.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(LAYER_METRICS))
def test_a_reader_on_the_recorded_run(name):
    value = reader(name).read(RECORDED)
    assert value == pytest.approx(RECORDED_READS[name], rel=1e-9)
    assert 0 < value < 100


@pytest.mark.parametrize("name", sorted(LAYER_METRICS))
def test_a_reader_finds_nothing_to_read_and_says_none(name):
    """A program without the block's scopes and counters (the parent's), a
    run without a chip, a trace that was not taken: ``None``, never 0 and
    never a raise, so the line leaves the metric out."""
    bare = {"stats": {}, "trace": None, "config": RECORDED["config"],
            "peak": None, "chips": 1}
    assert reader(name).read(bare) is None
    other = dict(RECORDED, stats={
        "device_scope_s": {"seq.mla/attn": 2.0, "seq.ffn": 1.0},
        "counters": {"dropped_pairs": 0.0}})
    for key in ("cost", "least", "least_attn_full", "least_attn_window",
                "least_experts"):
        other.pop(key)
    assert reader(name).read(other) is None


def test_the_tile_of_scopes_sums_to_the_busy_time_in_the_recorded_run():
    stats = RECORDED["stats"]
    total = sum(stats["device_scope_s"].values()) + stats["device_unscoped_s"]
    assert total == pytest.approx(stats["device_busy_s"], rel=1e-3)


@functools.cache
def _readings():
    import seq_cfg_readings

    out = io.StringIO()
    with redirect_stdout(out):
        seq_cfg_readings.main([
            "--workload", CELL, "--seeds", "9", "--control-seeds", "9",
            "--program", "0", "--faults",
            "window_ignored:1,window_256:1,gate_left_out:1,yarn_left_out:1,"
            "whole_head_rotated:1,kv_head_mod:1,topk_not_normalised:1,"
            "scale_one:1,expert_dropped:1,half_batch:1", "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_control_and_every_planted_fault_read_not_correct():
    """The reference with fp8's mantissa in the program's place fails the
    rehearsal's limits, and so does each of the ten planted faults after one
    step, each in a group of its own kind of layer."""
    import laguna_reference

    readings = _readings()
    limits = config()["rehearse"]["limits"]
    assert len(laguna_reference.FAULTS) == 10
    for name in ["control"] + ["fault_" + f for f in laguna_reference.FAULTS]:
        got = readings[name]
        assert any(got[k] > limits[k] for k in limits), (name, got)
    failed = {f: {k for k in limits if readings["fault_" + f][k] > limits[k]}
              for f in laguna_reference.FAULTS}
    assert "grad.attn_window" in failed["window_ignored"]
    assert "grad.attn_window" in failed["window_256"]
    assert "grad.attn_full" not in failed["window_ignored"]
    assert "grad.gate" in failed["gate_left_out"]
    assert failed["yarn_left_out"] and failed["whole_head_rotated"]
    assert "grad.routed_experts" in failed["expert_dropped"]
    assert "pairs" in failed["half_batch"]
    assert readings["control"]["loss.main"] > limits["loss.main"]


def test_the_witness_lies_nearer_than_the_control():
    readings = _readings()
    witness, control = readings["witness"], readings["control"]
    worse = [k for k in witness if witness[k] > control[k] and control[k] > 0]
    assert not [k for k in worse if k.startswith(("loss.", "update."))], worse


def test_the_parent_exits_at_once_on_the_new_cell():
    """A program without the gqa block's fields refuses the cell's params
    before any device work: ``ParamsError: unknown params``, not a hang."""
    from pio_tpu.controller.params import ParamsError, params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    driver = run.load_module("drivers", "train_seq_cfg")
    cfg = config()
    params = driver.algorithm_params(cfg, driver.reference_module(cfg).model(cfg), 1)
    assert params_from_dict(SeqRecParams, params).attention_kind == "gqa"
    with pytest.raises(ParamsError, match="unknown params"):
        params_from_dict(SeqRecParams, dict(params, no_such_field=1))
