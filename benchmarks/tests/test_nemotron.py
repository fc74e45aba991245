"""The Nemotron-3-Nano cell (PR 35): ``run.py --rehearse`` end to end through
``drivers/train_seq_cfg.py``, the configuration's file against the catalog's
numbers and the issue's traffic, ``nemotron_cost`` against a hand count, each
``nem.*`` reader on a made-up reading, and the control and the planted faults
at the rehearsal's size."""

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

CELL = "nemotron3nano-ep16.train-seq-16k"
CONFIG = os.path.join(BENCH, "configs", "nemotron3nano-ep16.json")
GROUPS = ("embedding", "head", "ssm_proj", "ssm_scan", "attn", "router",
          "routed_experts", "shared_expert", "norms")


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
    if trace:  # no chip: no scopes and no peak, so the three counters' readers
        counters = line["info"]["stats"]["counters"]
        assert counters["dropped_pairs"] == 0.0 and counters["ssm_state_absmax"] > 0
        # 3 steps x 1 row x 4 mamba layers x 32 / 8 chunks
        assert line["metrics"] == {
            "rehearsal.nem.ssm_chunks": {"value": 48.0, "unit": "count"},
            "rehearsal.nem.moe_dropped_pairs": {"value": 0.0, "unit": "count"},
            "rehearsal.nem.ssm_state_absmax": {
                "value": counters["ssm_state_absmax"], "unit": "absmax"}}
    else:
        assert set(line["metrics"]) == {"rehearsal.train_edges_per_s",
                                        "rehearsal.setup_s"}


def test_the_configuration_copies_the_catalogs_numbers():
    """Every published width stands uncut; the four cut keys are the ones
    ``reduced`` lists, with the published values and the deployment beside."""
    cfg = config()
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["nemotron3nano-ep16"]
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size"]
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert cfg["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": whole,
        "n_routed_experts": 128, "vocab_size": 131072}
    assert (whole.count("M"), whole.count("E"), whole.count("*")) == (23, 23, 6)
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (
        9, whole[:9], 8, 16384) and whole[:9] == "MEMEM*EME"
    widths = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
        "expand": 2, "num_attention_heads": 32, "num_key_value_heads": 2,
        "head_dim": 128, "intermediate_size": 1856, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
        "num_experts_per_tok": 6, "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 2.5, "norm_eps": 1e-05,
        "layer_norm_epsilon": 1e-05, "rope_theta": 10000,
        "partial_rotary_factor": 1, "max_position_embeddings": 262144,
        "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
        "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
        "norm_topk_prob": True, "use_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "tie_word_embeddings": False,
        "residual_in_fp32": False, "rescale_prenorm_residual": True,
        "model_type": "nemotron_h", "sliding_window": None}
    assert {k: cfg[k] for k in widths} == widths
    d = cfg["deployment"]
    assert (d["chips_per_layer"], d["router_width"], d["experts_first"],
            d["bytes_a_parameter"], d["parameters_here"]) == (
        16, 128, 0, 16, 666963456)
    assert (d["experts"], d["vocabulary"]) == ("16-way", "8-way")
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["published"]["n_routed_experts"]
    assert len(cfg["assumed"]) >= 6 and "NoPE" in cfg["assumed"][0]
    assert cfg["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")


def test_the_traffic_is_the_issues():
    cfg = config()
    a, data = cfg["algorithm_params"], cfg["data"]
    assert (a["steps"], a["batch_size"], a["max_len"], a["stream"],
            a["learning_rate"]) == (8, 1, 16384, "off", 1e-4)
    assert (data["n_histories"], data["history_len"], data["n_items"],
            data["zipf_exponent"]) == (32, 16384, 16383, 1.0)
    traffic = run.load_json(BENCH, "traffic", "train-seq-16k.json")
    assert traffic["driver"] == "train_seq_cfg"
    driver = run.load_module("drivers", "train_seq_cfg")
    rows = driver.histories(dict(data, n_histories=2, history_len=4096), 2 ** 31 + 5)
    assert rows.min() >= 1 and rows.max() <= 16383
    job_params = driver.algorithm_params(
        cfg, driver.reference_module(cfg).model(cfg), 7)
    assert job_params["mixer_pattern"] == (
        "mamba", "moe", "mamba", "moe", "mamba", "attn", "moe", "mamba", "moe")
    assert (job_params["ssm_heads"], job_params["ssm_head_dim"],
            job_params["ssm_groups"], job_params["ssm_state"],
            job_params["ssm_chunk"], job_params["heads_full"],
            job_params["kv_heads"], job_params["n_experts"],
            job_params["experts_held"], job_params["experts_per_token"],
            job_params["shared_experts"]) == (64, 64, 8, 128, 128, 32, 2, 128, 8, 6, 2)
    assert (job_params["expert_act"], job_params["router_kind"],
            job_params["attn_rope"], job_params["attn_gate"]) == (
        "relu2", "sigmoid_bias", False, False)
    # the kernel whose seconds a pair are near the roof's, so that a seed's
    # draw of held pairs does not set the run's level (PERF.md section 6)
    assert job_params["expert_matmul"] == "gmm"


def test_the_cost_is_the_hand_count():
    """At a tiny size by hand, then the cell's own 38.6 Tflop a step."""
    import nemotron_cost
    import nemotron_reference

    m = dict(vocab_size=10, hidden_size=4, num_hidden_layers=4,
             mixer_pattern=("mamba", "moe", "attn", "mamba"), mamba_num_heads=2,
             mamba_head_dim=3, n_groups=1, ssm_state_size=5, conv_kernel=4,
             chunk_size=4, head_dim=2, num_attention_heads=4,
             num_key_value_heads=2, router_width=8, experts_first=0,
             experts_held=2, num_experts_per_tok=2, moe_intermediate_size=5,
             moe_shared_expert_intermediate_size=7)
    # 2 steps x 1 row x 6 events; the chunk clamps to 3, a divisor of 6
    c = nemotron_cost.cost(m, 1, 6, 2, pairs=7.0)
    tokens, inner, conv = 12, 6, 6 + 2 * 5
    proj = 2 * tokens * 2 * 4 * (inner + conv + 2 + inner)
    scan = tokens * 2 * (2 * 3 * (1 * 5 + 2 * 3) + 4 * 2 * 3 * 5)
    attn_proj = 2 * tokens * 4 * (2 * 4 * 2 + 2 * 2 * 2)
    attn = 2 * (2 * 21) * 4 * (2 + 2)  # pairs x heads x (qk + pv)
    fwd = (proj + scan + attn_proj + attn + 2 * tokens * 2 * 4 * 7
           + 2 * tokens * 4 * 8 + 2 * 7 * 2 * 4 * 5 + 2 * tokens * 4 * 10)
    assert c["flops"] == 3.0 * fwd
    assert c["kernels"]["ssm_scan"]["flops"] == 3.0 * scan
    assert c["kernels"]["attn"]["flops"] == 3.0 * attn
    assert c["kernels"]["moe_experts"]["flops"] == 3.0 * 2 * 7 * 2 * 4 * 5
    # x, z, y (and B, C) at 2 B a channel, dt at 4 B a head, three times over
    assert c["kernels"]["ssm_scan"]["bytes"] == 3 * tokens * 2 * (
        2 * (3 * inner + 2 * 5) + 4 * 2)
    assert c["kernels"]["attn"]["bytes"] == tokens * 2 * 6 * (4 + 2) * 2
    assert c["ssm_chunks"] == 2 * 1 * 2 * 2 == nemotron_cost.ssm_chunks(m, 1, 6, 2)
    assert sum(c["share"].values()) == pytest.approx(1.0)
    real = nemotron_reference.model(config())
    cell = nemotron_cost.cost(real, 1, 16384, 8, 8 * 4 * 6144.0)
    assert cell["parameters"] == 666963456
    assert cell["ssm_chunks"] == 8 * 4 * 128
    assert cell["flops"] / 8 / 1e12 == pytest.approx(38.57, abs=0.01)
    # an event and layer: 3.41 Mflop for the recurrence at the published chunk
    assert cell["kernels"]["ssm_scan"]["flops"] / (3 * 8 * 16384 * 4) == 3407872
    share = cell["share"]
    assert share["ssm_proj"] + share["ssm_scan"] == pytest.approx(0.412, abs=0.001)
    assert share["attn"] == pytest.approx(0.171, abs=0.001)
    assert share["shared_expert"] == pytest.approx(0.203, abs=0.001)
    assert share["head"] == pytest.approx(0.112, abs=0.001)


LAYER_METRICS = {  # name -> (layer, source, unit)
    "nem.ssm_scan_s": ("sequence program", "program_span", "s"),
    "nem.ssm_proj_s": ("sequence program", "program_span", "s"),
    "nem.ssm_conv_norm_s": ("sequence program", "program_span", "s"),
    "nem.attn_s": ("sequence program", "program_span", "s"),
    "nem.moe_route_s": ("sequence program", "program_span", "s"),
    "nem.ssm_scan_roofline": ("sequence kernels", "program_span", "%"),
    "nem.attn_roofline": ("sequence kernels", "program_span", "%"),
    "nem.moe_experts_roofline": ("sequence kernels", "program_span", "%"),
    "nem_program_roofline": ("sequence program", "device_trace", "%"),
    "nem.mfu_train": ("whole step", "device_trace", "%"),
    "nem.ssm_chunks": ("sequence kernels", "program_counter", "count"),
    "nem.moe_dropped_pairs": ("sequence program", "program_counter", "count"),
    "nem.ssm_state_absmax": ("sequence kernels", "program_counter", "absmax"),
    "nem.moe_kernel_s": ("sequence kernels", "program_span", "s"),
}
# what the cell shares with the mla/moe cell's readers: its own entries, read
# through theirs (an accepted entry's ``workloads`` is not this PR's to edit)
SHARED_METRICS = {  # name -> (layer, source, unit)
    "nem.unscoped_pct": ("sequence program", "program_span", "%"),
    "nem.ffn_s": ("sequence program", "program_span", "s"),
    "nem.head_s": ("sequence program", "program_span", "s"),
    "nem.opt_s": ("sequence program", "program_span", "s"),
    "nem.moe_experts_s": ("sequence program", "program_span", "s"),
    "nem.device_idle_pct": ("device", "device_trace", "%"),
    "nem.device_peak_gib": ("device", "program_counter", "GiB"),
    "nem.compile_s": ("sequence program", "program_counter", "s"),
    "nem.compiles_in_call": ("sequence program", "program_counter", "count"),
    "nem.readback_s": ("sequence program", "program_span", "s"),
}
ALL_METRICS = {**LAYER_METRICS, **SHARED_METRICS}


def test_the_manifest_holds_the_cell_and_its_layers_metrics():
    """One cell on one chip, two end-to-end metrics, and twenty-four
    per-layer metrics that list this cell and no other (fourteen of what the
    block adds, ten that read what it shares with the mla/moe cell); no
    accepted metric lists it.
    (Where they stand in the list is not held: the next cell's entries come
    behind them, which is what turned ``test_laguna``'s same-named test red.)"""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3nano-ep16", "train-seq-16k", 1)
    assert "42%" in cell["why"] and "17%" in cell["why"] and "1/16" in cell["why"]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert {m["name"] for m in manifest["end_to_end"]
            if run.reports(m, cell, set())} == {"train_edges_per_s", "setup_s"}
    ours = [m for m in manifest["per_layer"] if run.reports(m, cell, e2e)]
    assert {m["name"]: (m["layer"], m["source"], m["unit"]) for m in ours
            } == ALL_METRICS
    for m in ours:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"] == "nem.compile_s"
                              else "train_edges_per_s")
        assert m["better"] == ("higher" if "roofline" in m["name"]
                               or "mfu" in m["name"] else "lower")


# a made-up reading in the form of a traced run's: scope seconds, counters,
# the trace's window and the cost function's least seconds
MADE_UP = {
    "stats": {
        "device_scope_s": {
            "seq.ssm/proj": 1.2, "seq.ssm/conv": 0.25, "seq.ssm/ssd": 2.0,
            "seq.ssm/ssd/seq.ssm/ssd": 0.5, "seq.ssm/norm": 0.15,
            "seq.gqa/proj": 0.2, "seq.gqa/attn/full": 0.5,
            "seq.gqa/attn/full/seq.gqa/attn/full": 0.3, "seq.moe/route": 0.6,
            "seq.moe/experts": 0.4, "seq.ffn": 0.5, "seq.head": 0.35,
            "seq.opt": 0.22},
        "device_unscoped_s": 0.43, "device_busy_s": 7.6,
        # what XLA renamed stands among the unscoped seconds
        "device_renamed_s": {"ragged-dot-none": 0.1},
        "counters": {"pairs_held": 196608.0, "dropped_pairs": 0.0,
                     "ssm_chunks": 4096.0, "ssm_state_absmax": 3.5},
        "readback_s": 0.35,
        "xla": {"compiles": 4, "compile_s": 60.0, "cache_loads": 1,
                "cache_load_s": 0.5,
                "in_call": {"compiles": 0, "compile_s": 0.0, "cache_loads": 0,
                            "cache_load_s": 0.0}},
    },
    "memory_peak_bytes": 15 * 2 ** 30,
    "trace": {"window_s": 8.3, "busy_s": 7.62,
              "module_s": {"jit_init_all": 0.02, "jit_chunk_staged": 7.6}},
    "config": {"device_programs": ["jit_chunk_staged"]},
    "peak": {"flops_per_s": 197e12}, "chips": 1,
    "cost": {"flops": 308.5e12}, "least": {"seconds": 1.9},
    "least_ssm_scan": {"seconds": 0.055}, "least_attn": {"seconds": 0.268},
    "least_moe_experts": {"seconds": 0.05},
}
MADE_UP_READS = {
    "nem.ssm_scan_s": 2.5, "nem.ssm_proj_s": 1.2, "nem.ssm_conv_norm_s": 0.4,
    "nem.attn_s": 0.8, "nem.moe_route_s": 0.6,
    "nem.ssm_scan_roofline": 100 * 0.055 / 2.5,
    "nem.attn_roofline": 100 * 0.268 / 0.8,
    "nem.moe_experts_roofline": 100 * 0.05 / (0.4 + 0.1),
    "nem.moe_kernel_s": 0.1,
    "nem_program_roofline": 100 * 1.9 / 7.6,
    "nem.mfu_train": 100 * 308.5e12 / (8.3 * 197e12),
    "nem.ssm_chunks": 4096.0, "nem.moe_dropped_pairs": 0.0,
    "nem.ssm_state_absmax": 3.5,
    "nem.unscoped_pct": 100 * 0.43 / 7.6, "nem.ffn_s": 0.5, "nem.head_s": 0.35,
    "nem.opt_s": 0.22, "nem.moe_experts_s": 0.4,
    "nem.device_idle_pct": 100 * (1 - 7.62 / 8.3), "nem.device_peak_gib": 15.0,
    "nem.compile_s": 60.5, "nem.compiles_in_call": 0, "nem.readback_s": 0.35,
}


def reader(name):
    return run.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(ALL_METRICS))
def test_a_reader_on_a_made_up_reading(name):
    value = reader(name).read(MADE_UP)
    assert value == pytest.approx(MADE_UP_READS[name], rel=1e-9)
    if ALL_METRICS[name][2] == "%":
        assert 0 < value < 100


@pytest.mark.parametrize("name", sorted(ALL_METRICS))
def test_a_reader_finds_nothing_to_read_and_says_none(name):
    """A program without the block's scopes and counters (the parent's), a
    run without a chip, a trace that was not taken: ``None``, never 0 and
    never a raise, so the line leaves the metric out."""
    bare = {"stats": {}, "trace": None, "config": MADE_UP["config"],
            "peak": None, "chips": 1}
    assert reader(name).read(bare) is None
    if name == "nem.moe_dropped_pairs" or name in SHARED_METRICS:
        return  # another block's scope or counter reads here too: it is shared
    other = dict(MADE_UP, stats={
        "device_scope_s": {"seq.mla/attn": 2.0, "seq.ffn": 1.0},
        "counters": {"dropped_pairs": 0.0}})
    for key in ("cost", "least", "least_ssm_scan", "least_attn",
                "least_moe_experts"):
        other.pop(key)
    assert reader(name).read(other) is None


def test_the_experts_roofline_counts_the_kernel_xla_renamed():
    """The kernel's seconds stand under the name XLA gave it, outside the
    scope; a program that reports no such seconds (one of before, or a
    compiler that keeps the scope) is read by the scope alone."""
    share = reader("nem.moe_experts_roofline").read
    assert share(MADE_UP) == pytest.approx(100 * 0.05 / 0.5)
    stats = {k: v for k, v in MADE_UP["stats"].items()
             if k != "device_renamed_s"}
    before = dict(MADE_UP, stats=stats)
    assert share(before) == pytest.approx(100 * 0.05 / 0.4)
    assert reader("nem.moe_kernel_s").read(before) is None
    other = dict(MADE_UP, stats=dict(stats, device_renamed_s={"copy.7": 0.2}))
    assert reader("nem.moe_kernel_s").read(other) is None


def test_the_pallas_kernel_is_read_inside_its_scope():
    """``expert_matmul="gmm"``: the kernel keeps its JAX name, stands under
    ``seq.moe/experts/gmm`` and is counted once in the scope's seconds."""
    stats = {k: v for k, v in MADE_UP["stats"].items()
             if k != "device_renamed_s"}
    stats["device_scope_s"] = dict(
        stats["device_scope_s"], **{"seq.moe/experts/gmm": 0.1})
    pallas = dict(MADE_UP, stats=stats)
    assert reader("nem.moe_kernel_s").read(pallas) == pytest.approx(0.1)
    assert reader("nem.moe_experts_s").read(pallas) == pytest.approx(0.5)
    assert reader("nem.moe_experts_roofline").read(pallas) == pytest.approx(
        100 * 0.05 / 0.5)


def test_the_scopes_tile_the_busy_time_in_the_made_up_reading():
    stats = MADE_UP["stats"]
    total = sum(stats["device_scope_s"].values()) + stats["device_unscoped_s"]
    assert total == pytest.approx(stats["device_busy_s"], rel=1e-3)


@functools.cache
def _readings():
    import nemotron_reference
    import seq_cfg_readings

    out = io.StringIO()
    with redirect_stdout(out):
        seq_cfg_readings.main([
            "--workload", CELL, "--seeds", "9", "--control-seeds", "9",
            "--program", "0", "--faults",
            ",".join(f + ":1" for f in nemotron_reference.FAULTS), "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


#: a limit each planted fault fails at the rehearsal's size, in a group of its
#: own kind of layer
FAILS = {
    "state_not_carried": "grad.ssm_proj", "conv_left_out": "grad.ssm_scan",
    "dt_bias_ignored": "grad.ssm_scan", "a_log_for_a": "grad.ssm_scan",
    "head_group_mod": "grad.ssm_scan", "norm_over_all_channels": "grad.ssm_scan",
    "norm_before_gate": "grad.ssm_scan", "d_left_out": "grad.ssm_scan",
    "silu_gated_experts": "grad.routed_experts", "rope_applied": "grad.attn",
    "kv_head_mod": "grad.attn", "topk_not_normalised": "grad.router",
    "scale_one": "grad.routed_experts", "bias_ignored": "pairs",
    "expert_dropped": "grad.routed_experts", "half_batch": "pairs",
}


def test_the_control_and_every_planted_fault_read_not_correct():
    """The reference with fp8's mantissa in the program's place fails the
    rehearsal's limits, and so does each of the sixteen planted faults after
    one step, each in a group of its own kind of layer."""
    import nemotron_reference

    readings = _readings()
    limits = config()["rehearse"]["limits"]
    assert set(FAILS) == set(nemotron_reference.FAULTS) and len(FAILS) == 16
    assert any(readings["control"][k] > limits[k] for k in limits)
    assert readings["control"]["loss.main"] > limits["loss.main"]
    for fault, key in FAILS.items():
        got = readings["fault_" + fault]
        assert got[key] > limits[key], (fault, key, got)
    # a fault of the state-space layer leaves the attention layer's own
    # weights' gradient nearer than the layer it is planted in
    got = readings["fault_state_not_carried"]
    assert got["grad.ssm_proj"] > got["grad.attn"]


def test_the_witness_lies_nearer_than_the_control():
    readings = _readings()
    witness, control = readings["witness"], readings["control"]
    worse = [k for k in witness if witness[k] > control[k] and control[k] > 0]
    assert not [k for k in worse if k.startswith(("loss.", "update."))], worse


def test_the_parent_exits_at_once_on_the_new_cell():
    """A program without the fields of a block of single mixers refuses the
    cell's params before any device work: ``ParamsError: unknown params``,
    not a hang."""
    from pio_tpu.controller.params import ParamsError, params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    driver = run.load_module("drivers", "train_seq_cfg")
    cfg = config()
    params = driver.algorithm_params(cfg, driver.reference_module(cfg).model(cfg), 1)
    assert params_from_dict(SeqRecParams, params).mixer_pattern[0] == "mamba"
    with pytest.raises(ParamsError, match="unknown params"):
        params_from_dict(SeqRecParams, dict(params, no_such_field=1))
