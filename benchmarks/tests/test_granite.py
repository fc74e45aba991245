"""The Granite 4.0-H Micro cell (PR 39): ``run.py --rehearse`` end to end
through ``drivers/train_seq_cfg.py`` with a model that has no expert anywhere,
the configuration's file against the catalog's numbers and the issue's
traffic, ``granite_cost`` against a hand count, each ``gra.*`` reader on a
made-up reading, and the control and the planted faults at the rehearsal's
size."""

import dataclasses
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

CELL = "granite4hmicro-vp8.train-seq-8k"
CONFIG = os.path.join(BENCH, "configs", "granite4hmicro-vp8.json")
GROUPS = ("embedding", "ssm_proj", "ssm_scan", "attn", "dense_mlp", "norms")
#: the catalog row's ``config``, every key
#: (``/opt/skills/guides/model-configs/architectures.jsonl``, granite-4.0-h-micro)
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]


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
    assert line["compared"]["pairs"] == {"value": 0.0, "limit": 0.0}
    assert line["compared"]["dropped_pairs"] == {"value": 0.0, "limit": 0.0}
    assert set(line["info"]["reported_without_limit"]) == set(cfg["reported"])
    ours, theirs = (line["info"][k] for k in ("trace_of_last_call",
                                              "trace_of_reference"))
    assert all(np.shape(ours[k]) == np.shape(theirs[k]) for k in theirs)
    assert np.shape(theirs["pairs"]) == (3, 0)  # no expert layer on either side
    if trace:  # no chip: no scopes and no peak, so the three counters' readers
        stats = line["info"]["stats"]
        assert stats["experts_impl"] == "none"
        # 3 steps x 1 row x 3 mamba mixers: 32 / 8 chunks, 32 / 16 turns of the map
        assert line["metrics"] == {
            "rehearsal.gra.ssm_chunks": {"value": 36.0, "unit": "count"},
            "rehearsal.gra.ssm_head_blocks": {"value": 18.0, "unit": "count"},
            "rehearsal.gra.ssm_state_absmax": {
                "value": stats["counters"]["ssm_state_absmax"], "unit": "absmax"}}
    else:
        assert set(line["metrics"]) == {"rehearsal.train_edges_per_s",
                                        "rehearsal.setup_s"}


def test_the_configuration_copies_the_catalogs_numbers():
    """Every key of the catalog row's ``config`` stands as published but the
    three ``reduced`` lists, whose published values and the deployment stand
    beside them; no width, head count, group count, state or chunk size is cut."""
    cfg = config()
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["granite4hmicro-vp8"]
    assert entry["reduced"] == REDUCED and len(PUBLISHED) == 33
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    assert {k: cfg[k] for k in PUBLISHED if k not in REDUCED} == {
        k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["layer_types"], cfg["vocab_size"]) == (
        10, PERIOD, 12544)
    # one whole period, the model's own 36 : 4
    assert (PUBLISHED["layer_types"].count("mamba"),
            PUBLISHED["layer_types"].count("attention")) == (36, 4)
    assert [i for i, k in enumerate(PUBLISHED["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    d = cfg["deployment"]
    assert (d["chips_per_layer"], d["vocabulary"], d["pipeline_stages"],
            d["bytes_a_parameter"], d["parameters_here"]) == (
        8, "8-way", 4, 16, 772160448)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert d["parameters_here"] * 16 == pytest.approx(12.35e9, rel=1e-3)
    assert len(cfg["assumed"]) >= 10 and "no router" in cfg["assumed"][0]
    assert len(cfg["limits_from"]) > 200  # every limit's reason is in the file


def test_the_traffic_is_the_issues():
    cfg = config()
    a, data = cfg["algorithm_params"], cfg["data"]
    assert (a["steps"], a["batch_size"], a["max_len"], a["stream"],
            a["learning_rate"]) == (8, 1, 8192, "off", 1e-4)
    assert (data["n_histories"], data["history_len"], data["n_items"],
            data["zipf_exponent"]) == (32, 8192, 12543, 1.0)
    traffic = run.load_json(BENCH, "traffic", "train-seq-8k.json")
    assert (traffic["driver"], traffic["annotation"]) == ("train_seq_cfg", "bench.call")
    driver = run.load_module("drivers", "train_seq_cfg")
    rows = driver.histories(dict(data, n_histories=2, history_len=4096), 2 ** 31 + 5)
    assert rows.min() >= 1 and rows.max() <= 12543
    job_params = driver.algorithm_params(
        cfg, driver.reference_module(cfg).model(cfg), 7)
    assert job_params["mixer_pattern"] == ("mamba", "mlp") * 5 + (
        "attn", "mlp") + ("mamba", "mlp") * 4
    assert (job_params["n_layers"], job_params["ssm_heads"],
            job_params["ssm_head_dim"], job_params["ssm_groups"],
            job_params["ssm_state"], job_params["ssm_chunk"],
            job_params["heads_full"], job_params["kv_heads"],
            job_params["head_dim"], job_params["ffn"]) == (
        20, 64, 64, 1, 128, 256, 32, 8, 64, 8192)
    assert (job_params["embed_scale"], job_params["residual_scale"],
            job_params["attn_scale"], job_params["logit_scale"]) == (
        12.0, 0.22, 0.015625, 0.125)
    assert (job_params["tied_head"], job_params["attn_rope"],
            job_params["attn_gate"]) == (True, False, False)
    # nothing an expert layer alone reads is set
    assert not {"router_kind", "expert_act", "expert_matmul", "n_experts",
                "experts_held"} & set(job_params)


def test_the_cost_is_the_hand_count():
    """At a tiny size by hand, then the cell's own 39.7 Tflop a step."""
    import granite_cost
    import granite_reference

    m = dict(vocab_size=10, hidden_size=4, num_hidden_layers=2,
             mixer_pattern=("mamba", "mlp", "attn", "mlp"), mamba_n_heads=2,
             mamba_d_head=3, mamba_n_groups=1, mamba_d_state=5, mamba_d_conv=4,
             mamba_chunk_size=4, head_dim=2, num_attention_heads=4,
             num_key_value_heads=2, shared_intermediate_size=7)
    # 2 steps x 1 row x 6 events; the chunk clamps to 3, a divisor of 6
    c = granite_cost.cost(m, 1, 6, 2, pairs=0.0)
    tokens, inner, conv = 12, 6, 6 + 2 * 5
    proj = 2 * tokens * 1 * 4 * (inner + conv + 2 + inner)
    scan = tokens * 1 * (2 * 3 * (1 * 5 + 2 * 3) + 4 * 2 * 3 * 5)
    attn_proj = 2 * tokens * 4 * (2 * 4 * 2 + 2 * 2 * 2)
    attn = 2 * (2 * 21) * 4 * (2 + 2)  # pairs x heads x (qk + pv)
    mlp = 2 * tokens * 2 * 3 * 4 * 7
    fwd = proj + scan + attn_proj + attn + mlp + 2 * tokens * 4 * 10
    assert c["flops"] == 3.0 * fwd
    assert c["kernels"]["ssm_scan"]["flops"] == 3.0 * scan
    assert c["kernels"]["attn"]["flops"] == 3.0 * attn
    assert c["kernels"]["ffn"]["flops"] == 3.0 * mlp
    assert c["kernels"]["ssm_scan"]["bytes"] == 3 * tokens * 1 * (
        2 * (3 * inner + 2 * 5) + 4 * 2)
    assert c["kernels"]["attn"]["bytes"] == tokens * 2 * 6 * (4 + 2) * 2
    # three matrices read in 2 B three times, their float32 gradient written,
    # a step; a token's input read and output written, three times
    assert c["kernels"]["ffn"]["bytes"] == 2 * 2 * 3 * 4 * 7 * 10 + (
        3 * tokens * 2 * 2 * 4 * 2)
    assert c["ssm_chunks"] == 2 * 1 * 1 * 2 == granite_cost.ssm_chunks(m, 1, 6, 2)
    assert sum(c["share"].values()) == pytest.approx(1.0)
    assert granite_cost.cost(m, 1, 6, 2, pairs=5.0) == c  # nothing is routed
    real = granite_reference.model(config())
    cell = granite_cost.cost(real, 1, 8192, 8, 0.0)
    assert cell["parameters"] == 772160448
    assert cell["ssm_chunks"] == 8 * 9 * 32
    assert cell["flops"] / 8 / 1e12 == pytest.approx(39.71, abs=0.01)
    assert cell["forward_flops_per_event"] * 8192 / 1e12 == pytest.approx(
        13.24, abs=0.01)
    # an event and layer: 4.26 Mflop for the recurrence at chunk 256, one group
    assert cell["kernels"]["ssm_scan"]["flops"] / (3 * 8 * 8192 * 9) == 4259840
    share = cell["share"]
    assert share["dense_mlp"] == pytest.approx(0.623, abs=0.001)
    assert share["ssm_proj"] == pytest.approx(0.288, abs=0.001)
    assert share["attn"] + share["attn_proj"] == pytest.approx(0.034, abs=0.001)
    assert share["head"] == pytest.approx(0.032, abs=0.001)
    assert share["ssm_scan"] == pytest.approx(0.024, abs=0.001)
    assert share["ssm_proj"] + share["ssm_scan"] == pytest.approx(0.311, abs=0.001)


ALL_METRICS = {  # name -> (layer, source, unit)
    "gra.ssm_scan_s": ("sequence program", "program_span", "s"),
    "gra.ssm_proj_s": ("sequence program", "program_span", "s"),
    "gra.ssm_conv_norm_s": ("sequence program", "program_span", "s"),
    "gra.attn_s": ("sequence program", "program_span", "s"),
    "gra.attn_proj_s": ("sequence program", "program_span", "s"),
    "gra.ffn_s": ("sequence program", "program_span", "s"),
    "gra.head_s": ("sequence program", "program_span", "s"),
    "gra.opt_s": ("sequence program", "program_span", "s"),
    "gra.unscoped_pct": ("sequence program", "program_span", "%"),
    "gra.readback_s": ("sequence program", "program_span", "s"),
    "gra.ssm_scan_roofline": ("sequence kernels", "program_span", "%"),
    "gra.attn_roofline": ("sequence kernels", "program_span", "%"),
    "gra.ffn_roofline": ("sequence kernels", "program_span", "%"),
    "gra.ssm_chunks": ("sequence kernels", "program_counter", "count"),
    "gra.ssm_head_blocks": ("sequence kernels", "program_counter", "count"),
    "gra.ssm_state_absmax": ("sequence kernels", "program_counter", "absmax"),
    "gra.compiles_in_call": ("sequence program", "program_counter", "count"),
    "gra.device_peak_gib": ("device", "program_counter", "GiB"),
    "gra.compile_s": ("sequence program", "program_counter", "s"),
    "gra_program_roofline": ("sequence program", "device_trace", "%"),
    "gra.mfu_train": ("whole step", "device_trace", "%"),
    "gra.device_idle_pct": ("device", "device_trace", "%"),
    # the process timeline of ``setup_s``, through the accepted ``setup.*``
    "gra.to_import_s": ("process", "program_span", "s"),
    "gra.to_context_s": ("process", "program_span", "s"),
    "gra.to_first_call_s": ("process", "program_span", "s"),
    "gra.first_call_s": ("process", "program_span", "s"),
    "gra.first_call_excess_s": ("process", "program_span", "s"),
    "gra.trace_s": ("process", "program_counter", "s"),
    "gra.lower_s": ("process", "program_counter", "s"),
    "gra.load_or_compile_s": ("process", "program_counter", "s"),
    "gra.unexplained_s": ("process", "program_span", "s"),
}
#: what only a block with Mamba-2 mixers, or this cost function, gives a reading
OWN = {"gra.ssm_scan_s", "gra.ssm_proj_s", "gra.ssm_conv_norm_s",
       "gra.ssm_scan_roofline", "gra.attn_roofline", "gra.ffn_roofline",
       "gra.attn_s", "gra.attn_proj_s", "gra.ssm_chunks", "gra.ssm_head_blocks",
       "gra.ssm_state_absmax"}


def test_the_manifest_holds_the_cell_and_its_layers_metrics():
    """One cell on one chip, two end-to-end metrics and thirty-one per-layer
    metrics that list this cell and no other (the issue's twenty-two and,
    after review, the nine of ``setup_s``'s timeline); no accepted metric
    lists it. (Where they stand in the list is not held.)"""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite4hmicro-vp8", "train-seq-8k", 1)
    assert all(s in cell["why"] for s in (
        "chunk 256", "45%", "31%", "37%", "no experts")) and len(cell["why"]) <= 200
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert {m["name"] for m in manifest["end_to_end"]
            if run.reports(m, cell, set())} == {"train_edges_per_s", "setup_s"}
    ours = [m for m in manifest["per_layer"] if run.reports(m, cell, e2e)]
    assert len(ours) == 31
    assert {m["name"]: (m["layer"], m["source"], m["unit"]) for m in ours
            } == ALL_METRICS
    for m in ours:
        assert m["workloads"] == [CELL]
        assert m["moves"] == (
            "setup_s" if m["name"] == "gra.compile_s" or m["layer"] == "process"
            else "train_edges_per_s")
        assert m["better"] == ("higher" if "roofline" in m["name"]
                               or "mfu" in m["name"] else "lower")
    assert [c["name"] for c in manifest["workloads"]].count(CELL) == 1
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 0


# a made-up reading in the form of a traced run's: scope seconds, counters,
# the trace's window and the cost function's least seconds
MADE_UP = {
    "stats": {
        "device_scope_s": {
            "seq.ssm/proj": 1.1, "seq.ssm/conv": 0.6, "seq.ssm/ssd": 1.0,
            "seq.ssm/ssd/seq.ssm/ssd": 0.3, "seq.ssm/norm": 0.35,
            "seq.gqa/proj": 0.07, "seq.gqa/attn/full": 0.2,
            "seq.gqa/attn/full/seq.gqa/attn/full": 0.1, "seq.ffn": 1.7,
            "seq.head": 0.16, "seq.opt": 0.25},
        "device_unscoped_s": 0.47, "device_busy_s": 6.3,
        "counters": {"pairs_held": 0.0, "dropped_pairs": 0.0,
                     "ssm_chunks": 2304.0, "ssm_head_blocks": 288.0,
                     "ssm_state_absmax": 0.4},
        "readback_s": 0.4,
        "xla": {"compiles": 4, "compile_s": 70.0, "cache_loads": 1,
                "cache_load_s": 0.5,
                "in_call": {"compiles": 0, "compile_s": 0.0, "cache_loads": 0,
                            "cache_load_s": 0.0}},
        "process": {
            "marks": {"process_start": 0.0, "pio_tpu_imported": 0.7,
                      "context_built": 16.2, "first_call_enter": 16.3,
                      "first_call_exit": 101.3},
            "calls": [[1, 16.3, 101.3], [2, 101.4, 106.9]],
            "first_call": {"xla": {"trace_s": 11.0, "lower_s": 3.0,
                                   "compile_s": 60.0, "cache_load_s": 0.5}}},
    },
    "memory_peak_bytes": 14 * 2 ** 30,
    "trace": {"window_s": 6.8, "busy_s": 6.32,
              "module_s": {"jit_init_all": 0.02, "jit_chunk_staged": 6.3}},
    "config": {"device_programs": ["jit_chunk_staged"]},
    "peak": {"flops_per_s": 197e12}, "chips": 1,
    "cost": {"flops": 317.7e12}, "least": {"seconds": 1.7},
    "least_ssm_scan": {"seconds": 0.038}, "least_attn": {"seconds": 0.034},
    "least_ffn": {"seconds": 1.004},
}
MADE_UP_READS = {
    "gra.ssm_scan_s": 1.3, "gra.ssm_proj_s": 1.1, "gra.ssm_conv_norm_s": 0.95,
    "gra.attn_s": 0.3, "gra.attn_proj_s": 0.07, "gra.ffn_s": 1.7,
    "gra.head_s": 0.16, "gra.opt_s": 0.25,
    "gra.unscoped_pct": 100 * 0.47 / 6.3, "gra.readback_s": 0.4,
    "gra.ssm_scan_roofline": 100 * 0.038 / 1.3,
    "gra.attn_roofline": 100 * 0.034 / 0.3,
    "gra.ffn_roofline": 100 * 1.004 / 1.7,
    "gra.ssm_chunks": 2304.0, "gra.ssm_head_blocks": 288.0,
    "gra.ssm_state_absmax": 0.4, "gra.compiles_in_call": 0,
    "gra.device_peak_gib": 14.0, "gra.compile_s": 70.5,
    "gra_program_roofline": 100 * 1.7 / 6.3,
    "gra.mfu_train": 100 * 317.7e12 / (6.8 * 197e12),
    "gra.device_idle_pct": 100 * (1 - 6.32 / 6.8),
    "gra.to_import_s": 0.7, "gra.to_context_s": 15.5,
    "gra.to_first_call_s": 0.1, "gra.first_call_s": 85.0,
    "gra.first_call_excess_s": 85.0 - 5.5, "gra.trace_s": 11.0,
    "gra.lower_s": 3.0, "gra.load_or_compile_s": 60.5,
    "gra.unexplained_s": 79.5 - 74.5,
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
    if name not in OWN:
        return  # another block's scope or counter reads here too: it is shared
    other = dict(MADE_UP, stats={
        "device_scope_s": {"seq.mla/attn": 2.0, "seq.ffn": 1.0},
        "counters": {"dropped_pairs": 0.0}})
    for key in ("cost", "least", "least_ssm_scan", "least_attn", "least_ffn"):
        other.pop(key)
    assert reader(name).read(other) is None


def test_the_scopes_tile_the_busy_time_in_the_made_up_reading():
    stats = MADE_UP["stats"]
    total = sum(stats["device_scope_s"].values()) + stats["device_unscoped_s"]
    assert total == pytest.approx(stats["device_busy_s"], rel=1e-3)


@functools.cache
def _readings():
    import granite_reference
    import seq_cfg_readings

    out = io.StringIO()
    with redirect_stdout(out):
        seq_cfg_readings.main([
            "--workload", CELL, "--seeds", "9", "--control-seeds", "9",
            "--program", "0", "--faults",
            ",".join(f + ":1" * (f not in WHOLE_CALL)
                     for f in granite_reference.FAULTS), "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


#: faults trained the whole call: at the first step q and k are random maps of
#: the stream and a rotation hardly moves the attention's gradient norm (the
#: Nemotron cell's finding, PERF.md section 2); the trajectory shows it
WHOLE_CALL = ("rope_applied",)
#: a limit each planted fault fails at the rehearsal's size, in a group of its
#: own kind of layer
FAILS = {
    "state_not_carried": "grad.ssm_proj", "residual_one": "grad.dense_mlp",
    "scale_rsqrt_d": "grad.attn", "logits_not_divided": "loss.main",
    "embedding_not_multiplied": "grad.embedding",
    "head_not_in_table_gradient": "grad.embedding",
    "norm_per_8_heads": "grad.ssm_scan", "conv_taps_reversed": "grad.ssm_scan",
    "rope_applied": "update.attn", "kv_head_mod": "grad.attn",
    "gate_up_exchanged": "grad.dense_mlp",
}


def test_the_control_and_every_planted_fault_read_not_correct():
    """The reference with fp8's mantissa in the program's place fails the
    rehearsal's limits, and so does each of the eleven planted faults (after
    one step, but for ``WHOLE_CALL``), each in a group of its own kind of layer."""
    import granite_reference

    readings = _readings()
    limits = config()["rehearse"]["limits"]
    assert set(FAILS) == set(granite_reference.FAULTS) and len(FAILS) == 11
    assert any(readings["control"][k] > limits[k] for k in limits)
    assert readings["control"]["loss.main"] > limits["loss.main"]
    for fault, key in FAILS.items():
        got = readings["fault_" + fault]
        assert got[key] > limits[key], (fault, key, got)
        assert got["pairs"] == got["dropped_pairs"] == 0.0


def test_the_witness_lies_nearer_than_the_control():
    readings = _readings()
    witness, control = readings["witness"], readings["control"]
    worse = [k for k in witness if witness[k] > control[k] and control[k] > 0]
    assert not [k for k in worse if k.startswith(("loss.", "update."))], worse


def test_the_parent_exits_at_once_on_the_new_cell():
    """A program without this PR's five fields (the parent commit's
    ``SeqRecParams``) refuses the cell's params before any device work:
    ``ParamsError: unknown params``, not a hang. The tree's own binds them."""
    from pio_tpu.controller.params import Params, ParamsError, params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    driver = run.load_module("drivers", "train_seq_cfg")
    cfg = config()
    params = driver.algorithm_params(cfg, driver.reference_module(cfg).model(cfg), 1)
    assert params_from_dict(SeqRecParams, params).mixer_pattern[:2] == (
        "mamba", "mlp")
    new = ("embed_scale", "residual_scale", "attn_scale", "logit_scale",
           "tied_head")
    assert set(new) <= set(params)
    parents = dataclasses.make_dataclass(
        "SeqRecParams", [(f.name, f.type, f) for f in dataclasses.fields(
            SeqRecParams) if f.name not in new], bases=(Params,), frozen=True,
        module="pio_tpu.models.seqrec")  # where the fields' annotations resolve
    with pytest.raises(ParamsError, match=r"unknown params \['attn_scale', "
                       r"'embed_scale', 'logit_scale', 'residual_scale', "
                       r"'tied_head'\]"):
        params_from_dict(parents, params)
