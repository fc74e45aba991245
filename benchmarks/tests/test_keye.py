"""The Keye-VL-2.0-30B-A3B cell: ``run.py --rehearse`` end to end through
``drivers/train_seq_cfg.py`` with sparse layers (a lightning indexer selects
each query's keys), the configuration's file against the published numbers
and the cell's traffic, ``keye_cost`` against the hand count, the manifest's
entries read by name, each ``key.*`` reader on a made-up reading, and the
control and the planted faults at the rehearsal's size."""

import dataclasses
import functools
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

from conftest import BENCH, ROOT

import run

CELL = "keyevl2-30b-ep8.train-seq-16k"
CONFIG = os.path.join(BENCH, "configs", "keyevl2-30b-ep8.json")
#: Keye-VL-2.0-30B-A3B's published config, every key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_ends_correct_and_labelled(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 13), "--seconds", "0.2", "--trace",
         str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    compared = line["compared"]
    assert {"loss.main", "loss.index", "loss.select", "grad.indexer",
            "update.indexer", "pairs", "dropped_pairs"} <= set(compared)
    stats = line["info"]["stats"]
    if trace:
        assert stats["attn_impl"] == {"sparse": "xla"}
        counters = stats["counters"]
        t, k = 32, 12  # the rehearsal's row and top-k
        per_row = sum(min(i + 1, k) for i in range(t))
        assert counters["selected_pairs"] == 2 * 3 * per_row
        assert line["metrics"]["rehearsal.key.selected_pct"]["value"] == (
            pytest.approx(100 * per_row / (t * (t + 1) / 2)))
    else:
        assert set(line["metrics"]) == {"rehearsal.train_edges_per_s",
                                        "rehearsal.setup_s"}


def test_the_configuration_copies_the_published_numbers():
    """Every key of the published config under the same key, equal but for the
    three ``reduced`` keys; the published counts and the deployment stand
    beside them."""
    cfg = config()
    changed = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert changed == sorted(REDUCED)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["keyevl2-30b-ep8"]
    assert entry["reduced"] == REDUCED and entry["file"] == (
        "benchmarks/configs/keyevl2-30b-ep8.json")
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]
            ) == (6, 16, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"], dep["experts_first"],
            dep["parameters_here"]) == (8, 128, 0, 659190016)
    assert "1,024" in dep["expert_load"]
    assert cfg["harness"]["params"]["expert_matmul"] == "gmm"
    assert cfg["rehearse"]["sa_config"]["topk"] < cfg["rehearse"][
        "algorithm_params"]["max_len"]  # the rehearsal's selection drops keys
    assert all(isinstance(a, str) and a for a in cfg["assumed"])


def test_the_traffic_is_the_cells():
    """The accepted ``train-seq-16k`` traffic: 8 steps of one row of 16,384
    events a call, ids 1..18,991 by Zipf(1.0)."""
    cfg = config()
    a, data = cfg["algorithm_params"], cfg["data"]
    assert (a["max_len"], a["steps"], a["batch_size"]) == (16384, 8, 1)
    assert (data["history_len"], data["n_items"], data["zipf_exponent"]) == (
        16384, 18991, 1.0)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keyevl2-30b-ep8", "train-seq-16k", 1)


def test_the_cost_is_the_hand_count():
    """One layer and row at 16,384 events, forward, in GFLOP, counted by
    hand: projections 618.5, the indexer's projections 74.1, its causal
    scores 274.9, the selected pairs 515.4, the held experts 154.6 at a
    balanced router, the router 8.6; the head 1,274.6; 23.44% of the causal
    pairs selected."""
    import keye_cost
    import keye_reference

    m = keye_reference.model(config())
    pairs = 16384 * 8 * 16 / 128 * 6  # a balanced router, six layers
    cost = keye_cost.cost(m, 1, 16384, 1, pairs)
    fwd = {k: v * cost["flops"] / 3 / 1e9 for k, v in cost["share"].items()}
    want = {"attn_proj": 618.5 * 6, "index_proj": 74.1 * 6,
            "index": 274.9 * 6, "sparse_attn": 515.4 * 6,
            "experts": 154.6 * 6, "router": 8.6 * 6, "head": 1274.6}
    for name, gflop in want.items():
        assert fwd[name] == pytest.approx(gflop, abs=0.1 * 6), name
    assert keye_cost.selected_pairs(16384, 2048) == 31_458_304
    assert keye_cost.causal_pairs(16384) == 134_225_920
    assert cost["parameters"] == 659_190_016
    index = cost["kernels"]["index"]["flops"] / 3 / 6 / 1e9
    assert index == pytest.approx(274.9, abs=0.1)


#: every metric of the cell: ``(layer, source, unit)``
ALL_METRICS = {
    "key.index_s": ("sequence program", "program_span", "s"),
    "key.select_s": ("sequence program", "program_span", "s"),
    "key.sparse_attn_s": ("sequence program", "program_span", "s"),
    "key.index_loss_s": ("sequence program", "program_span", "s"),
    "key.index_roofline": ("sequence kernels", "program_span", "%"),
    "key.sparse_attn_roofline": ("sequence kernels", "program_span", "%"),
    "key.sparse_blocks_pct": ("sequence kernels", "program_counter", "%"),
    "key.selected_pct": ("sequence kernels", "program_counter", "%"),
    "key.moe_kernel_s": ("sequence kernels", "program_span", "s"),
    "key.head_s": ("sequence program", "program_span", "s"),
    "key.opt_s": ("sequence program", "program_span", "s"),
    "key.unscoped_pct": ("sequence program", "program_span", "%"),
    "key.readback_s": ("sequence program", "program_span", "s"),
    "key.compiles_in_call": ("sequence program", "program_counter", "count"),
    "key.device_peak_gib": ("device", "program_counter", "GiB"),
    "key.compile_s": ("sequence program", "program_counter", "s"),
    "key_program_roofline": ("sequence program", "device_trace", "%"),
    "key.mfu_train": ("whole step", "device_trace", "%"),
    "key.device_idle_pct": ("device", "device_trace", "%"),
}
#: what only a block with sparse layers, or this cost function, reads
OWN = {"key.index_s", "key.select_s", "key.sparse_attn_s", "key.index_loss_s",
       "key.index_roofline", "key.sparse_attn_roofline",
       "key.sparse_blocks_pct", "key.selected_pct"}


def test_the_manifest_holds_the_cell_and_its_layers_metrics():
    """One cell on one chip, the two end-to-end metrics and nineteen
    per-layer metrics that list this cell and no other; no accepted metric
    lists it. Entries are found by name, never by their place in a list."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    cell = cells[CELL]
    assert all(s in cell["why"] for s in ("16,384", "52%", "23.4%", "1,024"))
    assert len(cell["why"]) <= 200
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert {m["name"] for m in manifest["end_to_end"]
            if run.reports(m, cell, set())} == {"train_edges_per_s", "setup_s"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    ours = {m["name"] for m in manifest["per_layer"]
            if run.reports(m, cell, e2e)}
    assert ours == set(ALL_METRICS)
    for name, (layer, source, unit) in ALL_METRICS.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["unit"]) == (layer, source, unit)
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if name == "key.compile_s"
                              else "train_edges_per_s")
        assert m["better"] == ("higher" if "roofline" in name
                               or "mfu" in name else "lower")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert [c["name"] for c in manifest["workloads"]].count(CELL) == 1
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 0


# a made-up reading in the form of a traced run's
MADE_UP = {
    "stats": {
        "device_scope_s": {
            "seq.gqa/proj": 1.0, "seq.dsa/index": 0.9, "seq.dsa/select": 0.4,
            "seq.gqa/attn/sparse": 1.6,
            "seq.gqa/attn/sparse/seq.gqa/attn/sparse": 0.2,
            "seq.dsa/kl": 0.7, "seq.moe/route": 0.3, "seq.moe/experts": 0.1,
            "seq.moe/experts/gmm": 0.5, "seq.head": 0.6, "seq.opt": 0.2},
        "device_unscoped_s": 0.3, "device_busy_s": 6.8,
        "counters": {"pairs_held": 786432.0, "dropped_pairs": 0.0,
                     "selected_pairs": 1_509_998_592.0,
                     "sparse_key_blocks": 25344.0,
                     "causal_key_blocks": 25344.0,
                     "topk_boundary_ties": 3.0},
        "readback_s": 0.35,
        "xla": {"compiles": 4, "compile_s": 80.0, "cache_loads": 1,
                "cache_load_s": 0.5,
                "in_call": {"compiles": 0, "compile_s": 0.0, "cache_loads": 0,
                            "cache_load_s": 0.0}},
    },
    "memory_peak_bytes": 14.5 * 2 ** 30,
    "trace": {"window_s": 7.4, "busy_s": 6.8,
              "module_s": {"jit_init_all": 0.02, "jit_chunk_staged": 6.7}},
    "config": {"device_programs": ["jit_chunk_staged"], "num_hidden_layers": 6,
               "algorithm_params": {"max_len": 16384, "steps": 8,
                                    "batch_size": 1}},
    "peak": {"flops_per_s": 197e12}, "chips": 1,
    "cost": {"flops": 267.6e12}, "least": {"seconds": 1.4},
    "least_index": {"seconds": 0.067}, "least_sparse_attn": {"seconds": 0.126},
}
MADE_UP_READS = {
    "key.index_s": 0.9, "key.select_s": 0.4, "key.sparse_attn_s": 1.8,
    "key.index_loss_s": 0.7, "key.index_roofline": 100 * 0.067 / 0.9,
    "key.sparse_attn_roofline": 100 * 0.126 / 1.8,
    "key.sparse_blocks_pct": 100.0,
    "key.selected_pct": 100 * 31_458_304 / 134_225_920,
    "key.moe_kernel_s": 0.5, "key.head_s": 0.6, "key.opt_s": 0.2,
    "key.unscoped_pct": 100 * 0.3 / 6.8, "key.readback_s": 0.35,
    "key.compiles_in_call": 0, "key.device_peak_gib": 14.5,
    "key.compile_s": 80.5, "key_program_roofline": 100 * 1.4 / 6.7,
    "key.mfu_train": 100 * 267.6e12 / (7.4 * 197e12),
    "key.device_idle_pct": 100 * (1 - 6.8 / 7.4),
}


def reader(name):
    return run.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(ALL_METRICS))
def test_a_reader_on_a_made_up_reading(name):
    value = reader(name).read(MADE_UP)
    assert value == pytest.approx(MADE_UP_READS[name], rel=1e-9)
    if ALL_METRICS[name][2] == "%":
        assert 0 < value <= 100


def test_the_selected_share_is_the_hand_counts():
    assert reader("key.selected_pct").read(MADE_UP) == pytest.approx(23.44,
                                                                      abs=0.005)


@pytest.mark.parametrize("name", sorted(ALL_METRICS))
def test_a_reader_finds_nothing_to_read_and_says_none(name):
    """A program without the sparse layers' scopes and counters (the
    parent's), a run without a chip, a trace that was not taken: ``None``,
    never 0 and never a raise, so the line leaves the metric out."""
    bare = {"stats": {}, "trace": None, "config": MADE_UP["config"],
            "peak": None, "chips": 1}
    assert reader(name).read(bare) is None
    if name not in OWN:
        return  # another block's scope or counter reads here too: it is shared
    other = dict(MADE_UP, stats={
        "device_scope_s": {"seq.mla/attn": 2.0, "seq.ffn": 1.0},
        "counters": {"dropped_pairs": 0.0}})
    for key in ("cost", "least", "least_index", "least_sparse_attn"):
        other.pop(key)
    assert reader(name).read(other) is None


@functools.cache
def _readings():
    import keye_reference
    import seq_cfg_readings

    out = io.StringIO()
    with redirect_stdout(out):
        seq_cfg_readings.main([
            "--workload", CELL, "--seeds", "7", "--control-seeds", "7",
            "--program", "0", "--faults",
            ",".join(f + ":1" for f in keye_reference.FAULTS), "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


#: a first-step limit each planted fault fails at the rehearsal's size, in a
#: group of its own kind (the rehearsal's row is shorter than 1,024 keys, so
#: ``topk_1024`` is dense attention there)
FAILS = {
    "dense_attention": "grad.attn", "topk_1024": "grad.attn",
    "relu_left_out": "grad.indexer", "ties_to_later": "grad.indexer",
    "target_attached": "grad.attn", "qk_norm_left_out": "grad.attn",
    "selection_per_kv_head": "grad.indexer",
    "topk_not_normalised": "grad.router",
    "expert_dropped": "grad.routed_experts",
}


def test_the_control_and_every_planted_fault_read_not_correct():
    import keye_reference

    readings = _readings()
    limits = config()["rehearse"]["limits"]
    assert set(FAILS) == set(keye_reference.FAULTS)
    assert any(readings["control"][k] > limits[k] for k in limits)
    assert readings["control"]["loss.index"] > limits["loss.index"]
    for fault, key in FAILS.items():
        got = readings["fault_" + fault]
        assert got[key] > limits[key], (fault, key, got)
    # the selection's checksum fails where the tie rule is broken
    assert readings["fault_ties_to_later"]["loss.select"] > limits["loss.select"]


def test_the_witness_lies_nearer_than_the_control():
    readings = _readings()
    witness, control = readings["witness"], readings["control"]
    worse = [k for k in witness if witness[k] > control[k] and control[k] > 0]
    assert not [k for k in worse if k.startswith(("loss.", "update."))], worse


def test_a_program_without_sparse_layers_exits_at_once_on_the_cell():
    """A program without the sparse layers' four fields (``SeqRecParams``
    before them) refuses the cell's params before any device work:
    ``ParamsError: unknown params``, not a hang. The current one binds them."""
    from pio_tpu.controller.params import Params, ParamsError, params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    driver = run.load_module("drivers", "train_seq_cfg")
    cfg = config()
    params = driver.algorithm_params(cfg, driver.reference_module(cfg).model(cfg), 1)
    assert params_from_dict(SeqRecParams, params).layer_pattern == ("sparse",)
    new = ("attn_qk_norm", "index_head_dim", "index_heads", "index_topk")
    assert set(new) <= set(params)
    parents = dataclasses.make_dataclass(
        "SeqRecParams", [(f.name, f.type, f) for f in dataclasses.fields(
            SeqRecParams) if f.name not in new],
        bases=(Params,), frozen=True,
        module="pio_tpu.models.seqrec")  # where the fields' annotations resolve
    with pytest.raises(ParamsError, match=r"unknown params \['attn_qk_norm', "
                       r"'index_head_dim', 'index_heads', 'index_topk'\]"):
        params_from_dict(parents, params)
