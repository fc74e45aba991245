"""``run.py --rehearse`` on the sequence cell end to end, the control, and
the planted faults."""

import functools
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout
from unittest import mock

import numpy as np
import pytest

from conftest import BENCH, ROOT

import run

CELL = "glm47flash-ep8.train-seq"
CONFIG = os.path.join(BENCH, "configs", "glm47flash-ep8.json")


def rehearse(trace, seed=2 ** 31 + 7):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "0.2", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contracts_keys(trace):
    proc = rehearse(trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared" and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    config = json.load(open(CONFIG))
    assert set(line["compared"]) == set(config["limits"])
    # what no limit can part from the control is read and shown, not judged
    later = {"later." + k for k in config["limits"]
             if k == "pairs" or k.startswith("grad.")}
    assert set(line["info"]["reported_without_limit"]) == set(
        config["reported"]) == {"loss.mtp"} | later
    # the reference's per-step numbers stand beside the call's
    ours, theirs = (line["info"][k] for k in ("trace_of_last_call",
                                              "trace_of_reference"))
    assert all(np.shape(ours[k]) == np.shape(theirs[k]) for k in theirs)
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    if trace:  # a CPU run reads no device scope, cost share or compile count:
        # the program's own counter is all it can report
        assert line["metrics"] == {"rehearsal.seq.moe_dropped_pairs":
                                   {"value": 0.0, "unit": "count"}}
        assert "pack_s" in line["info"]["stats"]
    else:
        assert set(line["metrics"]) == {"rehearsal.train_edges_per_s",
                                        "rehearsal.setup_s"}


def test_without_a_chip_there_is_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_the_configuration_copies_the_catalogs_numbers():
    """Every published key is in the file; the three cut keys are the ones
    ``reduced`` lists, with the published values and the deployment beside."""
    with open(CONFIG) as f:
        config = json.load(f)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["glm47flash-ep8"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64, "vocab_size": 154880}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    widths = {"hidden_size": 2048, "intermediate_size": 10240,
              "moe_intermediate_size": 1536, "q_lora_rank": 768,
              "kv_lora_rank": 512, "qk_nope_head_dim": 192,
              "qk_rope_head_dim": 64, "v_head_dim": 256,
              "num_attention_heads": 20, "num_experts_per_tok": 4,
              "n_shared_experts": 1, "routed_scaling_factor": 1.8,
              "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
              "rope_theta": 1000000, "rms_norm_eps": 1e-05}
    assert {k: config[k] for k in widths} == widths
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["deployment"]["router_width"] == 64
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]


def test_the_traffic_is_the_issues():
    with open(CONFIG) as f:
        config = json.load(f)
    driver = run.load_module("drivers", "train_seq")
    a, data = config["algorithm_params"], config["data"]
    assert (a["steps"], a["batch_size"], a["max_len"]) == (8, 2, 8192)
    assert (data["n_histories"], data["history_len"], data["n_items"]) == (
        64, 8192, 19359)
    small = dict(data, n_histories=4, history_len=4096)
    rows = driver.histories(small, 2 ** 31 + 5)
    assert rows.shape == (4, 4096) and rows.dtype == np.int32
    assert rows.min() >= 1 and rows.max() <= 19359  # full rows, no padding
    assert np.array_equal(rows, driver.histories(small, 2 ** 31 + 5))
    assert not np.array_equal(rows, driver.histories(small, 2 ** 31 + 6))
    # Zipf(1.0): the top item near 1 / H(19359) = 9.6% of the events
    assert 0.07 < np.mean(rows == 1) < 0.13


def _drive(tmp_path, seed=77, config=None):
    import argparse
    import time

    config = config or run.load_json(CONFIG)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    args = argparse.Namespace(seed=seed, seconds=0.05, trace=0, rehearse=True)
    return run.load_module("drivers", "train_seq").run(
        cell={"name": CELL, "chips": 1}, config=config,
        traffic=run.load_json(BENCH, "traffic", "train-seq.json"), args=args,
        t_start=time.monotonic(), e2e=manifest["end_to_end"],
        per_layer=manifest["per_layer"],
        load_reader=lambda name: run.load_module("layer_metrics", name),
        out_dir=str(tmp_path / "bench_out"),
    )


def _one_step_fewer(real):
    def train_seqrec(mesh, sequences, n_items, config, **kw):
        import dataclasses
        return real(mesh, sequences, n_items=n_items,
                    config=dataclasses.replace(config, steps=config.steps - 1),
                    **kw)
    return train_seqrec


def _bias_never_moves(real):
    def train_seqrec(mesh, sequences, n_items, config, **kw):
        import dataclasses
        return real(mesh, sequences, n_items=n_items,
                    config=dataclasses.replace(config, bias_update_rate=0.0),
                    **kw)
    return train_seqrec


def _head_rows_swapped(real):
    def train_seqrec(*a, **kw):
        model = real(*a, **kw)
        model.params["head"][[3, 4]] = model.params["head"][[4, 3]]
        return model
    return train_seqrec


@pytest.mark.parametrize("fault", [_one_step_fewer, _bias_never_moves,
                                   _head_rows_swapped])
def test_a_broken_timed_path_reads_not_correct(fault, tmp_path):
    from pio_tpu.templates import sequence

    with mock.patch.object(sequence, "train_seqrec",
                           fault(sequence.train_seqrec)):
        line = _drive(tmp_path)
    assert line["correct"] is False
    assert any(not c["value"] <= c["limit"] for c in line["compared"].values())


def test_a_number_without_a_limit_has_to_be_named(tmp_path):
    """A number the configuration neither limits nor names under
    ``reported`` stops the run: none goes unjudged by oversight."""
    config = run.load_json(CONFIG)
    del config["reported"]
    with pytest.raises(KeyError, match="no limit for loss.mtp"):
        _drive(tmp_path, seed=6, config=config)


def test_a_call_that_raises_is_a_failure_and_not_correct(tmp_path):
    from pio_tpu.templates import sequence

    def boom(*a, **kw):
        raise RuntimeError("planted")

    with mock.patch.object(sequence, "train_seqrec", boom):
        line = _drive(tmp_path, seed=5)
    assert line["correct"] is False and line["failed"] == line["attempted"] >= 1


def test_the_first_step_is_limited_and_the_later_steps_are_reported():
    """``grad.*`` and ``pairs`` hold the first step, where both sides stand on
    the same weights; what the steps after it read goes under ``later.*``,
    which the configuration reports without a limit."""
    import seq_reference

    driver = run.load_module("drivers", "train_seq")
    n = len(seq_reference.GROUPS)
    ref = {"l_main": np.full(3, 9.0), "l_mtp": np.full(3, 9.0),
           "pairs": np.full((3, 2), 100.0), "grad_norm": np.ones((3, n))}
    trace = {"l_main": ref["l_main"], "l_mtp": ref["l_mtp"],
             "pairs": ref["pairs"] + [[1, -1], [0, 0], [30, 10]],
             "grad_norm": ref["grad_norm"] * [[1.01], [1.5], [0.9]],
             "dropped": [0.0]}
    config = run.load_json(CONFIG)
    limits = dict(config["limits"], **dict.fromkeys(config["reported"], np.inf))
    got = driver.compare_call({"trace": trace, "params": {}}, ref, limits)
    values = {k: c["value"] for k, c in got.items()}
    assert values["pairs"] == pytest.approx(0.01)
    assert values["later.pairs"] == pytest.approx(0.2)
    for group in seq_reference.GROUPS:
        assert values[f"grad.{group}"] == pytest.approx(0.01)
        assert values[f"later.grad.{group}"] == pytest.approx(0.5)
    assert not any(k.startswith("later.") for k in config["limits"])
    # one step alone has nothing later, and a trace of another length is no pass
    one = {k: v[:1] for k, v in ref.items()}
    assert not any(k.startswith("later.") for k in driver.compare_call(
        {"trace": {k: np.asarray(v)[:1] for k, v in trace.items()},
         "params": {}}, one, limits))
    assert driver.compare_call({"trace": trace, "params": {}}, one,
                               limits)["grad.mla"]["value"] == np.inf


@functools.cache
def _readings():
    """One rehearsal of ``seq_readings.py`` for the tests below (a plain
    function: ``tests/test_benchmarks.py`` hands tier-1 the test functions
    of this file, not its fixtures)."""
    import seq_readings

    out = io.StringIO()
    with redirect_stdout(out):
        seq_readings.main(["--workload", CELL, "--seeds", "9",
                           "--control-seeds", "9", "--program", "0",
                           "--faults", "bias_ignored,topk_not_normalised,"
                           "scale_one,rope_on_nope,mtp_left_out,"
                           "expert_dropped:1,half_batch:1", "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_control_and_the_faults_read_not_correct():
    """The reference with fp8's mantissa, put in the program's place, fails
    the rehearsal's limits, and so does each planted fault, the two that
    trained one step only among them."""
    import seq_reference

    readings = _readings()
    with open(CONFIG) as f:
        limits = json.load(f)["rehearse"]["limits"]
    names = ["control"] + ["fault_" + f for f in seq_reference.FAULTS]
    assert len(names) == 8
    for name in names:
        got = readings[name]
        assert any(got[k] > limits[k] for k in limits), (name, got)
    assert readings["fault_expert_dropped"]["steps"] == 1
    # an expert that never trains: a quarter of the routed experts' weights
    # stand still while Adam moves the rest by a step each
    assert readings["fault_expert_dropped"]["update.routed_experts"] > 0.4


def test_the_witness_reads_what_rounding_alone_does():
    """The reference with bfloat16 operands lies nearer than the control in
    every number, and its first Adam step is off by what its gradients' sign
    flips predict: an entry whose sign turns moves the wrong way by a whole
    step, however small the gradient's error."""
    readings = _readings()
    witness, control = readings["witness"], readings["control"]
    assert all(witness[k] <= control[k] for k in witness)
    for name in ("witness_why", "control_why"):
        for group, why in readings[name].items():
            assert why["grad_rel"] < 0.1, (name, group)
            assert why["update_1"] < 1.1 * why["predicted"] + 0.01, (name, group)
    worst = max(readings["control_why"].values(), key=lambda w: w["update_1"])
    assert worst["update_1"] > 3 * worst["grad_rel"]
    assert set(readings["witness_leaves"]) >= {"emb", "head", "blocks/router_w"}


@pytest.mark.parametrize("bits", [7, 3])
def test_the_traced_rounding_is_reduce_precision(bits):
    import jax
    import jax.numpy as jnp
    import seq_reference

    a = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * jnp.exp(
        5 * jax.random.normal(jax.random.PRNGKey(1), (4096,)))
    want = jax.lax.reduce_precision(a, 8, bits)
    assert jnp.array_equal(
        jax.jit(seq_reference._rounded)(a, jnp.int32(bits)), want)
    ours = jax.grad(lambda a: (seq_reference._rounded(a, jnp.int32(bits)) * a).sum())
    theirs = jax.grad(lambda a: (jax.lax.reduce_precision(a, 8, bits) * a).sum())
    assert jnp.array_equal(ours(a), theirs(a))
