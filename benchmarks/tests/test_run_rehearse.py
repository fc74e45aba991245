"""``run.py --rehearse`` end to end, the control, and the planted faults."""

import argparse
import dataclasses
import importlib
import io
import json
import os
import subprocess
import sys
import time
from contextlib import redirect_stdout
from unittest import mock

import numpy as np
import pytest

from conftest import BENCH, ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "als-ml25m-r64.train"
# the implicit configuration is no cell (PERF.md section 7); its file drives
# the same harness here, so the path a later PR needs stays alive
IMPLICIT = os.path.join(BENCH, "tests", "data", "ials-ml25m-r64.json")
CONFIGS = {
    "explicit": os.path.join(BENCH, "configs", "als-ml25m-r64.json"),
    "implicit": IMPLICIT,
}


def drive(config_file, seed, tmp_path, trace=0):
    """One rehearsal run of the train driver on a configuration file: what
    ``run.main`` does after it has looked the cell up."""
    import run

    with open(config_file) as f:
        config = json.load(f)
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = {"name": config["name"] + ".train", "chips": 1}
    traffic = run.load_json(BENCH, "traffic", "train.json")
    args = argparse.Namespace(seed=seed, seconds=0.05, trace=trace,
                              rehearse=True)
    return run.load_module("drivers", "train").run(
        cell=cell, config=config, traffic=traffic, args=args,
        t_start=time.monotonic(), e2e=manifest["end_to_end"],
        per_layer=manifest["per_layer"],
        load_reader=lambda name: run.load_module("layer_metrics", name),
        out_dir=str(tmp_path / "bench_out"),
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contracts_keys(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "0.2", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    # a rehearsal never prints under a device metric's name
    assert line["metrics"] and all(k.startswith("rehearsal.") for k in line["metrics"])
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    assert "compared " in proc.stderr.strip().splitlines()[-1]


def test_without_a_chip_there_is_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def _fewer_steps(real):
    def train_als(ctx, u, i, r, n_users, n_items, config, **kw):
        config = dataclasses.replace(config, iterations=config.iterations - 1)
        return real(ctx, u, i, r, n_users=n_users, n_items=n_items, config=config, **kw)
    return train_als


def _half_the_edges(real):
    def train_als(ctx, u, i, r, n_users, n_items, config, **kw):
        return real(ctx, u[::2], i[::2], r[::2], n_users=n_users,
                    n_items=n_items, config=config, **kw)
    return train_als


def _rows_swapped(real):
    def train_als(*a, **kw):
        f = real(*a, **kw)
        for t in (f.user_factors, f.item_factors):
            t[[3, 4]] = t[[4, 3]]
        return f
    return train_als


@pytest.mark.parametrize("kind", sorted(CONFIGS))
@pytest.mark.parametrize("fault", [_fewer_steps, _half_the_edges, _rows_swapped])
def test_a_broken_timed_path_reads_not_correct(kind, fault, tmp_path):
    """The harness's look for a chip skipped (``--rehearse``), the rest of a
    run driven with the timed path broken underneath."""
    with open(CONFIGS[kind]) as f:
        module = importlib.import_module(json.load(f)["program"]["module"])
    assert drive(CONFIGS[kind], 77, tmp_path)["correct"] is True
    with mock.patch.object(module, "train_als", fault(module.train_als)):
        line = drive(CONFIGS[kind], 77, tmp_path)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


def test_a_call_that_raises_is_a_failure_and_not_correct(tmp_path):
    from pio_tpu.templates import recommendation

    def boom(*a, **kw):
        raise RuntimeError("planted")

    with mock.patch.object(recommendation, "train_als", boom):
        line = drive(CONFIGS["explicit"], 5, tmp_path)
    assert line["correct"] is False and line["failed"] == line["attempted"] >= 1


def test_the_implicit_path_runs_traced(tmp_path):
    line = drive(IMPLICIT, 2 ** 31 + 3, tmp_path, trace=1)
    assert line["correct"] is True and "rehearsal.feed.pack_s" in line["metrics"]


def test_the_control_and_the_faults_read_not_correct():
    """The reference in the precision below the configuration's (fp8 for
    bf16), put in the program's place, fails the cell's own limits, and so
    does each planted fault, at the rehearsal size."""
    import readings

    out = io.StringIO()
    with redirect_stdout(out):
        readings.main(["--workload", CELL, "--seeds", "9", "--control-seeds",
                       "9", "--program", "0", "--rehearse"])
    got = json.loads(out.getvalue().strip().splitlines()[-1])
    with open(CONFIGS["explicit"]) as f:
        limits = json.load(f)["limits"]
    for name in ("control", "fault_unchanged", "fault_half", "fault_altered"):
        assert any(got[name][k] > limits[k] for k in limits), (name, got[name])
