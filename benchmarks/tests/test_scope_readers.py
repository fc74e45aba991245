"""The readers of the program's own scopes and compile counts (PR 26)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

import run

CELL = "als-ml25m-r64.train"
SCOPES = {  # a hand-made stats["device_scope_s"]: seconds per scope path
    "als.decode": 0.25,
    "als.pack": 0.5,
    "als.user": 0.125,
    "als.user/als.normal_eq": 1.0,
    "als.user/als.normal_eq/gather": 2.0,
    "als.user/als.normal_eq/outer": 4.0,
    "als.user/als.normal_eq/segment_sum": 8.0,
    "als.user/als.gram": 0.0625,
    "als.user/als.solve": 16.0,
    "als.user/als.solve/cg": 32.0,
    "als.item/als.normal_eq/gather": 64.0,
    "als.item/als.normal_eq/outer": 128.0,
    "als.item/als.normal_eq/segment_sum": 256.0,
    "als.item/als.solve/cg": 512.0,
}
XLA = {"compiles": 7, "compile_s": 3.0, "cache_loads": 2, "cache_load_s": 0.5,
       "in_call": {"compiles": 1, "compile_s": 0.25, "cache_loads": 2,
                   "cache_load_s": 0.125}}
STATS = {"device_scope_s": SCOPES, "device_unscoped_s": 2.0,
         "device_busy_s": 80.0, "xla": XLA}
WANT = {
    "als.solve_s": 16.0 + 32.0 + 512.0,
    "als.gather_s": 2.0 + 64.0,
    "als.outer_s": 4.0 + 128.0,
    "als.segment_sum_s": 8.0 + 256.0,
    "als.pack_decode_s": 0.75,
    "als.unscoped_pct": 2.5,
    "setup.compile_s": 3.5,
    "als.compiles_in_call": 3,
}


def reader(name):
    return run.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_returns_none_where_the_program_reports_nothing(name):
    for trace in (None, {"window_s": 1.0, "busy_s": 1.0}):
        assert reader(name).read({"stats": {}, "trace": trace}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_sums_the_hand_made_scopes(name):
    reading = {"stats": STATS, "trace": {"window_s": 1.0, "busy_s": 1.0}}
    assert reader(name).read(reading) == WANT[name]


def test_compile_counts_of_a_run_without_a_chip_are_not_reported():
    for name in ("setup.compile_s", "als.compiles_in_call"):
        assert reader(name).read({"stats": STATS, "trace": None}) is None


def test_an_unscoped_share_is_never_zero_for_want_of_a_number():
    read = reader("als.unscoped_pct").read
    assert read({"stats": {"device_unscoped_s": 0.0}}) is None
    assert read({"stats": {"device_busy_s": 3.0}}) is None
    assert read({"stats": {"device_unscoped_s": 0.0, "device_busy_s": 3.0}}) == 0.0


def test_the_manifest_holds_the_eight_entries_for_the_one_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in WANT:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL] and entry["layer"] == "device program"
        assert entry["moves"] == ("setup_s" if name == "setup.compile_s"
                                  else "train_edges_per_s")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_a_rehearsal_prints_none_of_them():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 11), "--seconds", "0.2", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metrics"], "the feed's metrics are still there"
    for name in WANT:
        assert not any(name in key for key in line["metrics"]), line["metrics"]
    assert "device_scope_s" not in line["info"]["stats"]
