"""The readers of the sequence trainer's scopes and counters (PR 28)."""

import json
import os

import pytest

from conftest import BENCH, ROOT

import run

CELL = "glm47flash-ep8.train-seq"
ALS_CELL = "als-ml25m-r64.train"
SCOPES = {  # a hand-made stats["device_scope_s"]: seconds per scope path
    "seq.mla/proj": 1.0,
    "seq.mla/attn": 2.0,
    "seq.moe/route": 4.0,
    "seq.moe/experts": 8.0,
    "seq.ffn": 16.0,
    "seq.head": 32.0,
    "seq.opt": 64.0,
    "seq.mtp": 0.5,
    "seq.mtp/seq.mla/proj": 0.25,
    "seq.mtp/seq.mla/attn": 128.0,
    "seq.mtp/seq.moe/route": 256.0,
    "seq.mtp/seq.moe/experts": 512.0,
    "seq.mtp/seq.ffn": 1024.0,
    "seq.mtp/seq.head": 2048.0,
}
XLA = {"compiles": 7, "compile_s": 3.0, "cache_loads": 2, "cache_load_s": 0.5,
       "in_call": {"compiles": 0, "compile_s": 0.0, "cache_loads": 0,
                   "cache_load_s": 0.0}}
STATS = {"device_scope_s": SCOPES, "device_unscoped_s": 2.0,
         "device_busy_s": 80.0, "xla": XLA, "pack_s": 0.125,
         "readback_s": 0.75,
         "counters": {"dropped_pairs": 0.0, "pairs_held": 9.0}}
READING = {
    "stats": STATS,
    "trace": {"window_s": 10.0, "busy_s": 9.0,
              "module_s": {"jit_chunk_staged": 8.0, "jit__init_all": 1.0}},
    "config": {"device_programs": ["jit_chunk_staged"]},
    "peak": {"flops_per_s": 100.0, "bytes_per_s": 10.0}, "chips": 1,
    "memory_peak_bytes": 3 * 2 ** 30,
    "cost": {"flops": 500.0}, "least": {"seconds": 4.0},
    "least_attn": {"seconds": 13.0}, "least_experts": {"seconds": 52.0},
}
WANT = {
    "seq.mla_proj_s": 1.0, "seq.mla_attn_s": 2.0, "seq.moe_route_s": 4.0,
    "seq.moe_experts_s": 8.0, "seq.ffn_s": 16.0, "seq.head_s": 32.0,
    "seq.opt_s": 64.0, "seq.mtp_s": sum(
        s for p, s in SCOPES.items() if p.startswith("seq.mtp")),
    "seq.unscoped_pct": 2.5, "seq.moe_dropped_pairs": 0.0,
    "seq.mla_attn_roofline": 10.0, "seq.moe_experts_roofline": 10.0,
    "seq_program_roofline": 50.0, "seq.mfu_train": 50.0,
    "seq.device_idle_pct": pytest.approx(10.0),
    "seq.device_peak_gib": 3.0, "seq.compile_s": 3.5, "seq.pack_s": 0.125,
    "seq.readback_s": 0.75, "seq.compiles_in_call": 0,
}
SCOPE_READERS = [n for n in WANT if n.endswith("_s") and n not in (
    "seq.compile_s", "seq.pack_s", "seq.readback_s")]


def reader(name):
    return run.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_the_hand_made_reading(name):
    assert reader(name).read(READING) == WANT[name]


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_returns_none_where_the_program_reports_nothing(name):
    """What the parent commit gives: no ``seq.`` scope, no counter, and in a
    rehearsal no trace, cost or peak memory either. ``None``, never 0."""
    bare = {"stats": {}, "trace": None, "config": {"device_programs": []},
            "peak": None, "chips": 1, "memory_peak_bytes": 0}
    assert reader(name).read(bare) is None


def test_the_scope_seconds_tile_the_scoped_time():
    total = sum(reader(n).read(READING) for n in SCOPE_READERS)
    assert total == sum(SCOPES.values())


def test_a_dropped_pair_is_reported_not_hidden():
    stats = dict(STATS, counters={"dropped_pairs": 3.0})
    assert reader("seq.moe_dropped_pairs").read(dict(READING, stats=stats)) == 3.0


def test_a_call_that_compiled_is_reported_not_hidden():
    xla = dict(XLA, in_call=dict(XLA["in_call"], compiles=2, cache_loads=1))
    stats = dict(STATS, xla=xla)
    assert reader("seq.compiles_in_call").read(dict(READING, stats=stats)) == 3


def test_the_manifest_lists_each_metric_for_its_own_cells_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in WANT:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == ("setup_s" if name == "seq.compile_s"
                                  else "train_edges_per_s")
    for name, entry in per_layer.items():
        if name not in WANT:
            assert entry["workloads"] == [ALS_CELL], name
    cells = {c["name"]: c for c in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    als = [m["name"] for m in manifest["per_layer"]
           if run.reports(m, cells[ALS_CELL], e2e)]
    seq = [m["name"] for m in manifest["per_layer"]
           if run.reports(m, cells[CELL], e2e)]
    assert len(als) == 14 and sorted(seq) == sorted(WANT)
