"""The ``setup.*`` readers of the program's own process record, and the two
readers of the grouped expert matmuls XLA renames (PR 37)."""

import functools
import json
import os

import numpy as np
import pytest

from conftest import ROOT

import run

ON_A_CHIP = {"window_s": 1.0, "busy_s": 1.0}  # what a reader takes for a trace
# a hand-made stats["process"]: the warm call ends 40 s into the process
RECORD = {
    "origin": "proc_stat",
    "marks": {"process_start": 0.0, "pio_tpu_imported": 0.5,
              "context_built": 10.5, "first_call_enter": 12.0,
              "first_call_exit": 40.0},
    "calls": [[1, 12.0, 40.0], [2, 41.0, 48.0], [3, 50.0, 60.0]],
    "first_call": {
        "spans": [["seq.pack", 12.0, 12.25, 1], ["seq.steps", 12.25, 40.0, 1]],
        "spans_dropped": 0,
        "xla": {"traces": 900, "trace_s": 8.0, "lowers": 4, "lower_s": 4.0,
                "compiles": 1, "compile_s": 1.5, "cache_loads": 3,
                "cache_load_s": 2.5},
    },
    "xla_by_program": {"jit_chunk_staged": {
        "trace_s": 8.0, "lower_s": 4.0, "compile_s": 1.5, "cache_load_s": 2.5,
        "n": 1}},
}
WANT = {
    "setup.to_import_s": 0.5,
    "setup.to_context_s": 10.0,
    "setup.to_first_call_s": 1.5,
    "setup.first_call_s": 28.0,
    "setup.first_call_excess_s": 21.0,
    "setup.trace_s": 8.0,
    "setup.lower_s": 4.0,
    "setup.load_or_compile_s": 4.0,
    "setup.unexplained_s": 5.0,
}
CELLS = ["als-ml25m-r64.train", "glm47flash-ep8.train-seq",
         "laguna-s21-ep32.train-seq-16k"]
KERNELS = {"seq.moe_kernel_s": "glm47flash-ep8.train-seq",
           "lag.moe_kernel_s": "laguna-s21-ep32.train-seq-16k"}


def reader(name):
    return run.load_module("layer_metrics", name)


def als_stats():
    from pio_tpu.models import als
    from pio_tpu.parallel.context import ComputeContext

    rng = np.random.default_rng(3)
    u, i = (rng.integers(0, n, 3000).astype(np.int32) for n in (120, 90))
    args = (ComputeContext.local(), u, i, rng.random(3000).astype(np.float32),
            120, 90, als.ALSConfig(rank=8, iterations=1))
    stats = {}
    als.train_als(*args)
    als.train_als(*args, stats=stats)
    return stats


def seq_stats():
    from pio_tpu.models import seqrec
    from pio_tpu.parallel.context import ComputeContext

    ComputeContext.local()
    rows = np.random.default_rng(4).integers(1, 31, (8, 8)).astype(np.int32)
    args = (None, rows, 30, seqrec.SeqRecConfig(
        d_model=8, n_heads=2, n_layers=1, ffn=16, max_len=8, steps=2,
        batch_size=4))
    stats = {}
    seqrec.train_seqrec(*args)
    seqrec.train_seqrec(*args, stats=stats)
    return stats


@functools.lru_cache(maxsize=None)
def program_stats(trainer):
    """``stats`` as a trainer fills it, at a tiny size on the CPU, on a
    timeline of its own: the first call here is the record's first call,
    whatever this process trained before. (No fixture: tier-1 collects this
    file's tests through ``tests/test_benchmarks.py``, by name.)"""
    from pio_tpu.obs import monotonic_s, tracing

    process = tracing.PROCESS
    tracing.PROCESS = tracing.ProcessTimeline(monotonic_s(), "first_import")
    tracing.PROCESS.mark("pio_tpu_imported")
    try:
        return {"als": als_stats, "seqrec": seq_stats}[trainer]()
    finally:
        tracing.PROCESS = process


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_the_hand_made_record(name):
    reading = {"stats": {"process": RECORD}, "trace": ON_A_CHIP}
    assert reader(name).read(reading) == pytest.approx(WANT[name])


def test_the_first_four_sum_to_the_first_calls_exit():
    assert sum(WANT[n] for n in (
        "setup.to_import_s", "setup.to_context_s", "setup.to_first_call_s",
        "setup.first_call_s")) == RECORD["marks"]["first_call_exit"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_returns_none_where_there_is_nothing_to_read(name):
    read = reader(name).read
    # a rehearsal or a CPU; a program that keeps no record (the parent
    # commit's); a record whose first call has not ended
    assert read({"stats": {"process": RECORD}, "trace": None}) is None
    assert read({"stats": {"process": RECORD}}) is None
    assert read({"stats": {}, "trace": ON_A_CHIP}) is None
    assert read({"stats": {"pack_s": 0.25}, "trace": ON_A_CHIP}) is None
    assert read({"stats": {"process": dict(RECORD, first_call=None)},
                 "trace": ON_A_CHIP}) is None


@pytest.mark.parametrize("name", ["setup.first_call_excess_s",
                                  "setup.unexplained_s"])
def test_without_a_second_call_there_is_no_excess(name):
    record = dict(RECORD, calls=RECORD["calls"][:1])
    reading = {"stats": {"process": record}, "trace": ON_A_CHIP}
    assert reader(name).read(reading) is None


@pytest.mark.parametrize("trainer", ["als", "seqrec"])
def test_every_reader_reads_either_trainers_own_record(trainer):
    """Both trainers report the same record: one reader a metric serves every
    cell. Held to each other on what a tiny CPU call reports, and silent
    without a trace, as in a rehearsal."""
    stats = program_stats(trainer)
    record = json.loads(json.dumps(stats))["process"]
    reading = {"stats": stats, "trace": ON_A_CHIP}
    got = {name: reader(name).read(reading) for name in WANT}
    assert all(isinstance(v, float) for v in got.values()), got
    assert sum(got[n] for n in (
        "setup.to_import_s", "setup.to_context_s", "setup.to_first_call_s",
        "setup.first_call_s")) == pytest.approx(
            record["marks"]["first_call_exit"])
    assert got["setup.unexplained_s"] == pytest.approx(
        got["setup.first_call_excess_s"] - got["setup.trace_s"]
        - got["setup.lower_s"] - got["setup.load_or_compile_s"])
    for name in list(WANT) + list(KERNELS):
        assert reader(name).read({"stats": stats, "trace": None}) is None


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_the_renamed_kernels_seconds_are_read_where_no_scope_holds_them(name):
    stats = {"device_scope_s": {"seq.moe/experts": 0.5, "seq.moe/route": 1.0},
             "device_renamed_s": {"ragged-dot-none": 0.245, "sort-none": 9.0}}
    assert reader(name).read({"stats": stats, "trace": ON_A_CHIP}) == 0.245
    stats["device_scope_s"]["seq.moe/experts/gmm"] = 0.125  # the Pallas kernel
    assert reader(name).read({"stats": stats, "trace": ON_A_CHIP}) == 0.37
    assert reader(name).read({"stats": {}, "trace": ON_A_CHIP}) is None


def test_the_manifest_holds_the_eleven_entries():
    """By name, wherever they stand: a later PR appends its own after them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    last = [by_name[name] for name in list(WANT) + list(KERNELS)]
    for m in last[:9]:
        assert (m["layer"], m["moves"], m["unit"], m["better"]) == (
            "process", "setup_s", "s", "lower")
        assert m["workloads"] == CELLS
        assert m["source"] == ("program_counter" if m["name"] in (
            "setup.trace_s", "setup.lower_s", "setup.load_or_compile_s")
            else "program_span")
    for m in last[9:]:
        assert (m["layer"], m["moves"], m["source"]) == (
            "sequence kernels", "train_edges_per_s", "program_span")
        assert m["workloads"] == [KERNELS[m["name"]]]
