import os

import pytest

import trace_reduce
from conftest import BENCH

RECORDED = os.path.join(BENCH, "tests", "data", "tiny_tpu.xplane.pb")


def test_union_clips_and_merges():
    got = trace_reduce.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0.5, 10)
    assert got == [[0.5, 3], [5, 6], [9, 10]]


def test_self_times_do_not_count_a_loop_and_its_body_twice():
    events = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0), ("fusion", 5.0, 2.0),
              ("copy", 12.0, 1.0)]
    assert trace_reduce.self_times(events) == {"while": 5.0, "fusion": 5.0, "copy": 1.0}


def test_module_name():
    assert trace_reduce.module_name("jit_accum(123456789)") == "jit_accum"


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_reduction_of_the_recorded_tpu_trace():
    """A traced call of the explicit cell at rehearsal size, recorded on a
    v5e in PR 25: the numbers below were read from it by hand."""
    r = trace_reduce.reduce(RECORDED, "bench.call")
    assert 0 < r["busy_s"] < r["window_s"]
    assert any(name.startswith("jit_") for name in r["module_s"])
    assert 1 <= len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(s for _n, s in r["device_ops"]) <= r["busy_s"] * 1.001
    with pytest.raises(ValueError):
        trace_reduce.reduce(RECORDED, "no.such.span")
