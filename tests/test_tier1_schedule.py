"""How tier-1 spreads its tests over the workers (``tests/conftest.py``):
under ``--dist loadfile`` a unit is a test file, and each source file of the
benchmark's tests, collected into one module, is a unit of its own."""

import os
from types import SimpleNamespace

import pytest

import conftest
from conftest import LONG_UNITS, SHARED_UNIT, pytest_xdist_make_scheduler, unit_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WRAPPER = "tests/test_benchmarks.py"


@pytest.mark.parametrize("nodeid,unit", [
    (f"{WRAPPER}::test_train_seq_rehearse__a_broken_timed_path_reads_not_correct"
     "[_one_step_fewer]", f"{WRAPPER}::test_train_seq_rehearse"),
    (f"{WRAPPER}::test_nemotron__the_rehearsal_ends_correct_and_labelled[0]",
     f"{WRAPPER}::test_nemotron"),
    (f"{WRAPPER}::test_scope_readers__a_rehearsal_prints_none_of_them",
     f"{WRAPPER}::test_run_rehearse"),
    ("tests/test_glm_block.py::test_the_gradients_match_the_reference[mtp]",
     "tests/test_glm_block.py"),
    ("tests/test_als.py::TestFusedAccum::test_kernel_matches_xla_path"
     "[explicit-float32-padded]", "tests/test_als.py"),
    ("tests/test_faults.py::test_a__b", "tests/test_faults.py"),
])
def test_a_benchmark_test_is_its_source_files_unit_any_other_its_files(
        nodeid, unit):
    assert unit_of(nodeid) == unit


def test_every_unit_named_exists():
    """A renamed file leaves no stale name behind in the order."""
    assert len(set(LONG_UNITS)) == len(LONG_UNITS)
    for unit in LONG_UNITS + tuple(f"{WRAPPER}::{s}" for s in SHARED_UNIT):
        path, _, stem = unit.partition("::")
        assert os.path.isfile(os.path.join(ROOT, path)), unit
        if stem:
            assert os.path.isfile(os.path.join(
                ROOT, "benchmarks", "tests", stem + ".py")), unit


class _Config:
    """What xdist's schedulers read of a run's configuration."""

    option = SimpleNamespace(loadscopereorder=True)

    def __init__(self, dist):
        self.dist = dist

    def getoption(self, name):
        return {"dist": self.dist, "tx": ["1*popen"]}[name]

    getvalue = getoption


class _Node:
    shutting_down = False

    def send_runtest_some(self, indices):
        pass

    def shutdown(self):
        self.shutting_down = True


@pytest.mark.parametrize("dist", ["load", "loadscope", "worksteal", "no"])
def test_other_distributions_keep_xdists_own_scheduler(dist):
    assert pytest_xdist_make_scheduler(_Config(dist), None) is None


def test_loadfile_hands_out_small_units_first_then_the_long_longest_first(
        monkeypatch):
    pytest.importorskip("xdist")
    monkeypatch.setattr(conftest, "LONG_UNITS", (
        "tests/test_b.py", f"{WRAPPER}::test_x", f"{WRAPPER}::test_run_rehearse"))
    collection = [
        f"{WRAPPER}::test_run_rehearse__one",
        f"{WRAPPER}::test_scope_readers__two",
        f"{WRAPPER}::test_x__one",
        f"{WRAPPER}::test_x__two[0]",
        f"{WRAPPER}::test_y__one",
        "tests/test_a.py::test_one",
        "tests/test_a.py::test_two",
        "tests/test_b.py::test_one",
        "tests/test_b.py::test_two",
        "tests/test_b.py::test_three",
        "tests/test_c.py::T::test_one",
    ]
    sched = pytest_xdist_make_scheduler(_Config("loadfile"), None)
    node = _Node()
    sched.add_node(node)
    sched.add_node_collection(node, collection)
    sched.schedule()
    order = list(sched.assigned_work[node]) + list(sched.workqueue)
    assert order == [
        # the rest as xdist orders it, most tests first
        "tests/test_a.py", f"{WRAPPER}::test_y", "tests/test_c.py",
        # then the long units in their order
        "tests/test_b.py", f"{WRAPPER}::test_x", f"{WRAPPER}::test_run_rehearse",
    ]
    # a source file's tests travel together, and with the file it shares
    assert sched.workqueue[f"{WRAPPER}::test_run_rehearse"] == {
        f"{WRAPPER}::test_run_rehearse__one": False,
        f"{WRAPPER}::test_scope_readers__two": False}
    assert list(sched.workqueue[f"{WRAPPER}::test_x"]) == collection[2:4]
