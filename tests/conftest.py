"""Test configuration.

Tests run on CPU with 8 simulated XLA devices so multi-chip sharding paths
are exercised without TPU hardware (the reference's analog: running Spark
suites on ``local[*]`` — SURVEY.md §4). Must run before the first jax import.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# CPU unless the caller chose otherwise (tier-1 exports JAX_PLATFORMS=cpu;
# export JAX_PLATFORMS=tpu to run the suite on real hardware instead).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def tmp_home(tmp_path, monkeypatch):
    """Isolated PIO home directory for storage/metadata tests."""
    monkeypatch.setenv("PIO_TPU_HOME", str(tmp_path))
    return tmp_path


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process integration scenarios (quickstart lifecycle);"
        " runs by default, deselect quick runs with -m 'not slow'",
    )


# ------------------------------------------------------------ tier-1's spread
# Tier-1 runs ``pytest tests/ -n 6 --dist loadfile`` (README "Testing"):
# xdist hands each worker whole units, by default one test file each. The
# benchmark's tests all live in one module here (tests/test_benchmarks.py),
# so its source files are made units of their own, and the units known to
# take minutes are handed out last, longest first.

#: the module that collects ``benchmarks/tests/test_<stem>.py`` as
#: ``test_<stem>__<name>``
BENCHMARK_WRAPPER = "test_benchmarks.py"
#: benchmark source files that share another's unit: both write one path
#: (a traced rehearsal of the ALS cell goes to ``bench_out/<cell>``)
SHARED_UNIT = {"test_scope_readers": "test_run_rehearse"}
#: the units of twenty seconds or more in a six-worker tier-1 run, longest
#: first. Units not listed go out first: they are small, or new and of
#: unknown cost; a long unit started last sets the wall.
LONG_UNITS = (
    "tests/test_tpu_compile.py",
    "tests/test_laguna_block.py",
    "tests/test_als.py",
    "tests/test_glm_block.py",
    "tests/test_benchmarks.py::test_train_seq_rehearse",
    "tests/test_keye_block.py",
    "tests/test_smoke_endpoints.py",
    "tests/test_nemotron_layers.py",
    "tests/test_granite_layers.py",
    "tests/test_nemotron_block.py",
    "tests/test_benchmarks.py::test_nemotron",
    "tests/test_benchmarks.py::test_laguna",
    "tests/test_granite_block.py",
    "tests/test_benchmarks.py::test_run_rehearse",
    "tests/test_ssd_kernel.py",
    "tests/test_chip_bringup.py",
    "tests/test_seqrec.py",
    "tests/test_benchmarks.py::test_keye",
    "tests/test_benchmarks.py::test_granite",
    "tests/test_stream.py",
    "tests/test_blocked_attention.py",
    "tests/test_index_kernel.py",
    "tests/test_neural_templates.py",
    "tests/test_scopes.py",
    "tests/test_servers.py",
    "tests/test_checkpoint.py",
    "tests/test_recommendation_e2e.py",
    "tests/test_lint_clean.py",
    "tests/test_template_evaluations.py",
    "tests/test_two_tower.py",
    "tests/test_parallel.py",
    "tests/test_contracts.py",
    "tests/test_router.py",
    "tests/test_classification.py",
)


def unit_of(nodeid: str) -> str:
    """The unit of distribution of a test under ``--dist loadfile``: its
    file, or for a benchmark test the wrapper and its source file's stem."""
    path, _, name = nodeid.partition("::")
    if os.path.basename(path) != BENCHMARK_WRAPPER or "__" not in name:
        return path
    stem = name.split("__", 1)[0]
    return f"{path}::{SHARED_UNIT.get(stem, stem)}"


@pytest.hookimpl(optionalhook=True)  # xdist's hook: absent under -p no:xdist
def pytest_xdist_make_scheduler(config, log):
    if config.getoption("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class ByUnit(LoadFileScheduling):
        def _split_scope(self, nodeid):
            return unit_of(nodeid)

        def _assign_work_unit(self, node):
            for unit in LONG_UNITS:  # behind the rest, longest first
                if unit in self.workqueue:
                    self.workqueue.move_to_end(unit)
            super()._assign_work_unit(node)

    return ByUnit(config, log)
