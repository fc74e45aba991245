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
