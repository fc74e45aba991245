"""Bring-up contract (ISSUE 21): where the compile cache lives, that
``chip_smoke.py`` has no CPU path except ``--rehearse``, and that
``__graft_entry__`` runs on the devices it is given.

Everything here crosses a process boundary on purpose: the compile-cache
rule is about what a FRESH process does before its first backend use,
and the smoke's parent must never hold a backend itself.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_PROBE = """
import json, os, sys
from pio_tpu.utils.compile_cache import place_compile_cache
if sys.argv[1] == "late":
    import jax  # entry points that import jax before the helper runs
placed = place_compile_cache()
import jax
updates = []
orig = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), orig(k, v))
again = place_compile_cache()
print(json.dumps({
    "placed": placed, "again": again, "updates": updates,
    "config": jax.config.jax_compilation_cache_dir,
}))
"""


def _probe(cwd, env_value, order="early"):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop(CACHE_ENV, None)
    if env_value is not None:
        env[CACHE_ENV] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, order], cwd=str(cwd), env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_wins_and_program_sets_nothing(tmp_path):
    want = str(tmp_path / "operator-cache")
    got = _probe(tmp_path, want)
    assert got["placed"] == got["again"] == got["config"] == want
    assert got["updates"] == []  # jax read the variable itself


@pytest.mark.parametrize("order", ["early", "late"])
def test_compile_cache_default_is_fixed_inside_checkout(tmp_path, order):
    """Unset → ``<checkout>/.jax_cache`` whatever the working directory
    and whether or not jax was imported first: the path is part of the
    cache's key, so a directory that moves never hits."""
    want = os.path.join(REPO, ".jax_cache")
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    for cwd in (tmp_path, elsewhere):
        got = _probe(cwd, None, order)
        assert got["placed"] == got["again"] == got["config"] == want


def _smoke(*args, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # what this sandbox exports
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_has_no_cpu_path():
    """Without an accelerator the smoke exits non-zero at its first
    phase, names the platform it found and prints no result line."""
    out = _smoke(timeout=120)
    assert out.returncode != 0
    assert "ran on platform 'cpu', not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_rehearsal_passes_and_labels_itself():
    out = _smoke("--rehearse", timeout=300)
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-2000:]
    lines = out.stdout.strip().splitlines()
    # last line: the driver's contract, exactly — plus the rehearsal label
    result = json.loads(lines[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 1}
    assert summary["native"] == {"als_pack": "loaded",
                                 "topn_host": "loaded"}
    assert summary["train"]["platform"] == "cpu"
    # the asserted deploy answered every query on the device route
    forced = summary["deploy_device"]["scorer"]
    assert forced["routes"]["device"] > 0 and forced["routes"]["host"] == 0
    assert summary["deploy_default"]["scorer"]["linkRttS"] is not None
    assert summary["als_stream"]["streamed"] is True
    assert summary["embedding_bag_kernel"]["interpret"] is True
    # both table layouts gathered and held equal; on CPU the rule
    # packs neither
    assert set(summary["als_gather"]["picked"].values()) == {"plain"}
    assert len(summary["als_gather"]["ns_a_row"]) == 4
    for phase in ("env", "import", "train", "deploy_device",
                  "deploy_default", "reference", "als_stream",
                  "embedding_bag_kernel", "als_solve_kernel", "als_gather"):
        assert summary["phase_s"][phase] > 0


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """A chip run's last line is ``ok`` and ``device`` and nothing else,
    whatever else the summary carries."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line({
        "ok": True, "host_cores": 13, "claim": None,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    })
    assert json.loads(json.dumps(line)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert list(line) == ["ok", "device"]
    assert type(line["device"]["count"]) is int


def test_graft_entry_raises_on_too_few_devices():
    """No simulated mesh on its own: fewer devices than asked is an
    error, and the caller decides whether to simulate."""
    import jax

    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as graft
    finally:
        sys.path.remove(REPO)
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"has {have}"):
        graft.dryrun_multichip(have + 1)
