"""Tier-1 wrapper around scripts/smoke.sh: boots a real query server
over a freshly trained engine and curls every operational endpoint
(/healthz, /readyz, /logs.json, /slo.json, /traces.json, /stats.json,
/metrics) from outside the process — the one test that exercises the
full probe/log/SLO plane the way a load balancer and scrape job would.

The script is also runnable by hand (`bash scripts/smoke.sh`) against a
checkout; keeping it shell means operators can lift the curl commands
straight from it.
"""

import pathlib
import shutil
import subprocess

import pytest

SCRIPT = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "smoke.sh"


@pytest.mark.skipif(shutil.which("bash") is None, reason="needs bash")
@pytest.mark.skipif(shutil.which("curl") is None, reason="needs curl")
def test_smoke_script_passes():
    proc = subprocess.run(
        ["bash", str(SCRIPT)],
        capture_output=True,
        text=True,
        # the script takes 190-200 s alone on the 8-core sandbox (the lint
        # passes grow with the tree); under tier-1's six workers 280 s was
        # passed by chance
        timeout=900,
    )
    assert proc.returncode == 0, (
        f"smoke.sh failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
    )
    assert "smoke OK" in proc.stdout
