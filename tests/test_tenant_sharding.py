"""A session whose tenants are split over four devices answers as a
one-device session and as the eager estimators do, through ingest, query,
snapshots, one-tenant imports, padding rows and the gateway's tick.

The checks (`tests/four_device_checks.py`) run once, in a child process
that sees four virtual CPU devices, so this process keeps its one device;
each test reads its check's outcome."""
import json
import os
import subprocess
import sys

import pytest

CHECKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "four_device_checks.py")


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, CHECKS], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("check", [
    "matches_one_device_6", "matches_one_device_10",
    "matches_eager_6", "matches_eager_10",
    "lengths", "state_round_trip", "import_tenant", "padding_rows",
    "gateway_tick", "gateway_tick_pieces", "gateway_tick_kept",
    "window_raises",
])
def test_four_device_session(outcomes, check):
    assert outcomes[check] == "ok", outcomes[check]
