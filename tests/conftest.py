import os
import sys

# Tests see ONE device (brief: only dryrun.py forces 512).  Distributed
# tests spawn subprocesses that set XLA_FLAGS themselves.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

# The one place the suite asks for Pallas interpret mode: the registered
# "pallas" backend runs the kernels interpreted on CPU.  Registered here,
# before any test module imports, because many modules look the backend
# up at import time (``PALLAS = get_backend("pallas")``).
from repro.core.backend import PallasBackend, register_backend  # noqa: E402

register_backend("pallas", PallasBackend(interpret=True))


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="include tests marked slow (jit-heavy model/system suites)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: jit-heavy model/system test, deselected by default; "
        "include with --runslow (or select directly with -m slow)",
    )
    config.addinivalue_line(
        "markers",
        "backend: compute-backend registry parity test (jnp vs "
        "pallas-interpret); always part of the fast default tier — "
        "select alone with -m backend",
    )
    config.addinivalue_line(
        "markers",
        "integrity: data-plane integrity test (ingest sentinel, tenant "
        "rebuild, compensated accumulation); always part of the fast "
        "default tier — select alone with -m integrity",
    )


def pytest_collection_modifyitems(config, items):
    """Fast default run: deselect ``slow`` unless --runslow or an explicit
    -m expression is given, so ``python -m pytest -x -q`` stays quick and
    deterministic (the estimator/streaming equivalence tier).  Naming a
    test file or node id directly also opts in — ``pytest
    tests/test_models.py`` should run it, not report 'no tests ran'."""
    explicit = any(
        a.endswith(".py") or "::" in a for a in config.invocation_params.args
    )
    if config.getoption("--runslow") or config.getoption("-m") or explicit:
        return
    # backend-parity and integrity tests are pinned into the fast tier even
    # if a future module marks them slow: cross-backend equivalence and the
    # data-plane integrity contracts are tier-1.
    keep = lambda i: (
        "slow" not in i.keywords
        or "backend" in i.keywords
        or "integrity" in i.keywords
    )
    selected = [i for i in items if keep(i)]
    deselected = [i for i in items if not keep(i)]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected
