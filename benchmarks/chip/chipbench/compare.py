"""The comparison that decides ``correct``.

Every sampled host's answer, as the gateway delivered it, is set against
the plain float64 reference over the readings that host had acknowledged
when the answer was made (`chipbench.members`).  Each member gives its
numbers (gaps) over the sampled hosts' (answer, reference) pairs.
Exact checks (the window counts) run over every host's last answer.  A run
is correct when every number named in the cell's limit file is finite and
at most its limit.

With ``control=True`` the reference in the next lower precision takes the
program's place for the sampled hosts: the check must then fail.
"""
from __future__ import annotations

import math

import numpy as np

from . import spec
from .traffic import host_series


def readings(config: dict, pool: np.ndarray, driver, names: list,
             control: bool = False) -> dict:
    """Number name -> its reading over the sampled hosts (``inf`` when a
    sampled host has no answer or a reading is not finite)."""
    out: dict = {}

    def note(key, value):
        value = float(value) if math.isfinite(value) else math.inf   # NaN too
        out[key] = max(out.get(key, 0.0), value)

    members = [(m, spec.member(m["kind"]), name)
               for m, name in zip(config["plan"], names)]
    pairs: dict = {name: [] for _, _, name in members}
    for host in driver.sampled:
        if host not in driver.samples:
            note("missing_answers", math.inf)
            continue
        rounds, answer = driver.samples[host]
        x = host_series(pool, host, rounds)
        for m, mod, name in members:
            want = mod.reference(x, m["params"])
            got = mod.control(x, m["params"]) if control else answer[name]
            pairs[name].append((got, want))
    for m, mod, name in members:
        if not pairs[name]:
            continue
        for key, value in mod.numbers(pairs[name], m["params"]).items():
            note(key, value)
    chunk = config["chunk"]
    last = driver.last_answers
    for m, mod, name in members:
        if not hasattr(mod, "exact"):
            continue
        if last is None or any(a is None for a in last):
            note("missing_answers", math.inf)
            continue
        rows = [len(driver.acked_rounds(h)) * chunk for h in range(len(last))]
        for key, value in mod.exact([a[name] for a in last],
                                    rows, m["params"]).items():
            note(key, value)
    return out


def verdict(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number with a limit."""
    checks = {}
    ok = "missing_answers" not in found
    for key, limit in limits.items():
        value = found.get(key, math.inf)
        checks[key] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, checks
