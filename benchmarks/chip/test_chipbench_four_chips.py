"""The four-chip cell, tiny, driven through the gateway on four virtual CPU
devices: the session splits its hosts over the four, the served answers
match the plain reference, nothing compiles inside the window, and the
control comes out not correct.  The cell runs in a child process, since
the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")

CHILD = """
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax
import pytest
from chipbench import run
from rehearsal import run_tiny

assert len(jax.devices()) == 4
build, built = run.build, []

def keep(config):
    served = build(config)
    built.append(served[0])
    return served

run.build = keep
with pytest.MonkeyPatch.context() as mp:
    out = run_tiny(mp, "devops-16000-ingest")
    control = run_tiny(mp, "devops-16000-ingest", control=True)
(session, _) = built
lanes = [leaf for g in session.state_template().values()
         for leaf in jax.tree.leaves(g["lanes"])]
print(json.dumps({{
    "out": out, "control": control,
    "devices": session.devices,
    "chips_holding_state": sorted({{len(l.sharding.device_set) for l in lanes}}),
}}))
"""


@pytest.fixture(scope="module")
def ran():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CHILD.format(here=HERE, src=SRC))],
        capture_output=True, text=True, timeout=600, env=env, cwd=HERE)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return json.loads(r.stdout.splitlines()[-1]), r.stderr


def test_four_chip_cell_matches_the_reference(ran):
    result, err = ran
    out = result["out"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["count_gap"]["value"] == 0
    assert out["device"]["count"] == 4
    # both runs' windows compiled nothing
    assert err.count("compiles in window: 0 backend compiles") == 2
    assert result["devices"] == 4 and result["chips_holding_state"] == [4]


def test_four_chip_control_comes_out_not_correct(ran):
    result, _ = ran
    assert not result["control"]["correct"], result["control"]["checks"]


# ------------------------------------------- the stagger of the chips' ends
def _profile(device_ops):
    """Two ingest rounds, 0–10 and 10–20 s, then a drain to 22; each
    chip's ops as (start, end) seconds."""
    from types import SimpleNamespace as NS

    def ev(name, s, e, **stats):
        return NS(name=name, start_ns=s * 1e9, duration_ns=(e - s) * 1e9,
                  stats=stats)

    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.round", 0, 10, kind="ingest"), ev("bench.tick", 1, 2),
        ev("bench.round", 10, 20, kind="ingest"), ev("bench.tick", 11, 12),
        ev("bench.drain", 20, 22)])])
    chips = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=[
        ev("fusion", s, e, hlo_module="jit_scatter_update") for s, e in ops])])
        for i, ops in enumerate(device_ops)]
    return NS(planes=[host] + chips)


def _stagger(device_ops, chips):
    from types import SimpleNamespace as NS

    from chipbench import spec, tracing

    summary = tracing.reduce(_profile(device_ops), chips)
    return spec.reader("metrics", "chip_stagger_ms.ingest").read(
        NS(summary=summary, chips=chips))


def test_chip_stagger_reads_the_spread_of_the_chips_last_ends():
    ops = [
        [(2, 5), (12, 15)],
        [(2, 4), (4.5, 5.5), (12, 15)],
        [(3, 6), (12, 14.5)],
        [(2, 5), (12, 16), (21, 21.5)],   # round 1 ends with the drain
    ]
    # round 0: ends 5, 5.5, 6, 5 → 1 s; round 1: 15, 15, 14.5, 21.5 → 7 s
    assert _stagger(ops, 4) == pytest.approx(1e3 * (1 + 7) / 2)
    # a chip idle all round 1: only round 0 counts
    ops[2] = [(3, 6)]
    assert _stagger(ops, 4) == pytest.approx(1e3)
    # one chip has nothing to stagger against; a chip never busy, no round
    assert _stagger(ops[:1], 1) is None
    assert _stagger(ops[:3] + [[]], 4) is None


def test_pad_rows_reads_the_stack_spans_padding():
    from chipbench import program_spans
    from test_chipbench_program_spans import _chip_trace, _read

    trace = _chip_trace()
    # a stack span without the arg (the program before it was added)
    assert _read(program_spans.reduce(trace, 1), "pad_rows.ingest") is None
    for ev in trace.planes[0].lines[0].events:
        if ev.name == "repro.ingest.stack":
            ev.stats["pad_rows"] = 3
    assert _read(program_spans.reduce(trace, 1), "pad_rows.ingest") == 3.0
