"""Each cell, tiny, driven through the gateway on the CPU: the served
answers match the plain reference, nothing compiles inside the window, and
the control (the reference one precision lower) comes out not correct."""
import jax
import pytest

from chipbench import run
from rehearsal import run_tiny

CELLS = ["devops-ingest", "cpuonly-ingest", "devops-query"]


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_cell_matches_the_reference(monkeypatch, capsys, workload):
    out = run_tiny(monkeypatch, workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["count_gap"]["value"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]
    assert "compiles in window: 0 backend compiles" in capsys.readouterr().err


def test_tiny_cell_on_the_interpreted_kernels(monkeypatch):
    """The same check with every primitive on the Pallas kernels, in
    interpret mode (the vmapped megakernel is the served ingest)."""
    out = run_tiny(monkeypatch, "devops-ingest", seconds=0.05, backend="pallas")
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_comes_out_not_correct(monkeypatch, workload):
    out = run_tiny(monkeypatch, workload, control=True)
    assert not out["correct"], out["checks"]


# A configuration's ``session`` object: two ingest lanes.  The gateway
# ingests into lane 0; the query folds the empty second lane in.
SESSION = {"num_shards": 2}


@pytest.mark.parametrize("workload", ["devops-ingest", "devops-query"])
def test_session_options_reach_the_session(monkeypatch, workload):
    build, built = run.build, []

    def keep(config):
        served = build(config)
        built.append(served[0])
        return served

    monkeypatch.setattr(run, "build", keep)
    out = run_tiny(monkeypatch, workload, session=SESSION)
    assert out["correct"], out["checks"]
    (session,) = built
    assert session.num_shards == 2
    for group in session.state_template().values():
        assert {leaf.shape[0] for leaf in jax.tree.leaves(group["lanes"])} == {2}


@pytest.mark.parametrize("workload", ["devops-ingest", "devops-query"])
def test_control_with_session_options_comes_out_not_correct(monkeypatch, workload):
    out = run_tiny(monkeypatch, workload, control=True, session=SESSION)
    assert not out["correct"], out["checks"]
