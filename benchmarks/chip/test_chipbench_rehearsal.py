"""Each cell, tiny, driven through the gateway on the CPU: the served
answers match the plain reference, nothing compiles inside the window, and
the control (the reference one precision lower) comes out not correct."""
import pytest

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
