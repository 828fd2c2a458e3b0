"""The check catches a broken timed path: each fault a cell can have is
planted under a tiny run, which must come out not correct.  (One chip:
there is no exchange between chips to leave out.)"""
import jax
import numpy as np
import pytest

from rehearsal import run_tiny
from repro.core.frame import FrameSession
from repro.serving.rolling import RollingStatsService


def _state_unchanged(monkeypatch):
    """An ingest step that returns the state unchanged."""
    monkeypatch.setattr(RollingStatsService, "ingest", lambda self, *a, **k: None)


def _half_ingest_batch(monkeypatch):
    """Half of each ingest batch left out."""
    orig = FrameSession.ingest

    def ingest(self, user_ids, chunks, *a, **k):
        half = len(user_ids) // 2
        return orig(self, user_ids[:half], chunks[:half], *a, **k)

    monkeypatch.setattr(FrameSession, "ingest", ingest)


def _half_query_batch(monkeypatch):
    """Half of each query batch left out: its answers are the first half's."""
    orig = FrameSession.query_batch

    def query_batch(self, user_ids):
        ids = np.asarray(user_ids)
        half = (len(ids) + 1) // 2
        return orig(self, np.concatenate([ids[:half], ids[: len(ids) - half]]))

    monkeypatch.setattr(FrameSession, "query_batch", query_batch)


def _answer_altered(monkeypatch):
    """Every answer altered where it is made: the autocovariance off by one
    part in 10 000 (a wrong normalization)."""
    orig = FrameSession.query_batch

    def query_batch(self, user_ids):
        out = dict(orig(self, user_ids))
        out["autocovariance"] = out["autocovariance"] * (1 + 1e-4)
        return out

    monkeypatch.setattr(FrameSession, "query_batch", query_batch)


def _lags_altered(monkeypatch):
    """Only lags 1..H of every autocovariance altered, one part in 10 000
    (lag 0 kept): a cross-chunk product or a lag alignment gone wrong."""
    orig = FrameSession.query_batch

    def query_batch(self, user_ids):
        out = dict(orig(self, user_ids))
        acov = out["autocovariance"]
        out["autocovariance"] = acov.at[:, 1:].multiply(1 + 1e-4)
        return out

    monkeypatch.setattr(FrameSession, "query_batch", query_batch)


def _cross_altered(monkeypatch):
    """Only the off-diagonal pairs (i ≠ j) of every autocovariance altered,
    one part in 10 000 (every variance kept)."""
    orig = FrameSession.query_batch

    def query_batch(self, user_ids):
        out = dict(orig(self, user_ids))
        acov = out["autocovariance"]
        d = acov.shape[-1]
        out["autocovariance"] = acov * (1 + 1e-4 * (1 - np.eye(d, dtype=np.float32)))
        return out

    monkeypatch.setattr(FrameSession, "query_batch", query_batch)


ALTERED = [_answer_altered, _lags_altered, _cross_altered]
FAULTS = {
    "devops-ingest": [_state_unchanged, _half_ingest_batch, *ALTERED],
    "cpuonly-ingest": [_state_unchanged, _half_ingest_batch, *ALTERED],
    "devops-query": [_state_unchanged, _half_query_batch, *ALTERED],
}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, faults in FAULTS.items() for f in faults
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_fault_comes_out_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    out = run_tiny(monkeypatch, workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("kind", ["autocovariance", "moments", "welch"])
def test_a_nan_in_one_answer_reads_infinite(kind):
    """One sampled host's NaN answer is never skipped by the widest gap."""
    import math

    from chipbench import compare, spec

    config = spec.load("devops-ingest").config
    m = next(m for m in config["plan"] if m["kind"] == kind)
    mod = spec.member(kind)
    rng = np.random.default_rng(3)
    xs = [50 + rng.standard_normal((400, 3)).cumsum(axis=0) for _ in range(2)]
    pairs = [(mod.reference(x, m["params"]), mod.reference(x, m["params"])) for x in xs]
    nan = jax.tree.map(lambda a: np.full_like(np.asarray(a, np.float64), np.nan),
                       pairs[1][0])
    pairs[1] = (nan, pairs[1][1])
    found = mod.numbers(pairs, m["params"])
    assert all(math.isnan(v) for v in found.values())
    ok, _ = compare.verdict(found, {k: 1.0 for k in found})
    assert not ok
