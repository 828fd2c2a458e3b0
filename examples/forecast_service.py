"""Forecast service demo — served predictions + anomaly flags (PR 9).

Forecasting rides the SAME weak-memory state every other statistic uses:
the fused plan's lagged sums fit the model (Yule-Walker / innovations
ARMA / periodicity-seeded seasonal AR) and the carried tail window seeds
a jitted companion-matrix recurrence.  Under the gateway, N tenants'
forecasts coalesce into ONE vmapped finalize per tick — prediction is a
query kind, not a separate pipeline.

Two acts:

  1. 32 tenants stream seasonal traffic (random phase each, one tenant
     with an injected spike); every tenant asks the gateway for
     ``model="auto"`` forecasts and anomaly scores, narrowed with the
     ``only=`` query filter.  The period is detected per tenant from the
     plan's Welch member; the spiked tenant is the one flagged.
  2. The same workload on a `CircuitBreakerBackend`, with a seeded
     `FaultInjector` killing the primary's tail-correction primitive
     mid-serve: the breaker trips to the jnp oracle, the served forecasts
     are IDENTICAL to act 1, and the breaker metrics show the trip.

  PYTHONPATH=src python examples/forecast_service.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import asyncio

import numpy as np

from repro.core.backend import CircuitBreakerBackend, JnpBackend
from repro.core.frame import FrameSession
from repro.runtime import chaos
from repro.runtime.chaos import FaultInjector
from repro.serving.gateway import StatsGateway

TENANTS, D, CHUNK = 32, 2, 160
PERIOD, HORIZON = 8, 12
SPIKED_TENANT = 7


def make_session(backend) -> FrameSession:
    sess = FrameSession(d=D, num_users=TENANTS, backend=backend)
    sess.welch(64)
    sess.forecast(HORIZON, model="auto", p=2, max_period=16)
    sess.anomaly_scores(model="ar", p=2)
    return sess


def make_traffic() -> np.ndarray:
    """Seasonal sine per tenant (random phase) + noise; tenant 7 takes a
    spike near the end of its stream — inside the scored tail window."""
    rng = np.random.RandomState(0)
    t = np.arange(CHUNK)
    phases = rng.uniform(0, 2 * np.pi, size=TENANTS)
    base = np.sin(2 * np.pi * t[None, :] / PERIOD + phases[:, None])
    chunks = (
        base[:, :, None] + 0.15 * rng.randn(TENANTS, CHUNK, D)
    ).astype(np.float32)
    chunks[SPIKED_TENANT, -9] += 12.0
    return chunks


async def serve(backend) -> list:
    """Ingest every tenant's stream, then query forecast + anomaly through
    the ticking gateway (the ``only=`` filter narrows each answer)."""
    gw = StatsGateway(make_session(backend))
    gw.start()
    chunks = make_traffic()

    async def tenant_task(u: int) -> dict:
        await gw.ingest(u, chunks[u])
        fc = await gw.query(u, only="forecast")
        an = await gw.query(u, only=("anomaly",))
        return {**fc, **an}

    answers = await asyncio.gather(*(tenant_task(u) for u in range(TENANTS)))
    metrics = gw.metrics()
    health = gw.health()
    await gw.stop()
    occupancy = metrics["batch_occupancy"]
    print(
        f"  served {TENANTS} tenants: health={health!r}, "
        f"mean query batch occupancy={occupancy['query_mean']:.1f}"
    )
    return answers


def report(answers: list) -> None:
    periods = [int(a["forecast"]["period"]) for a in answers]
    hit = sum(p == PERIOD for p in periods)
    print(f"  period detection: {hit}/{TENANTS} tenants -> {PERIOD}")
    # flag relative to the fleet: the AR(2) anomaly model leaves some
    # seasonal structure in everyone's residuals (so an absolute cutoff
    # would be workload-dependent), and a large spike partially masks
    # itself by inflating the fitted innovation variance — 2x the fleet
    # median is the robust line the spike still clears decisively
    maxima = np.asarray(
        [float(np.max(a["anomaly"]["score"])) for a in answers]
    )
    flagged = [u for u in range(TENANTS) if maxima[u] > 2 * np.median(maxima)]
    print(
        f"  anomaly flags (max score > 2x fleet median): tenants {flagged}"
        f" (score {maxima[SPIKED_TENANT]:.1f} vs median {np.median(maxima):.1f})"
    )
    assert flagged == [SPIKED_TENANT]
    pred = np.asarray(answers[0]["forecast"]["pred"])
    print(
        "  tenant 0 forecast (dim 0, first 6 steps): "
        + " ".join(f"{v:+.2f}" for v in pred[:6, 0])
    )


def main() -> None:
    print("== act 1: forecasts + anomaly scoring through the gateway ==")
    clean = asyncio.run(serve("jnp"))
    report(clean)

    print("== act 2: breaker trips mid-serve, forecasts unchanged ==")
    # the injector kills the primary's first two tail-correction calls —
    # they fire while the finalize program traces, i.e. mid-first-serve
    br = CircuitBreakerBackend(
        primary=JnpBackend(), fallback=JnpBackend(),
        trip_after=1, cooldown_calls=8,
    )
    inj = FaultInjector(seed=0).fail(
        "backend.masked_lagged_sums", calls={0, 1}
    )
    with chaos.scoped(inj):
        faulted = asyncio.run(serve(br))
    report(faulted)
    st = br.breaker_metrics()["primitives"]["masked_lagged_sums"]
    print(
        f"  breaker: trips={st['trips']} state={st['state']!r} "
        f"fallback_calls={st['fallback_calls']}"
    )
    assert st["trips"] >= 1
    for u in range(TENANTS):
        np.testing.assert_array_equal(
            np.asarray(clean[u]["forecast"]["pred"]),
            np.asarray(faulted[u]["forecast"]["pred"]),
        )
        np.testing.assert_array_equal(
            np.asarray(clean[u]["anomaly"]["score"]),
            np.asarray(faulted[u]["anomaly"]["score"]),
        )
    print("  forecasts and anomaly scores bit-identical to the clean run")


if __name__ == "__main__":
    enable_compile_cache()
    main()
