"""Quickstart — the paper's workflow through the deferred session API.

Everything goes through ONE front door now: build a `SeriesFrame` over your
data placement, defer the statistics you want, and ``collect()`` them in a
single fused traversal.

    from repro import SeriesFrame

    frame = SeriesFrame.from_array(xs)          # or .from_chunks(stream)
    gamma = frame.autocovariance(6)             # deferred — reads nothing
    fit   = frame.yule_walker(2)                # rides the same traversal
    roll  = frame.moments(window=256)           # ... and so does this
    psd   = frame.welch(nperseg=512)
    frame.collect()                             # ONE pass serves all four
    A_hat, sigma = fit.result()                 # memoized — free
    frame.append(new_chunk)                     # folds into the carried ⊕
    fit.result()                                # re-read: walks ONLY new data

The demo below simulates a causal VAR(2), places it three ways (monolithic
array / chunked stream / overlapping shards — the paper's §10 structure,
halo sized lazily from the widest deferred window), collects identical
statistics from each, identifies and fits the model, and forecasts.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import jax
import jax.numpy as jnp

from repro import SeriesFrame
from repro.core.estimators.prediction import ar_forecast
from repro.core.estimators.stats import autocorrelation, partial_autocorrelation
from repro.timeseries import random_stable_var, simulate_var


def main():
    # 1. A "large" multivariate series with known dynamics.
    d, p, n = 6, 2, 200_000
    A_true = random_stable_var(jax.random.PRNGKey(0), p, d, radius=0.6)
    xs = simulate_var(jax.random.PRNGKey(1), A_true, n)
    print(f"simulated VAR({p}) with d={d}, N={n}")

    # 2. One frame, four deferred statistics, ONE traversal at collect().
    max_lag = 6
    frame = SeriesFrame.from_array(xs)
    gamma_h = frame.autocovariance(max_lag, normalization="paper")
    fit_h = frame.yule_walker(p)
    roll_h = frame.moments(window=4096)
    frame.welch(nperseg=1024)
    frame.collect()
    print(f"collected {len(frame.collect())} statistics in "
          f"{frame.num_traversals} fused traversal(s)")

    # 3. The same session over the paper's placements: a chunked stream
    #    (scan-driven ingest) and mesh-ready overlapping shards (per-shard
    #    partials + one psum; the halo is sized lazily at collect, when the
    #    fused plan knows its widest window).
    stream = SeriesFrame.from_chunks(
        [xs[lo : lo + 8192] for lo in range(0, n, 8192)]
    )
    stream.autocovariance(max_lag)
    sharded = SeriesFrame.from_sharded(xs, block_size=8192)
    sharded.autocovariance(max_lag)
    agree = jnp.max(jnp.abs(
        stream.collect()["autocovariance"] - sharded.collect()["autocovariance"]
    ))
    print(f"chunked ≡ sharded placement to {float(agree):.2e}")

    # 4. Model identification (paper §3.1): ACF / PACF from the collected γ̂.
    gamma = gamma_h.result()  # memoized — no second traversal
    rho = autocorrelation(gamma)
    pacf = partial_autocorrelation(gamma, 4)
    pacf_norm = [float(jnp.max(jnp.abs(pacf[m]))) for m in range(4)]
    print("PACF magnitude by order:", [f"{v:.3f}" for v in pacf_norm],
          "⇒ choose p =", int(jnp.argmax(jnp.asarray(pacf_norm) < 0.02)))

    # 5. The Yule-Walker fit rode the same traversal as γ̂.
    A_hat, sigma = fit_h.result()
    print(f"YW error: {float(jnp.max(jnp.abs(A_hat - A_true))):.4f}; "
          f"rolling var (last 4096-window avg): "
          f"{float(jnp.mean(roll_h.result()['var'])):.3f}")

    # 6. New data folds into the carried state — history is never re-read.
    tail = simulate_var(jax.random.PRNGKey(2), A_true, 5_000)
    frame.append(tail)
    A_hat2, _ = fit_h.result()
    print(f"after append(+5k): YW drift "
          f"{float(jnp.max(jnp.abs(A_hat2 - A_hat))):.2e} "
          f"(incremental — only the new chunk was walked)")

    # 7. Forecast.
    preds = ar_forecast(A_hat2, tail[-10:], steps=5)
    print("5-step forecast (first dim):", [f"{float(v):.3f}" for v in preds[:, 0]])

    # 8. Where the math ran: the default "auto" backend dispatches each of
    #    the eight primitives through per-primitive crossovers
    #    (repro.core.calibrate), not a hard-coded size constant.  They come
    #    from the built-in table until you measure explicitly — one pass,
    #    persisted, picked up by every later process on this machine:
    #
    #        python -m repro.core.calibrate --tune
    #
    from repro.core.backend import get_backend
    from repro.core.calibrate import cache_path

    table = get_backend("auto").table
    shown = {k: ("never" if v == float("inf") else int(v))
             for k, v in sorted(table.thresholds.items())}
    print(f"auto-backend crossovers ({table.platform}, {table.source}; "
          f"cache: {cache_path()}):")
    for prim, thr in shown.items():
        print(f"  {prim:<22s} -> pallas at {thr} rows")

    # 9. Serving: the same math behind a concurrency front door.  A
    #    `FrameSession` holds per-tenant partials as ONE stacked pytree;
    #    `repro.serving.gateway.StatsGateway` serves it to concurrent
    #    asyncio clients — each tick coalesces every admitted ingest into
    #    one donated scatter and every query into one vmapped fused
    #    finalize, with token-bucket backpressure, p50/p99 metrics, and
    #    periodic snapshots (a killed gateway restarts from the last
    #    snapshot serving identical answers, zero re-ingest):
    #
    #        from repro.serving import GatewayConfig, StatsGateway
    #        session = FrameSession(d=d, num_users=10_000)
    #        session.autocovariance(6); session.moments(4096)
    #        gw = StatsGateway(session, GatewayConfig(checkpoint_dir=...))
    #        gw.start()                         # background coalescing ticks
    #        await gw.ingest(tenant, chunk)
    #        stats = await gw.query(tenant)
    #
    print("serving front door: PYTHONPATH=src python examples/gateway_demo.py")

    # 10. The megakernel and the tuned tile table.  When a plan carries ≥2
    #     primitive families (lagged sums / rolling moments / Welch members),
    #     its chunk update collapses into ONE ``fused_plan_update`` backend
    #     call — on the Pallas backend a single persistent kernel launch
    #     that stages each (block_t, d) tile into VMEM once and feeds ALL
    #     families from the resident block (the frame above did this at
    #     collect()).  Tile sizes are not hard-coded: every kernel entry
    #     point resolves its block_t / block_s / block_rows through the
    #     calibrated table, and
    #
    #         from repro.core.calibrate import calibrate
    #         calibrate(tune_blocks=True)     # crossovers AND tile search,
    #                                         # persisted to the same cache
    #
    #     searches the candidate grid per primitive on THIS machine and
    #     persists the winners next to the dispatch thresholds — one
    #     calibration artifact, picked up by every later process.  Inspect /
    #     re-measure / install from the shell:
    #
    #         PYTHONPATH=src python -m repro.core.calibrate --show
    #         PYTHONPATH=src python -m repro.core.calibrate --tune
    #         PYTHONPATH=src python -m repro.core.calibrate --bless table.json
    #
    #     Memory-bound deployments can additionally narrow the HBM↔VMEM
    #     stream with ``fused_engine(..., stage_dtype="bfloat16")`` — the
    #     series is staged in bf16, every accumulation stays f32 (measured
    #     mode: validate against the default on your data first).
    tuned = table.blocks or "(none tuned — kernels use built-in defaults)"
    print(f"megakernel engaged for ≥2-family plans; tuned tile configs: {tuned}")

    # 11. Operating under failure.  The serving stack assumes things break
    #     and degrades instead of dying — every piece is deterministic and
    #     rehearsable with the seedable fault injector
    #     (`repro.runtime.chaos`):
    #
    #       * circuit breaker: wrap the compute in
    #         ``CircuitBreakerBackend(primary=PallasBackend(),
    #         fallback=JnpBackend())`` and a raising kernel is quarantined —
    #         calls are served by the jnp oracle, the primary is probed
    #         again after a call-counted cooldown, and every trip/recovery
    #         shows up in ``breaker_metrics()`` and ``gw.health()``;
    #       * verified checkpoints: every snapshot manifest carries per-leaf
    #         crc32 checksums; restore verifies them and walks back past a
    #         torn generation to the newest intact one (freshness is lost,
    #         availability never); transient write failures retry with
    #         backoff;
    #       * tick deadline + degraded mode: set
    #         ``GatewayConfig(tick_deadline=0.05)`` and a blown tick flips
    #         ``gw.health()`` to "degraded" — lowest-priority queries are
    #         shed with `Degraded` (distinct from `RateLimited`), snapshots
    #         defer, and clean ticks recover to "ok";
    #       * rehearse it before production does it to you:
    #
    #             from repro.runtime.chaos import FaultInjector, scoped
    #             inj = FaultInjector(seed=0)
    #             inj.fail("backend.fused_plan_update", calls=range(3, 6))
    #             inj.corrupt("checkpoint.payload", calls={1})
    #             inj.stall("gateway.tick", calls={4}, seconds=0.2)
    #             with scoped(inj):
    #                 ...   # drive the gateway; answers must not change
    #
    #     (tests/test_chaos.py drives exactly this schedule end-to-end and
    #     pins that every non-rejected answer matches a fault-free run.)
    print("chaos drill: PYTHONPATH=src python -m pytest tests/test_chaos.py -q")

    # 12. Forecasting as a query, not a pipeline.  ``.forecast(h)`` and
    #     ``.anomaly_scores()`` are deferred statistics like any other:
    #     they join the fused plan's lag family (still ONE traversal), fit
    #     their model from the SAME corrected lagged sums the estimators
    #     use, and seed a jitted companion-matrix recurrence from the
    #     plan's carried tail window — predictions and standardized
    #     innovation scores serve from weak memory (O(W) retained
    #     samples), never a second pass over the series.
    f12 = SeriesFrame.from_array(xs[-32_768:])
    fit12 = f12.yule_walker(p)
    fc12 = f12.forecast(8, model="ar", p=p)
    an12 = f12.anomaly_scores(model="ar", p=p)
    f12.collect()
    A12, _ = fit12.result()
    drift = jnp.max(jnp.abs(
        fc12.result()["pred"] - ar_forecast(A12, xs[-32_768:], 8)
    ))
    print(f"plan forecast ≡ eager ar_forecast oracle to {float(drift):.1e}; "
          f"max anomaly score on the retained window: "
          f"{float(jnp.max(an12.result()['score'])):.2f} "
          f"({f12.num_traversals} traversal)")
    #     ``model="auto"`` additionally wants a deferred ``.welch(...)``
    #     member: the dominant period is detected from the plan's own
    #     spectrum (per tenant, under vmap) and seeds a seasonal-lag fit.
    #     The serving side — per-tenant forecasts + anomaly flags
    #     coalesced through the gateway, breaker tripping mid-serve —
    #     is examples/forecast_service.py.
    print("forecast service: "
          "PYTHONPATH=src python examples/forecast_service.py")

    # 13. Data-plane integrity.  Weak memory cuts both ways: state is only
    #     ever ⊕-folded, never recomputed, so one NaN ingested is a NaN
    #     FOREVER and f32 rounding per merge is drift forever.  PR 10 adds
    #     the three defenses:
    #
    #       * ingest sentinel: ``GatewayConfig(sentinel=True)`` runs ONE
    #         fused all-finite verdict per coalesced ingest batch (no extra
    #         host syncs), with a per-tenant policy —
    #         ``gw.set_tenant_policy(t, "reject" | "sanitize" |
    #         "quarantine")``; a rejected chunk raises `PoisonedChunk`, a
    #         quarantined tenant is fenced off both planes until repaired;
    #       * self-healing tenants: ``gw.audit()`` sweeps every lane
    #         on-device for non-finite state (poison that predates the
    #         sentinel, or arrived with it off) and quarantines the
    #         unhealthy; ``gw.rebuild_tenant(t)`` restores ONE tenant from
    #         the newest checkpoint generation whose slice verifies AND is
    #         finite — no other tenant's live state moves, nothing
    #         re-traces, and the chaos site ``ingest.payload`` rehearses
    #         the whole story seedably (tests/test_integrity.py);
    #       * compensated accumulation: ``fused_engine(...,
    #         compensated=True)`` / ``FrameSession(compensated=True)``
    #         carries Neumaier error companions through every chunk update
    #         and ⊕-merge, recovering the rounding a plain f32 fold
    #         discards (benchmarks/bench_integrity.py pins ≥10× less
    #         drift on hostile offset data).
    from repro.core.plan import autocovariance_request, fused_engine

    comp = fused_engine([autocovariance_request(max_lag)], d=d,
                        compensated=True)
    cs = comp.init()
    for lo in range(0, n, 8192):
        cs = comp.update_jit(cs, xs[lo : lo + 8192])
    g_comp = comp.finalize(cs)["autocovariance"]
    g_plain = stream.collect()["autocovariance"]
    print(f"compensated streaming γ̂ matches plain to "
          f"{float(jnp.max(jnp.abs(g_comp - g_plain))):.1e} "
          f"(error companions reabsorbed at readout); integrity drill: "
          f"PYTHONPATH=src python -m pytest tests/test_integrity.py -q")


if __name__ == "__main__":
    enable_compile_cache()
    main()
