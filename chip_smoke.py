#!/usr/bin/env python3
"""Chip smoke: the served statistics path and its Pallas kernels on a TPU.

    python3 chip_smoke.py              # phases A, B, C on one chip
    python3 chip_smoke.py --chips 4    # the sharded SeriesFrame path only

One process drives everything; it never falls back to the CPU and never
runs a kernel in interpret mode.  Every phase raises on failure.

A. Served path, host-fleet deployment in the shape of the TSBS "devops"
   use case (Time Series Benchmark Suite): ``--scale`` hosts as tenants,
   d = 100 metrics per host, 64-reading chunks 10 s apart, generated from
   ``--seed``.  A `FrameSession` with the default backend serves
   autocovariance(8), moments(32) and welch(64, overlap 32) through
   `StatsGateway`: 4 ingest ticks (every tenant sends a chunk), then 2 query
   ticks (every tenant queries).  Sampled tenants are checked against the
   eager jnp estimators over their full generated series.
B. Every Pallas primitive compiled on the chip (``interpret=False``) at
   d = 8 and d = 100, n = 65 536, against the jnp oracle.
C. Phase A's deployment and traffic again on ``backend="pallas"``: the
   vmapped fused-plan megakernel is the served ingest.  Every tenant's
   answers must match phase A's.

``--chips 4``: the paper's overlapping shards merged by one psum
(`SeriesFrame.from_sharded`) on a 4-chip mesh, for the ``var-dense-wide``
workload (n = 1 000 000, d = 64), against `SeriesFrame.from_array` on one
device.

Wall and compile times are printed as set-up time; nothing here measures
speed.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# -- deployment: TSBS devops host fleet ---------------------------------------
METRICS = 100          # metrics per host
CHUNK = 64             # readings per chunk
INTERVAL_S = 10        # seconds between readings
INGEST_TICKS = 4
QUERY_TICKS = 2
SAMPLED = 16           # tenants checked against the eager reference
MAX_LAG, WINDOW, NPERSEG, OVERLAP = 8, 32, 64, 32

# -- phase B -------------------------------------------------------------------
KERNEL_N = 65536
KERNEL_WIDTHS = (8, 100)
SEG_LEN = 256          # standalone segment-DFT length
BANDWIDTH = 8

# tests/test_frame.py's tolerances (`_assert_matches`): name -> (rtol, atol)
FRAME_TOL = {
    "autocovariance": (1e-5, 1e-4),
    "moments": (1e-5, 1e-6),
    "welch_freqs": (1e-6, 0.0),
    "welch_psd": (1e-4, 1e-5),
}


# ------------------------------------------------------------------ helpers
class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    print(f"[{name}] start", flush=True)
    t0, c0 = time.perf_counter(), clock.seconds
    yield
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    print(f"[{name}] passed")
    print(f"[{name}] wall time (set-up time, not speed): {wall:.3f} s")
    print(f"[{name}] compile time (set-up time, not speed): {comp:.3f} s",
          flush=True)


@jax.jit
def _violations(got, want, rtol, atol):
    err = jnp.abs(got - want)
    return jnp.sum(~(err <= atol + rtol * jnp.abs(want))), jnp.max(err)


def check_close(label: str, got, want, rtol: float, atol: float) -> float:
    """np.testing.assert_allclose semantics (|got - want| ≤ atol + rtol·|want|),
    evaluated where the arrays live, in one fused program; returns the max
    abs error."""
    got, want = jnp.asarray(got), jnp.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{label}: shape {got.shape} != {want.shape}")
    if got.size == 0:
        return 0.0
    bad, max_err = _violations(got, want, rtol, atol)
    bad, max_err = int(bad), float(max_err)
    if bad:
        raise AssertionError(
            f"{label}: {bad} of {got.size} elements outside rtol={rtol} "
            f"atol={atol}; max abs error {max_err:.3e}"
        )
    return max_err


def require_tpu() -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices[0] is {dev.platform!r}); "
            "this script runs on the chip only"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ------------------------------------------------------------------ phase A/C
def fleet_series(scale: int, seed: int) -> np.ndarray:
    """(scale, ticks·CHUNK, METRICS) f32 readings: per-host, per-metric AR(1)
    noise around a daily cycle (8640 readings of 10 s), as standardized
    deviations.  Generated in bulk; this is set-up, not traffic."""
    rng = np.random.default_rng(seed)
    steps = INGEST_TICKS * CHUNK
    per_day = 24 * 3600 // INTERVAL_S
    phi = rng.uniform(0.3, 0.9, (scale, METRICS)).astype(np.float32)
    amp = rng.uniform(0.0, 1.0, (scale, METRICS)).astype(np.float32)
    shift = rng.uniform(0.0, 2 * np.pi, (scale, METRICS)).astype(np.float32)
    noise = rng.standard_normal((steps, scale, METRICS), dtype=np.float32)
    x = np.empty((scale, steps, METRICS), np.float32)
    state = np.zeros((scale, METRICS), np.float32)
    for t in range(steps):
        state = phi * state + noise[t]
        x[:, t] = state + amp * np.sin(2 * np.pi * t / per_day + shift)
    return x


def build_session(scale: int, backend):
    from repro import FrameSession

    session = FrameSession(d=METRICS, num_users=scale, backend=backend)
    session.autocovariance(MAX_LAG)
    session.moments(WINDOW)
    session.welch(nperseg=NPERSEG, overlap=OVERLAP)
    return session


def _raise_if_failed(futures) -> None:
    for fut in futures:
        if not fut.done():
            raise AssertionError("a request was left unresolved by its tick")
        if fut.exception() is not None:
            raise fut.exception()


async def drive_gateway(gw, series: np.ndarray) -> list:
    """4 ingest ticks (every tenant one chunk each), then 2 query ticks
    (every tenant queries); returns every tenant's last answer."""
    scale = series.shape[0]
    for k in range(INGEST_TICKS):
        chunk = series[:, k * CHUNK:(k + 1) * CHUNK]
        futs = [gw.submit_ingest(t, chunk[t]) for t in range(scale)]
        stats = await gw.tick()
        _raise_if_failed(futs)
        if stats["ingests"] != scale:
            raise AssertionError(f"ingest tick {k} absorbed {stats['ingests']}")
    answers = None
    for k in range(QUERY_TICKS):
        futs = [gw.submit_query(t) for t in range(scale)]
        stats = await gw.tick()
        _raise_if_failed(futs)
        if stats["queries"] != scale:
            raise AssertionError(f"query tick {k} answered {stats['queries']}")
        answers = [f.result() for f in futs]
    return answers


def check_gateway_health(gw) -> None:
    from repro.core.backend import CircuitBreakerBackend

    for key in ("failed_ingest", "failed_query", "tick_faults"):
        if gw.counters[key]:
            raise AssertionError(f"gateway counter {key} = {gw.counters[key]}")
    if gw.config.checkpoint_dir is not None:
        errors = gw._loop_rt.manager.errors
        if errors:
            raise AssertionError(f"checkpoint manager errors: {errors}")
    backend = gw.session.plan.groups[0].backend
    if isinstance(backend, CircuitBreakerBackend):
        raise AssertionError("the smoke serves without a fallback backend")


def assert_frame_close(label: str, got: dict, want: dict) -> None:
    check_close(f"{label} autocovariance", got["autocovariance"],
                want["autocovariance"], *FRAME_TOL["autocovariance"])
    for stat in ("mean", "var", "count"):
        check_close(f"{label} moments.{stat}", got["moments"][stat],
                    want["moments"][stat], *FRAME_TOL["moments"])
    check_close(f"{label} welch freqs", got["welch"][0], want["welch"][0],
                *FRAME_TOL["welch_freqs"])
    check_close(f"{label} welch psd", got["welch"][1], want["welch"][1],
                *FRAME_TOL["welch_psd"])


def eager_reference(x) -> dict:
    """The plain reference: the eager jnp estimators over one tenant's full
    series (tests/test_frame.py `_eager`)."""
    from repro.core.estimators.spectral import welch_psd
    from repro.core.estimators.stats import (
        autocovariance,
        moment_engine,
        streaming_window_moments,
    )

    x = jnp.asarray(x)
    me = moment_engine(WINDOW, x.shape[1], backend="jnp")
    return {
        "autocovariance": autocovariance(x, MAX_LAG, backend="jnp"),
        "moments": streaming_window_moments(me, me.from_chunk(x)),
        "welch": welch_psd(x, nperseg=NPERSEG, overlap=OVERLAP, backend="jnp"),
    }


def run_served(series: np.ndarray, backend, sampled, label: str) -> list:
    from repro.serving.gateway import StatsGateway

    session = build_session(series.shape[0], backend)
    gw = StatsGateway(session)
    answers = asyncio.run(drive_gateway(gw, series))
    check_gateway_health(gw)
    plan = session.plan
    print(f"[{label}] backend={plan.groups[0].backend.name} "
          f"megakernel={plan.groups[0]._use_megakernel} "
          f"tenants={series.shape[0]} d={METRICS} "
          f"ingested={int(session.lengths()[0])} rows/tenant")
    for t in sampled:
        assert_frame_close(f"{label} tenant {t}", answers[t],
                           eager_reference(series[t]))
    del gw, session
    gc.collect()
    return answers


# ------------------------------------------------------------------ phase B
def kernel_cases(d: int, n: int, key):
    """name -> (call(backend), rtol, atol) per primitive at width d.

    Tolerances are tests/test_backend.py's; sums over the n rows (lag sums,
    masked/fused lag and moment sums, and the windowed sums, whose jnp
    oracle is a running cumsum) take its n-scaled lagged-sums rule,
    atol = 1e-5·n.  The lag sums also take its fused-lag rtol = 1e-5: on a
    TPU v5e the jnp oracle's own error on the lag-0 diagonal (sums of
    about n) reaches 1.4e-5 of the sum at n = 65 536 against a float64
    reference, 40 times the kernel's.  The megakernel takes
    tests/test_megakernel.py's rtol.
    """
    ks = jax.random.split(key, 4)
    H, W, L, step = MAX_LAG, WINDOW, NPERSEG, NPERSEG - OVERLAP
    reach = max(H, W - 1, L - 1)
    x = jax.random.normal(ks[0], (n, d))
    y = jax.random.normal(ks[1], (n + reach, d))
    mask = jax.random.bernoulli(ks[2], 0.9, (n,))
    segs = jax.random.normal(ks[3], (n // SEG_LEN, SEG_LEN, d))
    taper = jnp.hanning(SEG_LEN).astype(jnp.float32)
    wtaper = jnp.hanning(L).astype(jnp.float32)
    diags = jax.random.normal(ks[0], (n, 2 * BANDWIDTH + 1))
    xt = x.T  # banded over the n axis, d right-hand sides
    z0 = jnp.asarray(13, jnp.int32)
    n_tol = 1e-5 * n
    return {
        "lagged_sums": (lambda be: be.lagged_sums(x, H), 1e-5, n_tol),
        "masked_lagged_sums": (
            lambda be: be.masked_lagged_sums(y, mask, H), 1e-5, n_tol),
        "windowed_moments": (lambda be: be.windowed_moments(x, W), 0.0, n_tol),
        "fused_lagged_moments": (
            lambda be: be.fused_lagged_moments(y, mask, H, (W, 8)), 1e-5, n_tol),
        "segment_fft_power": (
            lambda be: be.segment_fft_power(segs, taper), 1e-3, 1e-4 * SEG_LEN),
        "segment_csd": (
            lambda be: be.segment_csd(segs, taper), 1e-3, 1e-4 * SEG_LEN),
        "banded_matvec": (lambda be: be.banded_matvec(diags, xt), 0.0, 1e-5),
        "fused_plan_update": (
            lambda be: be.fused_plan_update(
                y, mask, z0, H, (W, 8), (L,), (step,), (wtaper,)),
            2e-3, n_tol),
    }


def run_kernels(widths, n: int, seed: int, pallas) -> None:
    from repro.core.backend import JnpBackend

    oracle = JnpBackend()
    key = jax.random.PRNGKey(seed)
    for d in widths:
        for name, (call, rtol, atol) in kernel_cases(d, n, key).items():
            t0 = time.perf_counter()
            got = jax.block_until_ready(call(pallas))
            first = time.perf_counter() - t0
            want = call(oracle)
            err = max(
                check_close(f"B d={d} {name}", g, w, rtol, atol)
                for g, w in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want))
            )
            del got, want
            print(f"[B] d={d} n={n} {name}: ok, max abs err {err:.3e} "
                  f"(first call incl. compile {first:.3f} s, set-up time)",
                  flush=True)
        gc.collect()


# ------------------------------------------------------------------ 4 chips
def run_sharded(num_devices: int, seed: int, workload: str = "var-dense-wide",
                n: int | None = None) -> None:
    from jax.sharding import Mesh

    from repro import SeriesFrame
    from repro.configs.paper_var import PAPER_VAR_CONFIGS
    from repro.timeseries import TimeSeriesStore

    cfg = PAPER_VAR_CONFIGS[workload]
    n = n or cfg.n
    devices = jax.devices()[:num_devices]
    if len(devices) != num_devices:
        raise AssertionError(f"need {num_devices} devices, have {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, cfg.d))
    # n splits into whole blocks, a whole number per device
    num_blocks = 16 * num_devices
    if n % num_blocks:
        raise AssertionError(f"n={n} must split into {num_blocks} blocks")
    reach = max(MAX_LAG, WINDOW - 1, NPERSEG - 1)
    store = TimeSeriesStore.from_series(
        x, n // num_blocks, h_left=0, h_right=reach, mesh=mesh)
    for shard in store.blocks.addressable_shards:
        print(f"[sharded] blocks {shard.index[0]} on {shard.device}")
    placed = {shard.device for shard in store.blocks.addressable_shards}
    if placed != set(devices):
        raise AssertionError(f"shards sit on {placed}, expected {set(devices)}")

    def collect(frame):
        frame.autocovariance(MAX_LAG)
        frame.moments(WINDOW)
        frame.welch(nperseg=NPERSEG, overlap=OVERLAP)
        return frame.collect()

    # the psum's replicated answer, brought to the reference's device
    got = jax.device_put(collect(SeriesFrame.from_sharded(store)), devices[0])
    want = collect(SeriesFrame.from_array(jax.device_put(x, devices[0])))
    print(f"[sharded] {workload}: n={n} d={cfg.d} over {num_devices} devices, "
          f"{num_blocks} blocks of {n // num_blocks}")
    assert_frame_close("sharded vs one device", got, want)


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: run only the sharded path on a 4-chip mesh")
    parser.add_argument("--scale", type=int, default=4000,
                        help="hosts (tenants) in the served deployment")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    device = require_tpu()
    from repro.core.backend import PallasBackend, get_backend
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({cached} entries at start)")
    print(f"device kind: {device['kind']}")
    print(f"device count: {device['count']}")
    print(f"jax {jax.__version__}, jaxlib {importlib.metadata.version('jaxlib')}, "
          f"libtpu {importlib.metadata.version('libtpu')}", flush=True)
    clock = CompileClock()

    if args.chips == 4:
        if device["count"] < 4:
            raise SystemExit(f"--chips 4 needs 4 devices, found {device['count']}")
        with phase("sharded 4-chip", clock):
            run_sharded(4, args.seed)
        device["count"] = 4
    else:
        series = fleet_series(args.scale, args.seed)
        rng = np.random.default_rng(args.seed + 1)
        sampled = sorted(rng.choice(args.scale, min(SAMPLED, args.scale),
                                    replace=False).tolist())
        with phase("A served, default backend", clock):
            answers_a = run_served(series, None, sampled, "A")
        pallas = get_backend("pallas")
        if not isinstance(pallas, PallasBackend) or pallas.interpret:
            raise AssertionError("the registered pallas backend is not compiled")
        with phase("B kernels", clock):
            run_kernels(KERNEL_WIDTHS, KERNEL_N, args.seed, pallas)
        with phase("C served, pallas megakernel", clock):
            answers_c = run_served(series, "pallas", sampled, "C")
            for t, (got, want) in enumerate(zip(answers_c, answers_a)):
                assert_frame_close(f"C vs A tenant {t}", got, want)
            print(f"[C] all {len(answers_c)} tenants match phase A")

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
