"""Spectral primitive + fused Welch plan benchmarks (PR 5).

Three questions:

  * what does the ``segment_fft_power`` primitive cost through each backend
    (jnp rfft vs the Pallas twiddle-matmul kernel — interpret mode on CPU,
    so the CPU pallas number measures tiling correctness cost, not the TPU
    speedup);
  * what does a fused plan containing a Welch member cost vs the eager
    sequential calls it replaces (welch_psd + autocovariance + moments) —
    now that the spectral primitive is a first-class backend citizen the
    whole plan rides one traversal;
  * what does a streamed Welch cost per scan-consumed chunk stack.

Emits ``BENCH_spectral.json`` at the repo root (via `benchmarks.run`);
`benchmarks.check_regression` diffs it against the committed baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backend import PallasBackend, get_backend
from repro.core.estimators.spectral import streaming_welch, welch_engine, welch_psd
from repro.core.plan import (
    StatPlan,
    autocovariance_request,
    moments_request,
    welch_request,
)
from repro.core.estimators.stats import (
    autocovariance,
    moment_engine,
    streaming_window_moments,
)

from .common import row, time_call, write_bench_json

# Interpret-mode Pallas is python-slow; shapes keep the suite in seconds.
S_SEGS, L, D = 512, 256, 4
N, H, MOM_W = 262_144, 16, 64
CHUNK, N_CHUNKS = 4_096, 16


def run() -> None:
    results = []

    def bench(name, fn, *args, backend="", derived=""):
        us = time_call(fn, *args)
        entry = {"name": name, "us_per_call": us, "derived": derived}
        if backend:
            entry["backend"] = backend
        results.append(entry)
        row(f"spectral_{name}" + (f"_{backend}" if backend else ""), us, derived)
        return us

    # -- the primitive, per backend -----------------------------------------
    segs = jax.random.normal(jax.random.PRNGKey(0), (S_SEGS, L, D))
    taper = 0.5 - 0.5 * jnp.cos(2 * jnp.pi * jnp.arange(L) / L)
    # off the TPU the kernels run only where asked to, interpreted
    pallas = PallasBackend(interpret=jax.default_backend() != "tpu")
    for be_name, be in [("jnp", get_backend("jnp")), ("pallas", pallas)]:
        fn = jax.jit(lambda ss, b=be: b.segment_fft_power(ss, taper))
        bench(
            "segment_power", fn, segs, backend=be_name,
            derived=f"S={S_SEGS};L={L};d={D}",
        )

    # -- fused Welch plan vs eager sequential calls -------------------------
    # Both sides timed steady-state: the plan (and its jitted traversal) is
    # built once, exactly as the eager estimators reuse their module-level
    # jit caches — what's measured is the traversal, not the trace.
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    plan = StatPlan(
        [welch_request(L), autocovariance_request(H), moments_request(MOM_W)],
        d=D,
        backend="jnp",
    )
    traverse = jax.jit(plan.from_chunk)

    def fused_collect():
        return plan.finalize(traverse(x), cache=False)

    def eager_three():
        welch_psd(x, L, backend="jnp")
        autocovariance(x, H, backend="jnp")
        me = moment_engine(MOM_W, D, backend="jnp")
        return streaming_window_moments(me, me.from_chunk(x))

    us_fused = bench(
        "welch_fused_collect", fused_collect,
        derived=f"N={N};L={L};H={H};mom_w={MOM_W}",
    )
    us_eager = bench("welch_eager_3stats", eager_three)
    row(
        "spectral_fused_vs_eager", 0.0,
        f"eager/fused={us_eager / us_fused:.2f}x",
    )

    # -- streamed Welch (scan-consumed chunk stack) -------------------------
    eng = welch_engine(L, d=D, backend="jnp")
    stack = x[: CHUNK * N_CHUNKS].reshape(N_CHUNKS, CHUNK, D)

    def consume_stack():
        state = eng.consume(eng.init(), stack)
        return streaming_welch(eng, state)

    us_stream = bench(
        "welch_stream_consume", consume_stack,
        derived=f"chunks={N_CHUNKS};chunk={CHUNK}",
    )
    results[-1]["derived"] += f";us_per_chunk={us_stream / N_CHUNKS:.1f}"

    write_bench_json(
        "BENCH_spectral.json",
        {
            "pallas_interpret": jax.default_backend() != "tpu",
            "shapes": {
                "segment_power": {"S": S_SEGS, "L": L, "d": D},
                "welch_plan": {"n": N, "L": L, "max_lag": H, "mom_w": MOM_W},
                "stream": {"chunks": N_CHUNKS, "chunk": CHUNK},
            },
            "speedup_eager_vs_fused": us_eager / us_fused,
            "results": results,
        },
    )


if __name__ == "__main__":
    run()
