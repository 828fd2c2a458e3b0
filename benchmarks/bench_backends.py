"""Backend-registry shootout on the lag-sum hot loop (tentpole perf table).

Times the same primitives through the "jnp" backend and the "pallas"
backend (interpret mode on CPU — tiling-faithful but interpreted, so CPU
numbers measure correctness cost, not the TPU speedup) on fixed shapes, and
writes ``BENCH_backends.json`` at the repo root so the perf trajectory of
the backend dispatch starts populating per commit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backend import PallasBackend, get_backend
from repro.core.estimators.stats import lag_sum_engine, streaming_autocovariance

from .common import row, time_call, write_bench_json

# Interpret-mode Pallas is python-slow; shapes are sized so the full suite
# stays in seconds while the grid still covers many tiles.
N, D, H = 65_536, 8, 8
BANDED_D, BANDED_B, BANDED_RHS = 16_384, 8, 4
CHUNK = 8_192


def run() -> None:
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    diags = jax.random.normal(jax.random.PRNGKey(1), (BANDED_D, 2 * BANDED_B + 1))
    v = jax.random.normal(jax.random.PRNGKey(2), (BANDED_RHS, BANDED_D))

    results = []

    def bench(name, backend, fn, *args, derived=""):
        us = time_call(fn, *args)
        results.append(
            {"name": name, "backend": backend, "us_per_call": us, "derived": derived}
        )
        row(f"backends_{name}_{backend}", us, derived)
        return us

    # off the TPU the kernels run only where asked to, interpreted
    pallas = PallasBackend(interpret=jax.default_backend() != "tpu")
    for be_name, be in [("jnp", get_backend("jnp")), ("pallas", pallas)]:
        fn = jax.jit(lambda xx, b=be: b.lagged_sums(xx, H))
        bench("lag_sums", be_name, fn, x, derived=f"N={N};d={D};H={H}")

        fn = jax.jit(lambda dd, vv, b=be: b.banded_matvec(dd, vv))
        bench(
            "banded_matvec", be_name, fn, diags, v,
            derived=f"d={BANDED_D};b={BANDED_B};nrhs={BANDED_RHS}",
        )

        # the streaming serving hot path: one chunked update
        eng = lag_sum_engine(H, D, backend=be)
        state = eng.update(eng.init(), x[:CHUNK])
        fn = jax.jit(eng.update)
        bench(
            "streaming_update", be_name, fn, state, x[CHUNK : 2 * CHUNK],
            derived=f"chunk={CHUNK};H={H};d={D}",
        )

    # cross-backend agreement recorded alongside the timings
    g_j = streaming_autocovariance(
        *(lambda e: (e, e.update(e.init(), x[:CHUNK])))(lag_sum_engine(H, D, "jnp"))
    )
    g_p = streaming_autocovariance(
        *(lambda e: (e, e.update(e.init(), x[:CHUNK])))(lag_sum_engine(H, D, pallas))
    )
    err = float(jnp.max(jnp.abs(g_j - g_p)))
    row("backends_parity_check", 0.0, f"err={err:.1e};interpret={jax.default_backend() != 'tpu'}")

    write_bench_json(
        "BENCH_backends.json",
        {
            "pallas_interpret": jax.default_backend() != "tpu",
            "shapes": {
                "lag_sums": {"n": N, "d": D, "max_lag": H},
                "banded_matvec": {
                    "d": BANDED_D, "bandwidth": BANDED_B, "nrhs": BANDED_RHS
                },
                "streaming_update": {"chunk": CHUNK, "max_lag": H, "d": D},
            },
            "parity_max_abs_err": err,
            "results": results,
        },
    )


if __name__ == "__main__":
    run()
