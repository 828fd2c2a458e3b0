"""Very-high-d banded spatial AR (paper §6) — the d ≫ p regime where
Yule-Walker's O(d³) inversion is intractable and the paper's partitioned
first-order method is the only scalable option.

Simulates a d=16384 banded system (a numerical-differentiation-style
stencil), fits it with the partitioned conditional-MLE gradient, and checks
the one-step predictor via the Pallas banded_matvec kernel.

  PYTHONPATH=src python examples/spatial_ar.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import time

import jax
import jax.numpy as jnp

from repro.core.estimators.spatial import (
    SpatialPartition,
    banded_predict,
    banded_predict_partitioned,
    banded_to_dense,
    fit_banded_ar,
)
from repro.kernels.banded_matvec import ops as bmv


def main():
    d, b, n = 1024, 2, 8_000  # (paper regime is d~1e5+; CPU-budgeted here)
    key = jax.random.PRNGKey(0)
    rows = jnp.arange(d)[:, None]
    cols = rows + jnp.arange(-b, b + 1)[None, :]
    valid = (cols >= 0) & (cols < d)
    diags_true = (jax.random.normal(key, (d, 2 * b + 1)) * 0.15) * valid
    print(f"banded AR(1): d={d}, bandwidth={b} "
          f"(dense would be {d*d} params; banded is {d*(2*b+1)})")

    # simulate with the O(d·(2b+1)) predictor — never materialize dense A
    def sim(key, steps):
        def body(x, k):
            nxt = banded_predict(diags_true, x) + jax.random.normal(k, (d,))
            return nxt, nxt
        _, xs = jax.lax.scan(body, jnp.zeros(d), jax.random.split(key, steps))
        return xs

    xs = sim(jax.random.PRNGKey(1), n)

    # partitioned fit (paper §6.2): gradient separates across row partitions
    t0 = time.time()
    res = fit_banded_ar(xs, bandwidth=b, n_steps=100, num_parts=16)
    err = float(jnp.max(jnp.abs((res.diags - diags_true) * valid)))
    print(f"fit: {time.time()-t0:.1f}s, max coefficient error {err:.4f}, "
          f"final nll {float(res.nll_trace[-1]):.4f}")

    # partitioned predictor == full predictor (embarrassingly parallel, §6.1)
    part = SpatialPartition(d=d, num_parts=16, bandwidth=b)
    x = xs[-1]
    y_part = banded_predict_partitioned(res.diags, x, part)
    y_full = banded_predict(res.diags, x)
    print(f"partitioned vs full predictor: {float(jnp.max(jnp.abs(y_part-y_full))):.2e}")

    # Pallas kernel path (VMEM row tiles with spatial halos)
    y_kernel = bmv.banded_matvec(res.diags, x, block_rows=256, interpret=True)
    print(f"pallas banded_matvec vs ref:   {float(jnp.max(jnp.abs(y_kernel-y_full))):.2e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
