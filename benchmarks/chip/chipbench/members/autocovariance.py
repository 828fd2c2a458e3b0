"""Autocovariance γ̂(0..H), "paper" normalization: S(h)/(n − h − 1) with
S(h) = Σ_{k<n−h} x_k x_{k+h}ᵀ, uncentered (arXiv 1511.06493 §2.1.2)."""
from __future__ import annotations

import numpy as np


def declare(session, params):
    return session.autocovariance(params["max_lag"],
                                  normalization=params["normalization"])


def window(params) -> int:
    return params["max_lag"] + 1


def stat_floats(params, d: int) -> int:
    return (params["max_lag"] + 1) * d * d


def flops(params, d: int, rows: int) -> float:
    # one multiply-add per (row, lag, i, j)
    return 2.0 * rows * d * d * (params["max_lag"] + 1)


def _divisors(n: int, lags: int) -> np.ndarray:
    return np.maximum(n - np.arange(lags) - 1, 1).astype(np.float64)


def reference(x: np.ndarray, params) -> np.ndarray:
    x = np.asarray(x, np.float64)
    n, H = x.shape[0], params["max_lag"]
    sums = np.stack([x[: n - h].T @ x[h:] for h in range(H + 1)])
    return sums / _divisors(n, H + 1)[:, None, None]


def control(x: np.ndarray, params) -> np.ndarray:
    """The reference with its float32 contraction at ``high`` precision:
    three bf16 passes (hi·hi + hi·lo + lo·hi) with float32 accumulation, the
    pass below the program's ``highest``."""
    import jax.numpy as jnp

    def split(a):
        hi = a.astype(jnp.bfloat16)
        return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(u, v):
        return jnp.einsum("ti,tj->ij", u, v, preferred_element_type=jnp.float32)

    x = jnp.asarray(x, jnp.float32)
    n, H = x.shape[0], params["max_lag"]
    out = []
    for h in range(H + 1):
        (ah, al), (bh, bl) = split(x[: n - h]), split(x[h:])
        out.append(dot(ah, bh) + dot(ah, bl) + dot(al, bh))
    sums = np.asarray(jnp.stack(out), np.float64)
    return sums / _divisors(n, H + 1)[:, None, None]


def numbers(pairs, params) -> dict:
    """Gaps on the reference's correlation scale sqrt(γ_ii(0) γ_jj(0)),
    over the sampled hosts' (answer, reference) pairs.

    ``acov_err``: the widest gap of any lag and channel pair.
    ``acov_bias``: the largest signed mean gap, over the hosts, of any
    lag's diagonal (i = j) or of any lag's off-diagonal pairs (i ≠ j).
    Rounding leaves no common sign, so it averages out of the mean; a
    precision step that drops lo·lo products biases every variance low."""
    widest, diag, off = [], [], []
    for got, want in pairs:
        got = np.asarray(got, np.float64)
        var0 = np.diagonal(want[0])
        rel = (got - want) / np.sqrt(np.outer(var0, var0))
        widest.append(np.max(np.abs(rel)))
        d = rel.shape[-1]
        on = np.eye(d, dtype=bool)
        diag.append(rel[:, on].mean(axis=1))
        off.append(rel[:, ~on].mean(axis=1) if d > 1 else np.zeros(len(rel)))
    means = np.concatenate([np.mean(diag, axis=0), np.mean(off, axis=0)])
    # np.max, not max(): a NaN answer must read NaN, never be skipped
    return {"acov_err": float(np.max(widest)), "acov_bias": float(np.max(np.abs(means)))}
