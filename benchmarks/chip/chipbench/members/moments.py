"""Aggregate windowed moments: mean and variance over every sample of every
full width-w window (windows overlap, so interior samples weigh w times),
and the count of windows, n − w + 1."""
from __future__ import annotations

import numpy as np


def declare(session, params):
    return session.moments(params["window"])


def window(params) -> int:
    return params["window"]


def stat_floats(params, d: int) -> int:
    return 2 * d + 1  # [Σx, Σx²] over the windows, and their count


def flops(params, d: int, rows: int) -> float:
    # x², then one add each into Σx and Σx² per sample
    return 3.0 * rows * d


def _coverage(n: int, w: int) -> np.ndarray:
    """How many full windows hold sample t."""
    t = np.arange(n)
    lo = np.maximum(t - w + 1, 0)
    hi = np.minimum(t, n - w)
    return np.maximum(hi - lo + 1, 0)


def _finish(s1, s2, count: int, w: int) -> dict:
    m1 = s1 / (count * w)
    m2 = s2 / (count * w)
    return {"mean": m1, "var": np.maximum(m2 - m1 * m1, 0.0),
            "count": np.float64(count)}


def reference(x: np.ndarray, params) -> dict:
    x = np.asarray(x, np.float64)
    n, w = x.shape[0], params["window"]
    c = _coverage(n, w).astype(np.float64)[:, None]
    return _finish(np.sum(c * x, axis=0), np.sum(c * x * x, axis=0), n - w + 1, w)


def control(x: np.ndarray, params) -> dict:
    """The reference from bfloat16 samples with float32 sums (the "other
    float32" step below the program's float32)."""
    import jax.numpy as jnp

    xb = jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
    n, w = xb.shape[0], params["window"]
    c = jnp.asarray(_coverage(n, w), jnp.float32)[:, None]
    s1 = np.asarray(jnp.sum(c * xb, axis=0), np.float64)
    s2 = np.asarray(jnp.sum(c * xb * xb, axis=0), np.float64)
    return _finish(s1, s2, n - w + 1, w)


def numbers(pairs, params) -> dict:
    """Over the sampled hosts' (answer, reference) pairs: ``mean_err``, the
    widest gap of a channel's mean in its standard deviations; ``var_err``,
    the widest relative gap of a channel's variance."""
    mean_err, var_err = [], []
    for got, want in pairs:
        mean = np.asarray(got["mean"], np.float64)
        var = np.asarray(got["var"], np.float64)
        mean_err.append(np.max(np.abs(mean - want["mean"]) / np.sqrt(want["var"])))
        var_err.append(np.max(np.abs(var - want["var"]) / want["var"]))
    # np.max, not max(): a NaN answer must read NaN, never be skipped
    return {"mean_err": float(np.max(mean_err)), "var_err": float(np.max(var_err))}


def exact(answers, rows, params) -> dict:
    """``count_gap``: the widest gap between a host's window count and the
    count its acknowledged rows give (every host, exact)."""
    w = params["window"]
    got = np.asarray([float(a["count"]) for a in answers])
    want = np.maximum(np.asarray(rows, np.int64) - w + 1, 0)
    return {"count_gap": float(np.max(np.abs(got - want)))}
