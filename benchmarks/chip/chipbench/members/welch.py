"""Welch power spectral density: segments of ``nperseg`` samples at global
multiples of nperseg − overlap, each mean-removed and tapered with the
periodic Hann window; the mean of |rfft|² over segments, scaled by
1/(fs Σw²), one-sided (every bin but DC and the Nyquist bin doubled)."""
from __future__ import annotations

import numpy as np


def declare(session, params):
    return session.welch(nperseg=params["nperseg"], overlap=params["overlap"],
                         fs=params["fs"])


def window(params) -> int:
    return params["nperseg"]


def stat_floats(params, d: int) -> int:
    return (params["nperseg"] // 2 + 1) * d + 1  # Σ segment power, count


def flops(params, d: int, rows: int) -> float:
    L = params["nperseg"]
    segments = rows / (L - params["overlap"])
    # mean removal and taper (3 per sample), a real FFT (2.5 L log2 L),
    # |·|² and the add into the sum (3 per bin) — per segment and channel
    per_segment = 3 * L + 2.5 * L * np.log2(L) + 3 * (L // 2 + 1)
    return float(segments * d * per_segment)


def _hann(L: int):
    return 0.5 - 0.5 * np.cos(2 * np.pi * np.arange(L) / L)


def _one_sided(psd, L: int):
    mult = np.ones(psd.shape[0])
    mult[1:] = 2.0
    if L % 2 == 0:
        mult[-1] = 1.0
    return psd * mult[:, None]


def _segment_index(n: int, params) -> np.ndarray:
    L = params["nperseg"]
    step = L - params["overlap"]
    count = (n - params["overlap"]) // step
    return np.arange(count)[:, None] * step + np.arange(L)[None, :]


def reference(x: np.ndarray, params):
    x = np.asarray(x, np.float64)
    L, fs = params["nperseg"], params["fs"]
    w = _hann(L)
    segs = x[_segment_index(x.shape[0], params)]
    segs = segs - segs.mean(axis=1, keepdims=True)
    power = np.abs(np.fft.rfft(segs * w[None, :, None], axis=1)) ** 2
    psd = power.mean(axis=0) / (fs * np.sum(w * w))
    return np.fft.rfftfreq(L, d=1.0 / fs), _one_sided(psd, L)


def control(x: np.ndarray, params):
    """The reference from bfloat16 samples, in float32."""
    import jax.numpy as jnp

    xb = jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
    L, fs = params["nperseg"], params["fs"]
    w = _hann(L)
    segs = xb[jnp.asarray(_segment_index(xb.shape[0], params))]
    segs = segs - segs.mean(axis=1, keepdims=True)
    tw = jnp.asarray(w, jnp.float32)[None, :, None]
    power = jnp.abs(jnp.fft.rfft(segs * tw, axis=1)) ** 2
    psd = np.asarray(power.mean(axis=0), np.float64) / (fs * np.sum(w * w))
    return np.fft.rfftfreq(L, d=1.0 / fs), _one_sided(psd, L)


def numbers(pairs, params) -> dict:
    """``psd_err``: over the sampled hosts, the widest gap of any bin over
    its channel's mean power."""
    err = []
    for got, want in pairs:
        psd = np.asarray(got[1], np.float64)
        ref = want[1]
        err.append(np.max(np.abs(psd - ref) / ref.mean(axis=0)))
    return {"psd_err": float(np.max(err))}   # NaN stays NaN
