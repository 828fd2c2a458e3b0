"""Sufficient statistics of second-order stationary series (paper §2, §7.1).

All estimators are M-estimators of order-H weak memory: a windowed kernel
mapped over time, reduced with a sum.  Three equivalent execution paths are
provided (serial oracle / overlapping blocks / sharded blocks); equality is
property-tested.

Every lagged contraction routes through the compute-backend registry
(`repro.core.backend`): the block path's per-block lag sums, the serial
path, and the streaming ChunkKernel are all the same primitive —
``lagged_sums`` / ``masked_lagged_sums`` — executed by whichever backend the
caller picks (``"jnp"``, the Pallas VMEM tile kernels of
`repro.kernels.window_stats`, or ``"auto"``).  No estimator owns a private
matmul; changing the substrate is a ``backend=`` argument.

A fourth, *streaming* path (`core.streaming`) computes the same statistic
over data arriving in chunks of arbitrary uneven sizes:
:func:`lag_sum_engine` builds a `StreamingEngine` whose chunk kernel is the
backend's masked lagged matmul, and :func:`streaming_autocovariance`
finalizes a `PartialState` into γ̂ — equal to the serial estimator within
float round-off (the ragged end-of-series terms are recovered from the
state's carried tail halo, again through the backend).
"""
from __future__ import annotations

from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..backend import BackendSpec, get_backend
from ..overlap import OverlapSpec, make_overlapping_blocks
from ..streaming import PartialState, StreamingEngine, resolved_stat

Normalization = Literal["paper", "standard"]

__all__ = [
    "mean",
    "raw_lag_sums",
    "block_lag_sums",
    "autocovariance",
    "autocovariance_blocked",
    "autocovariance_sharded",
    "autocorrelation",
    "partial_autocorrelation",
    "gamma_normalizer",
    "windowed_moments",
    "lag_sum_engine",
    "moment_engine",
    "streaming_autocovariance",
    "streaming_window_moments",
    "streaming_mean",
]


def mean(x: jax.Array) -> jax.Array:
    """μ̂ = (1/N) Σ X_k — the order-0 weak-memory estimator (paper §2.1.1)."""
    if x.ndim == 1:
        x = x[:, None]
    return jnp.mean(x, axis=0)


def gamma_normalizer(n: int, max_lag: int, normalization: Normalization) -> jax.Array:
    """Per-lag normalizers for γ̂(h), h = 0..max_lag.

    "paper":    1/(N-h-1)  (paper §2.1.2 — unbiased-style, not PSD-safe)
    "standard": 1/N        (biased, guarantees a PSD block-Toeplitz matrix;
                            preferred when feeding Yule-Walker solves)

    The "paper" divisor is clamped to ≥ 1: when ``max_lag`` is within 1 of
    the series length, N-h-1 reaches 0 (or below) and the paper's formula is
    undefined — those (degenerate, one-sample) lags fall back to divisor 1
    instead of producing ±inf.
    """
    h = jnp.arange(max_lag + 1)
    if normalization == "paper":
        return 1.0 / jnp.maximum(n - h - 1, 1)
    return jnp.full((max_lag + 1,), 1.0 / n)


def raw_lag_sums(
    x: jax.Array, max_lag: int, backend: BackendSpec = None
) -> jax.Array:
    """S(h) = Σ_{k=0}^{N-1-h} X_k X_{k+h}ᵀ, h = 0..max_lag: (max_lag+1, d, d).

    Thin front-end over the backend ``lagged_sums`` primitive (the jnp
    implementation is the serial correctness oracle).
    """
    return get_backend(backend).lagged_sums(x, max_lag)


def block_lag_sums(
    blocks: jax.Array,
    spec: OverlapSpec,
    max_lag: int,
    backend: BackendSpec = None,
) -> jax.Array:
    """Per-block lag sums via the backend's masked lagged matmul:
    (P, max_lag+1, d, d).

    Requires ``spec.h_left == 0`` and ``spec.h_right >= max_lag`` (causal
    forward window).  Boundary correctness is automatic: halo slots beyond
    the global series end are zero-filled, so their products vanish — every
    block start stays unmasked (the paper's Fig. 2 scheme).
    """
    if spec.h_left != 0 or spec.h_right < max_lag:
        raise ValueError(
            f"autocovariance at max_lag={max_lag} needs h_left=0, "
            f"h_right>={max_lag}; got ({spec.h_left},{spec.h_right})"
        )
    be = get_backend(backend)
    nb = spec.block_size
    ones = jnp.ones((nb,), jnp.bool_)

    def per_block(block):
        return be.masked_lagged_sums(block[: nb + max_lag], ones, max_lag)

    return jax.vmap(per_block)(blocks)


def autocovariance(
    x: jax.Array,
    max_lag: int,
    normalization: Normalization = "paper",
    center: bool = False,
    backend: BackendSpec = None,
) -> jax.Array:
    """Serial γ̂(h), h = 0..max_lag: (max_lag+1, d, d).  γ̂(-h) = γ̂(h)ᵀ."""
    if x.ndim == 1:
        x = x[:, None]
    if center:
        x = x - mean(x)[None, :]
    s = get_backend(backend).lagged_sums(x, max_lag)
    norm = gamma_normalizer(x.shape[0], max_lag, normalization)
    return s * norm[:, None, None]


def autocovariance_blocked(
    x: jax.Array,
    max_lag: int,
    block_size: int,
    normalization: Normalization = "paper",
    center: bool = False,
    backend: BackendSpec = None,
) -> jax.Array:
    """Embarrassingly-parallel γ̂ over overlapping blocks (paper Fig. 2/4)."""
    if x.ndim == 1:
        x = x[:, None]
    if center:
        x = x - mean(x)[None, :]
    spec = OverlapSpec(n=x.shape[0], block_size=block_size, h_left=0, h_right=max_lag)
    blocks, _ = make_overlapping_blocks(x, spec)
    partial = block_lag_sums(blocks, spec, max_lag, backend=backend)
    s = jnp.sum(partial, axis=0)
    norm = gamma_normalizer(x.shape[0], max_lag, normalization)
    return s * norm[:, None, None]


def autocovariance_sharded(
    blocks: jax.Array,
    spec: OverlapSpec,
    max_lag: int,
    mesh: Mesh,
    axis: str = "data",
    normalization: Normalization = "paper",
    backend: BackendSpec = None,
) -> jax.Array:
    """Cluster path: blocks pre-sharded over ``axis``; one psum of (H+1,d,d).

    Data never moves between devices — only the (max_lag+1)·d² sufficient
    statistic is reduced.  This is the paper's core scaling claim.  The
    per-shard local contraction runs through the backend registry, so each
    shard can hit the Pallas tile kernel while the collective stays the
    backend-agnostic psum.
    """

    from ...parallel.sharding import psum_tree

    def local(blocks_local):
        partial = block_lag_sums(blocks_local, spec, max_lag, backend=backend)
        return psum_tree(jnp.sum(partial, axis=0), axis)

    s = jax.shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )(blocks)
    norm = gamma_normalizer(spec.n, max_lag, normalization)
    return s * norm[:, None, None]


def windowed_moments(
    x: jax.Array, window: int, backend: BackendSpec = None
) -> dict:
    """Rolling mean/variance over every full width-``window`` slice.

    Returns {"mean": (n_win, d), "var": (n_win, d)} (population variance),
    computed from the backend's ``windowed_moments`` sum/sum-of-squares
    primitive — one VPU tile pass on the Pallas backend.

    The variance pass runs on the globally centered series: Var is
    shift-invariant, and E[x²]−E[x]² in f32 cancels catastrophically for
    high-mean series (a 1e4 offset swamps a 1e-3 signal), so the second
    moment is taken about the global mean and clamped at 0.
    """
    if x.ndim == 1:
        x = x[:, None]
    be = get_backend(backend)
    mu = mean(x)
    s = be.windowed_moments(x - mu[None, :], window)
    m_c = s[:, 0] / window
    var = jnp.maximum(s[:, 1] / window - m_c * m_c, 0.0)
    return {"mean": m_c + mu[None, :], "var": var}


def lag_sum_engine(
    max_lag: int, d: int, backend: BackendSpec = None
) -> StreamingEngine:
    """Streaming engine for the lag-sum sufficient statistic S(0..max_lag).

    ``state.stat`` is (max_lag+1, d, d); each chunk update carries only the
    last ``max_lag`` samples of context.  The chunk kernel is the backend's
    ``masked_lagged_sums`` — with ``backend="pallas"`` every streaming
    ``update``/``merge`` runs the VMEM tile kernel.  Finalize with
    :func:`streaming_autocovariance` (γ̂, feeds Yule-Walker/ARMA) or read
    the raw windowed sums directly.
    """
    be = get_backend(backend)

    def ck(y_padded: jax.Array, start_mask: jax.Array) -> jax.Array:
        return be.masked_lagged_sums(y_padded, start_mask, max_lag)

    return StreamingEngine(
        d=d, h_left=0, h_right=max_lag, chunk_kernel=ck, backend=be
    )


def moment_engine(
    window: int, d: int, backend: BackendSpec = None
) -> StreamingEngine:
    """Streaming engine for aggregate windowed moments (paper §2.1.1's
    order-0/1 statistics lifted to the window walk).

    ``state.stat`` is {"sums": (2, d) of Σ_s [Σ_j x_{s+j}, Σ_j x²_{s+j}],
    "count": ()} over every full width-``window`` start s — a fixed-size
    mergeable reduction of the rolling-moment kernel (unlike
    :func:`windowed_moments`, which materializes every window's value and
    therefore cannot stream).  The chunk kernel is the backend's fused
    primitive at ``max_lag=0``, so a standalone moment stream and a fused
    plan member run the identical contraction.  Finalize with
    :func:`streaming_window_moments`.
    """
    be = get_backend(backend)

    def ck(y_padded: jax.Array, start_mask: jax.Array) -> dict:
        _, mom = be.fused_lagged_moments(y_padded, start_mask, 0, window)
        return {"sums": mom, "count": jnp.sum(start_mask.astype(jnp.float32))}

    return StreamingEngine(
        d=d, h_left=0, h_right=window - 1, chunk_kernel=ck, backend=be
    )


def streaming_window_moments(engine: StreamingEngine, state: PartialState) -> dict:
    """Finalize a moment-engine PartialState into aggregate rolling moments.

    Returns {"mean": (d,), "var": (d,), "count": ()} where mean/var are the
    population moments over all samples of all full windows (overlapping
    windows weight interior samples up, exactly as the windowed walk
    defines).  ``count`` is the number of windows; with count == 0 the
    moments are NaN — check before trusting early-stream queries.
    """
    w = engine.window
    stat = resolved_stat(state)
    total = stat["count"] * w
    m1 = stat["sums"][0] / total
    m2 = stat["sums"][1] / total
    return {
        "mean": m1,
        "var": jnp.maximum(m2 - m1 * m1, 0.0),
        "count": stat["count"],
    }


def streaming_autocovariance(
    engine: StreamingEngine,
    state: PartialState,
    normalization: Normalization = "paper",
) -> jax.Array:
    """Finalize a lag-sum PartialState into γ̂(0..max_lag): (H+1, d, d).

    Equivalent to :func:`autocovariance` on the concatenated stream (the
    cross-strategy equivalence suite pins this to 1e-5).

    The windowed stream counts only starts with a *full* forward window
    (s ≤ n-1-max_lag); the serial estimator is ragged — lag h keeps starts
    up to n-1-h.  The missing end-of-series pairs live entirely within the
    last ``max_lag`` samples, i.e. in ``state.tail`` (right-aligned, zero
    where invalid), and are recovered here with one more masked lagged
    contraction through the engine's backend.
    """
    H = engine.h_right
    s = resolved_stat(state)
    if H > 0:
        tail_sums = engine.backend.masked_lagged_sums(
            jnp.concatenate([state.tail, jnp.zeros_like(state.tail)]),
            jnp.ones((H,), jnp.bool_),
            H,
        )
        s = s + tail_sums
    norm = gamma_normalizer(state.length, H, normalization)
    return s * norm[:, None, None]


def streaming_mean(state: PartialState) -> jax.Array:
    """μ̂ from any PartialState — the order-0 rolling statistic."""
    return state.sample_sum / state.length


def autocorrelation(gamma: jax.Array) -> jax.Array:
    """ρ̂(h) = diag(γ̂(0))^{-1/2} γ̂(h) diag(γ̂(0))^{-1/2} (paper §2.1.3)."""
    d0 = jnp.sqrt(jnp.diagonal(gamma[0]))
    inv = 1.0 / d0
    return gamma * inv[None, :, None] * inv[None, None, :]


def partial_autocorrelation(gamma: jax.Array, max_order: Optional[int] = None) -> jax.Array:
    """κ̂(p) for p = 1..max_order from γ̂ (paper §2.1.3, "from auto-correlation
    to partial auto-correlation" linear system), solved per order with the
    dense block-Toeplitz system; the scalable recursion lives in
    `yule_walker.block_levinson`.

    Returns (max_order, d, d): entry p-1 is U_p^{(p)}.
    """
    H = gamma.shape[0] - 1
    if max_order is None:
        max_order = H
    if max_order > H:
        raise ValueError(f"need γ̂ up to lag {max_order}, got {H}")
    d = gamma.shape[1]
    out = []
    for p in range(1, max_order + 1):
        from .yule_walker import _block_toeplitz, _stack_rhs

        G = _block_toeplitz(gamma, p)
        rhs = _stack_rhs(gamma, p)
        sol = jnp.linalg.solve(G, rhs)  # (p·d, d) of [U_1ᵀ; ...; U_pᵀ]
        u_p_T = sol[(p - 1) * d : p * d, :]
        out.append(u_p_T.T)
    return jnp.stack(out)
