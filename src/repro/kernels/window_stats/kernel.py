"""Pallas TPU kernels: windowed contractions over overlapping VMEM tiles.

Paper §12.2 (Fig. 9) stages blocks of size N_B + 2H into GPU shared memory so
every thread's window is local.  The TPU adaptation (DESIGN.md §2):

  * the "shared memory block" is a VMEM tile; the halo is realized by giving
    the grid step a *second* BlockSpec view of the same HBM array shifted by
    one tile (core tile i + tile i+1 ⇒ all windows with h ≤ N_B are local);
  * instead of one thread per window centre, one MXU matmul per lag computes
    EVERY centre of the tile at once:  S_tile(h) = coreᵀ @ shifted_h, a
    (d × N_B)·(N_B × d) contraction — systolic-array-aligned when
    N_B % 128 == 0 and d % 128 == 0 (padded by ops.py otherwise);
  * the output block (H+1, d, d) is revisited by every grid step
    (accumulation over the sequential TPU grid), initialized at step 0.

Three kernels share the tiling scheme:

  :func:`cross_window_stats_pallas` — cross-lagged sums Σ_k a_k b_{k+h}ᵀ.
    With a = b this is the plain lagged-sum statistic; with a = mask·b it is
    the *masked* form the streaming engine's ChunkKernel contract needs
    (`repro.core.backend.PallasBackend.masked_lagged_sums`).
  :func:`window_moments_pallas` — per-window first/second moment sums
    (rolling mean/variance), one VPU accumulation pass per tile.
  :func:`fused_lag_moments_pallas` — lagged sums AND masked windowed-moment
    sums from ONE staging of each VMEM tile: the series is read from HBM
    once, the MXU lag contractions and the VPU moment accumulation both run
    against the same resident tile pair.  This is the device half of the
    fused statistics-plan layer (`repro.core.plan`): a plan serving
    autocovariance + Yule-Walker + rolling moments costs one HBM traversal
    instead of one per statistic.

Zero-fill boundary handling: ops.py pads the series with one extra zero tile
so the last core tile's "next" view is all zeros — out-of-range products
vanish without any masking (the same trick the overlap data structure uses).

Shifted windows are read from a VMEM scratch holding the core tile plus the
halo (:func:`stage_rows`): the TPU lowering has no value-level dynamic
slice, but a ref slice at any row offset (static, or ``pl.ds`` in a loop)
lowers to a plain VMEM load.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def rows_scratch(block_t: int, d: int):
    """VMEM scratch for :func:`stage_rows`: the core tile plus its halo."""
    return pltpu.VMEM((2 * block_t, d), jnp.float32)


def stage_rows(core_ref, next_ref, rows_ref) -> None:
    """Copy the core tile and the halo tile into one f32 VMEM scratch, so
    every shifted window is a ref slice ``rows_ref[s : s + block_t]``."""
    bt = core_ref.shape[0]
    rows_ref[:bt, :] = core_ref[...].astype(jnp.float32)
    rows_ref[bt:, :] = next_ref[...].astype(jnp.float32)


def moment_sums(rows_ref, lo: int, hi: int, carry: tuple) -> tuple:
    """Add rows ``[j, j + block_t)`` and their squares to ``carry`` for every
    j in [lo, hi): the per-start window sums of the moments kernels."""
    block_t = carry[0].shape[0]

    def body(j, c):
        seg = rows_ref[pl.ds(j, block_t), :]
        return c[0] + seg, c[1] + seg * seg

    return jax.lax.fori_loop(lo, hi, body, carry)


def _lag_kernel(a_core_ref, b_core_ref, b_next_ref, out_ref, rows_ref, *, max_lag: int, block_t: int):
    i = pl.program_id(0)

    core = a_core_ref[...]  # (block_t, d) — the (possibly masked) left factor
    stage_rows(b_core_ref, b_next_ref, rows_ref)  # (2·block_t, d)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # One MXU contraction per lag: every window centre of the tile at once.
    for h in range(max_lag + 1):
        contrib = jax.lax.dot_general(
            core,
            rows_ref[h : h + block_t, :],
            (((0,), (0,)), ((), ())),  # contract over time: (d, d)
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        out_ref[h, :, :] += contrib


def cross_window_stats_pallas(
    a: jax.Array,
    b: jax.Array,
    max_lag: int,
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Cross-lagged sums S(h) = Σ_k a_k b_{k+h}ᵀ of two zero-padded series.

    Args:
      a, b: (n_padded, d) with n_padded % block_t == 0, REQUIRED to end with
        at least one all-zero tile (ops.py guarantees this) and
        max_lag ≤ block_t.  Pass a is b for the plain lagged sums.
      max_lag: H.
      block_t: core tile length N_B (the VMEM block).

    Returns (max_lag+1, d, d) float32.
    """
    n, d = b.shape
    if a.shape != b.shape:
        raise ValueError(f"a/b shapes must match, got {a.shape} vs {b.shape}")
    if n % block_t != 0:
        raise ValueError(f"padded length {n} must be a multiple of block_t={block_t}")
    if max_lag > block_t:
        raise ValueError(f"max_lag={max_lag} must be ≤ block_t={block_t}")
    grid = (n // block_t,)
    num_tiles = grid[0]

    return pl.pallas_call(
        functools.partial(_lag_kernel, max_lag=max_lag, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # a core tile
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # b core tile
            pl.BlockSpec(  # halo: the next b tile (clamped; last tile is zeros)
                (block_t, d), lambda i: (jnp.minimum(i + 1, num_tiles - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((max_lag + 1, d, d), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((max_lag + 1, d, d), jnp.float32),
        scratch_shapes=[rows_scratch(block_t, d)],
        interpret=interpret,
    )(a, b, b)


def window_stats_pallas(
    x: jax.Array,
    max_lag: int,
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Raw lagged sums S(0..max_lag) of a zero-padded series (a = b case)."""
    return cross_window_stats_pallas(
        x, x, max_lag, block_t=block_t, interpret=interpret
    )


def _fused_kernel(
    a_core_ref,
    b_core_ref,
    b_next_ref,
    m_core_ref,
    lag_ref,
    mom_ref,
    rows_ref,
    *,
    max_lag: int,
    windows: tuple,
    block_t: int,
):
    i = pl.program_id(0)

    core = a_core_ref[...]  # (block_t, d) mask-zeroed left factor
    stage_rows(b_core_ref, b_next_ref, rows_ref)
    m = m_core_ref[...]  # (block_t, 1) f32 start mask

    @pl.when(i == 0)
    def _init():
        lag_ref[...] = jnp.zeros_like(lag_ref)
        mom_ref[...] = jnp.zeros_like(mom_ref)

    # MXU half: one contraction per lag, every window start of the tile.
    for h in range(max_lag + 1):
        lag_ref[h, :, :] += jax.lax.dot_general(
            core,
            rows_ref[h : h + block_t, :],
            (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    # VPU half on the SAME resident tile pair: per-start window sums, then a
    # masked reduce over starts — (2, d) moment partials per grid step and
    # per requested window.  Windows are visited in ascending order so the
    # running per-start accumulator is SHARED: window w_k's sums extend
    # w_{k-1}'s with rows [w_{k-1}, w_k) — total work is O(max(windows)) per
    # tile whatever K is, and every window reads the same resident tile pair
    # (one HBM staging for all of them).
    zeros = jnp.zeros((block_t, core.shape[1]), jnp.float32)
    carry = (zeros, zeros)
    prev_w = 0
    for k in sorted(range(len(windows)), key=lambda q: windows[q]):
        carry = moment_sums(rows_ref, prev_w, windows[k], carry)
        prev_w = windows[k]
        acc, acc2 = carry
        mom_ref[k, 0, :] += jnp.sum(m * acc, axis=0)
        mom_ref[k, 1, :] += jnp.sum(m * acc2, axis=0)


def fused_lag_moments_pallas(
    a: jax.Array,
    b: jax.Array,
    m: jax.Array,
    max_lag: int,
    windows: tuple,
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> tuple:
    """Masked lagged sums + masked windowed-moment sums in one tile pass.

    Args:
      a: (n_padded, d) mask-zeroed left factor (rows of b with the start
        mask applied) — exactly the masked_lagged_sums contract.
      b: (n_padded, d) raw padded series, ending with one all-zero tile.
      m: (n_padded, 1) f32 start mask (1.0 at valid starts).
      max_lag: H (≤ block_t); windows: tuple of distinct moment windows
        (each ≤ block_t + 1) — all accumulated from the same resident tile.

    Returns:
      lag: (max_lag+1, d, d) f32 — Σ_{s: m_s} b_s b_{s+h}ᵀ.
      mom: (K, 2, d) f32 — row k is Σ_{s: m_s} Σ_{j<windows[k]}
        [b_{s+j}, b²_{s+j}].
    """
    n, d = b.shape
    windows = tuple(windows)
    if a.shape != b.shape:
        raise ValueError(f"a/b shapes must match, got {a.shape} vs {b.shape}")
    if m.shape != (n, 1):
        raise ValueError(f"mask must be ({n}, 1), got {m.shape}")
    if n % block_t != 0:
        raise ValueError(f"padded length {n} must be a multiple of block_t={block_t}")
    if max_lag > block_t:
        raise ValueError(f"max_lag={max_lag} must be ≤ block_t={block_t}")
    if not windows:
        raise ValueError("need at least one moment window")
    if max(windows) > block_t + 1:
        raise ValueError(
            f"windows={windows} must all be ≤ block_t+1={block_t + 1}"
        )
    grid = (n // block_t,)
    num_tiles = grid[0]
    K = len(windows)

    return pl.pallas_call(
        functools.partial(
            _fused_kernel, max_lag=max_lag, windows=windows, block_t=block_t
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # masked a tile
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # b core tile
            pl.BlockSpec(  # halo: next b tile (clamped; last tile is zeros)
                (block_t, d), lambda i: (jnp.minimum(i + 1, num_tiles - 1), 0)
            ),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),  # start mask tile
        ],
        out_specs=[
            pl.BlockSpec((max_lag + 1, d, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, 2, d), lambda i: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((max_lag + 1, d, d), jnp.float32),
            jax.ShapeDtypeStruct((K, 2, d), jnp.float32),
        ],
        scratch_shapes=[rows_scratch(block_t, d)],
        interpret=interpret,
    )(a, b, b, m)


def _moments_kernel(x_core_ref, x_next_ref, out_ref, rows_ref, *, window: int, block_t: int):
    stage_rows(x_core_ref, x_next_ref, rows_ref)  # (2·block_t, d)

    # VPU accumulation: window starts s = tile offset + [0, block_t); sample
    # s + j lives at local row s + j of the staged rows (j ≤ window-1 ≤
    # block_t).  fori_loop keeps the traced kernel body O(1) in window — a
    # Python loop would unroll `window` slice+add pairs into the program.
    zeros = jnp.zeros(x_core_ref.shape, jnp.float32)
    acc, acc2 = moment_sums(rows_ref, 0, window, (zeros, zeros))
    out_ref[0, :, :] = acc
    out_ref[1, :, :] = acc2


def window_moments_pallas(
    x: jax.Array,
    window: int,
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-window moment sums of a zero-padded series.

    Args:
      x: (n_padded, d), n_padded % block_t == 0, ending with one all-zero
        tile; window ≤ block_t + 1.

    Returns (2, n_padded, d) float32: out[0, s] = Σ_{j<window} x_{s+j},
    out[1, s] = Σ_{j<window} x²_{s+j}.  Starts whose window runs into the
    padding are sliced off by ops.py.
    """
    n, d = x.shape
    if n % block_t != 0:
        raise ValueError(f"padded length {n} must be a multiple of block_t={block_t}")
    if window > block_t + 1:
        raise ValueError(f"window={window} must be ≤ block_t+1={block_t + 1}")
    grid = (n // block_t,)
    num_tiles = grid[0]

    return pl.pallas_call(
        functools.partial(_moments_kernel, window=window, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec(
                (block_t, d), lambda i: (jnp.minimum(i + 1, num_tiles - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((2, block_t, d), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, n, d), jnp.float32),
        scratch_shapes=[rows_scratch(block_t, d)],
        interpret=interpret,
    )(x, x)
