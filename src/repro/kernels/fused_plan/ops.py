"""Public entry point for the fused-plan megakernel.

Handles everything the device kernel must not: reach-aware zero-extension,
tile padding with a halo tile only when some member reaches past its start
row, per-Welch-member segment counts (the kernel derives each tile's
stride-aligned starts itself from ``z0``), twiddle construction, optional
bf16 staging, and the tile-size resolution through
the calibrated block table (`repro.kernels.tiling.resolve_block`).

The block size is resolved OUTSIDE the jit boundary: the inner program is
traced with a concrete ``block_t``, so installing a new calibration table
(``calibrate(tune_blocks=True)``) changes the geometry of the next call
instead of being baked into a stale trace.

This is the Pallas half of the ``fused_plan_update`` backend primitive
(`repro.core.backend.PallasBackend`); the jnp half composes the existing
primitives and is the parity oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..segment_dft.ref import dft_power_matrices
from ..tiling import clamp_block_t, pad_tiles, resolve_block
from .kernel import fused_plan_megakernel_pallas


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_lag",
        "windows",
        "seg_lens",
        "seg_steps",
        "detrend",
        "block_t",
        "interpret",
        "stage_dtype",
    ),
)
def _fused_plan_update_jit(
    y_padded: jax.Array,
    start_mask: jax.Array,
    z0: jax.Array,
    tapers: tuple,
    *,
    max_lag: int,
    windows: tuple,
    seg_lens: tuple,
    seg_steps: tuple,
    detrend: bool,
    block_t: int,
    interpret: bool,
    stage_dtype: Optional[str],
):
    if y_padded.ndim == 1:
        y_padded = y_padded[:, None]
    L = start_mask.shape[0]
    w_max = max(windows) if windows else 1
    l_max = max(seg_lens) if seg_lens else 1
    reach = max(max_lag, w_max - 1, l_max - 1)
    need = L + reach
    y = y_padded.astype(jnp.float32)
    if y.shape[0] < need:
        y = jnp.pad(y, ((0, need - y.shape[0]), (0, 0)))
    head = jnp.where(start_mask[:, None], y[:L], 0.0)
    head = jnp.pad(head, ((0, y.shape[0] - L), (0, 0)))
    m = jnp.pad(
        start_mask.astype(jnp.float32)[:, None], ((0, y.shape[0] - L), (0, 0))
    )

    n = y.shape[0]
    bt = clamp_block_t(block_t, n, max(reach, 1))
    halo = 1 if reach > 0 else 0
    head_p = pad_tiles(head, bt, halo=halo)
    y_p = pad_tiles(y, bt, halo=halo)
    m_p = pad_tiles(m, bt, halo=halo)

    if stage_dtype is not None:
        # bf16 staging: the HBM↔VMEM stream narrows; the kernel widens back
        # to f32 right after the load, so accumulation precision is kept.
        dt = jnp.dtype(stage_dtype)
        head_p = head_p.astype(dt)
        y_p = y_p.astype(dt)

    z0 = jnp.asarray(z0, jnp.int32)
    twiddles = [
        dft_power_matrices(Lseg, taper)
        for Lseg, taper in zip(seg_lens, tapers)
    ]
    cos_mats = tuple(c for c, _ in twiddles)
    sin_mats = tuple(s for _, s in twiddles)

    lag, mom, psds = fused_plan_megakernel_pallas(
        head_p,
        y_p,
        m_p,
        z0.reshape(1, 1),
        cos_mats,
        sin_mats,
        max_lag,
        windows,
        seg_lens,
        seg_steps,
        detrend=detrend,
        block_t=bt,
        interpret=interpret,
    )
    # a segment starts at every masked start whose global index is a
    # multiple of the member's stride — the kernel's weight-1 candidates
    rows = z0 + jnp.arange(L, dtype=jnp.int32)
    n_segs = tuple(
        jnp.sum((start_mask & (rows % step == 0)).astype(jnp.float32))
        for step in seg_steps
    )
    return lag, mom, psds, n_segs


def fused_plan_update(
    y_padded: jax.Array,
    start_mask: jax.Array,
    z0,
    max_lag: int,
    windows: Tuple[int, ...] = (),
    seg_lens: Tuple[int, ...] = (),
    seg_steps: Tuple[int, ...] = (),
    tapers: tuple = (),
    detrend: bool = True,
    *,
    stage_dtype: Optional[str] = None,
    block_t: Optional[int] = None,
    interpret: bool = False,
) -> tuple:
    """Every member family of a fused plan from ONE grid walk of the chunk.

    The seventh backend primitive: masked lagged sums (``max_lag``), K
    multi-window moment sums (``windows``), and per-member Welch segment-DFT
    power sums (``seg_lens[j]``/``seg_steps[j]``/``tapers[j]``, stride
    alignment against the global index ``z0``) — each tile of the chunk is
    staged into VMEM once and feeds all three families.

    Args:
      y_padded: (≥ L, d) chunk rows (zero-extended to the widest member
        reach when shorter).
      start_mask: (L,) bool window-start validity.
      z0: global index of row 0 (traced ok) — Welch stride alignment.
      windows: distinct moment windows (may be empty).
      seg_lens / seg_steps / tapers: per Welch member; ``tapers[j]`` is the
        (seg_lens[j],) window function.
      stage_dtype: e.g. ``"bfloat16"`` — narrow the HBM↔VMEM staging of the
        series; accumulation stays f32.
      block_t: tile length override; None resolves through the calibrated
        block table (``calibrate(tune_blocks=True)``), else the built-in
        default.

    Returns:
      lag: (max_lag+1, d, d) f32 — Σ_{s: mask} y_s y_{s+h}ᵀ.
      mom: (K, 2, d) f32 (None when ``windows`` is empty).
      psds: tuple of (seg_lens[j]//2+1, d) f32 raw power sums.
      n_segs: tuple of f32 scalars — valid segment counts.
    """
    windows = tuple(int(w) for w in windows)
    if len(set(windows)) != len(windows):
        raise ValueError(f"moment windows must be distinct, got {windows}")
    seg_lens = tuple(int(v) for v in seg_lens)
    seg_steps = tuple(int(v) for v in seg_steps)
    tapers = tuple(tapers)
    if not (len(seg_lens) == len(seg_steps) == len(tapers)):
        raise ValueError(
            f"seg_lens/seg_steps/tapers must align, got lengths "
            f"{len(seg_lens)}/{len(seg_steps)}/{len(tapers)}"
        )
    if any(s <= 0 for s in seg_steps):
        raise ValueError(f"seg_steps must be positive, got {seg_steps}")
    block_t = resolve_block("fused_plan_update", "block_t", block_t)
    return _fused_plan_update_jit(
        y_padded,
        start_mask,
        jnp.asarray(z0, jnp.int32),
        tapers,
        max_lag=max_lag,
        windows=windows,
        seg_lens=seg_lens,
        seg_steps=seg_steps,
        detrend=detrend,
        block_t=block_t,
        interpret=interpret,
        stage_dtype=stage_dtype,
    )
