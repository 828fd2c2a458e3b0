"""Public jit'd wrappers for the segment-DFT kernels.

Handles: segment-count padding to a ``block_s`` multiple (with all-zero
segments, sliced off after the call), twiddle-matrix construction, f32
promotion, complex recombination for the CSD form, and the interpret
switch for CPU validation.  These are the Pallas half of the compute
registry's ``segment_fft_power`` / ``segment_csd`` primitives
(`repro.core.backend.PallasBackend`); prefer routing through the registry.

``block_s`` resolves through the calibrated block table
(`repro.kernels.tiling.resolve_block`) OUTSIDE the jit boundary — a newly
installed table changes the next call's geometry instead of being baked
into a stale trace; pass ``block_s=`` explicitly to override.  Either way
it is then bounded by VMEM at the call's ``(L, F, d)`` (:func:`_fit_block_s`):
the cross-spectral output grows as ``F·d²`` per segment, so at d ≈ 100 a
single segment's double-buffered block already fills the default scoped
VMEM, and the kernel is given a larger limit instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..tiling import resolve_block
from .kernel import segment_csd_pallas, segment_dft_power_pallas
from .ref import dft_power_matrices, segment_csd_ref, segment_dft_power_ref


# Scoped VMEM the blocks of one grid step may take before the kernel asks
# the compiler for more: under the TPU's 16 MiB default scoped limit, with
# room for the compiler's own temporaries.
_VMEM_BUDGET = 12 << 20


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of an f32 (rows, cols) VMEM tile, padded to the (8, 128)
    layout."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def _fit_block_s(block_s: int, s: int, L: int, d: int, csd: bool):
    """Largest segments-per-step ≤ ``block_s`` (and ≤ the segment count)
    whose double-buffered blocks fit :data:`_VMEM_BUDGET`, and the VMEM
    limit to compile with (None: the default suffices)."""
    F = L // 2 + 1
    out = 2 * F * _tile_bytes(d, d) if csd else _tile_bytes(F, d)
    per_seg = 2 * (_tile_bytes(L, d) + out)  # double-buffered in + out
    fixed = 2 * 2 * _tile_bytes(L, F)  # the two resident twiddle blocks
    bs = max(1, min(block_s, s, (_VMEM_BUDGET - fixed) // per_seg))
    need = fixed + bs * per_seg
    if need <= _VMEM_BUDGET:
        return bs, None
    # one segment's blocks alone overflow the budget: ask for them plus the
    # kernel body's own copy of the per-segment output and some headroom
    return bs, need + out + (4 << 20)


def _pad_segments(segments: jax.Array, block_s: int):
    s = segments.shape[0]
    s_pad = -(-max(s, 1) // block_s) * block_s
    return jnp.pad(
        segments.astype(jnp.float32), ((0, s_pad - s), (0, 0), (0, 0))
    )


def _check_segments(segments: jax.Array, taper: jax.Array):
    if segments.ndim != 3:
        raise ValueError(f"segments must be (S, L, d), got {segments.shape}")
    L = segments.shape[1]
    if taper.shape != (L,):
        raise ValueError(f"taper must be ({L},), got {taper.shape}")


@functools.partial(
    jax.jit, static_argnames=("detrend", "block_s", "interpret")
)
def _segment_fft_power_jit(
    segments: jax.Array,
    taper: jax.Array,
    *,
    detrend: bool,
    block_s: int,
    interpret: bool,
) -> jax.Array:
    s, L, d = segments.shape
    C, Sn = dft_power_matrices(L, taper)
    block_s, vmem_limit = _fit_block_s(block_s, s, L, d, csd=False)
    out = segment_dft_power_pallas(
        _pad_segments(segments, block_s),
        C,
        Sn,
        detrend=detrend,
        block_s=block_s,
        vmem_limit=vmem_limit,
        interpret=interpret,
    )
    return out[:s]


def segment_fft_power(
    segments: jax.Array,
    taper: jax.Array,
    detrend: bool = True,
    *,
    block_s: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Per-segment one-sided power |rfft((seg − mean)·taper)|², via Pallas.

    Drop-in for the jnp rfft form (`repro.core.backend.JnpBackend
    .segment_fft_power`): the DFT of a fixed segment length is a constant
    linear map, evaluated here as two MXU matmuls per segment against
    precomputed taper-folded twiddle matrices — one VMEM staging per
    segment, no FFT primitive needed.

    Args:
      segments: (S, L, d), any float dtype (f32 accumulation).
      taper: (L,) window function (e.g. Hann).
      block_s: segments per grid step; None resolves through the calibrated
        block table, else the built-in default.

    Returns (S, L//2+1, d) float32.
    """
    _check_segments(segments, taper)
    block_s = resolve_block("segment_fft_power", "block_s", block_s)
    return _segment_fft_power_jit(
        segments, taper, detrend=detrend, block_s=block_s, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("detrend", "block_s", "interpret")
)
def _segment_csd_jit(
    segments: jax.Array,
    taper: jax.Array,
    *,
    detrend: bool,
    block_s: int,
    interpret: bool,
) -> jax.Array:
    s, L, d = segments.shape
    C, Sn = dft_power_matrices(L, taper)
    block_s, vmem_limit = _fit_block_s(block_s, s, L, d, csd=True)
    re, im = segment_csd_pallas(
        _pad_segments(segments, block_s),
        C,
        Sn,
        detrend=detrend,
        block_s=block_s,
        vmem_limit=vmem_limit,
        interpret=interpret,
    )
    return jax.lax.complex(re[:s], im[:s])


def segment_csd(
    segments: jax.Array,
    taper: jax.Array,
    detrend: bool = True,
    *,
    block_s: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Per-segment cross-spectral products ``rfft_i · conj(rfft_j)``.

    The complex cross-spectra enter the kernel as four REAL contractions of
    the same resident segment (re/im twiddle matmuls, then a channel outer
    product); the complex dtype only materializes on the way out — Pallas
    carries no complex arrays.

    Args:
      segments: (S, L, d), any float dtype (f32 accumulation).
      taper: (L,) window function.

    Returns (S, L//2+1, d, d) complex64, Hermitian in (i, j).
    """
    _check_segments(segments, taper)
    block_s = resolve_block("segment_csd", "block_s", block_s)
    return _segment_csd_jit(
        segments, taper, detrend=detrend, block_s=block_s, interpret=interpret
    )


def segment_fft_power_reference(
    segments: jax.Array, taper: jax.Array, detrend: bool = True
) -> jax.Array:
    """Matmul-form oracle re-export used by tests/benchmarks."""
    return segment_dft_power_ref(segments, taper, detrend)


def segment_csd_reference(
    segments: jax.Array, taper: jax.Array, detrend: bool = True
) -> jax.Array:
    """rfft-form oracle re-export used by tests/benchmarks."""
    return segment_csd_ref(segments, taper, detrend)
