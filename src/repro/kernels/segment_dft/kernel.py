"""Pallas TPU kernel: framed real-DFT power via tiled twiddle matmuls.

There is no Pallas FFT — but the spectral member of a fused plan does not
need one.  A Welch/Whittle periodogram evaluates a *fixed* segment length L,
so the real DFT is a constant (L, L//2+1) linear map: precompute the
taper-folded twiddle matrices

    C[t, f]  =  taper[t] · cos(2π t f / L)
    S[t, f]  = −taper[t] · sin(2π t f / L)

and each segment's one-sided power spectrum is two MXU contractions plus a
VPU square-and-add:

    re = Cᵀ y,   im = Sᵀ y,   |rfft(y · taper)|² = re² + im²

(with the optional per-segment detrend y ← y − mean(y) folded in before the
contraction).  Complexity is O(L²) per segment instead of the FFT's
O(L log L) — but the constant is a 128×128 systolic array fed from VMEM, and
for the segment lengths Welch uses (L ≤ a few thousand) the matmul form is
bandwidth-bound like every other kernel in this package: each segment is
staged into VMEM exactly once (one HBM read), the twiddle matrices are
resident across the whole grid, and the (S, F, d) output streams out tile by
tile.  This is what lets a fused statistics plan containing a Welch request
keep ALL of its members on the tile path — previously the spectral
primitive silently ejected to jnp.

Grid scheme: ``block_s`` segments per grid step; the segment block, the two
twiddle matrices (revisited — same block every step), and the output block
live in VMEM.  ops.py pads the segment count to a multiple of ``block_s``
with zero segments (their power is zero and is sliced off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compiler_params(vmem_limit):
    if vmem_limit is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))


def _twiddle_dot(twiddles, y):
    """(L, F) twiddles against an (L, d) segment, contracted over time in
    f32 (TPU's default precision would round both operands to bf16)."""
    return jax.lax.dot_general(
        twiddles,
        y,
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (F, d)


def _dft_power_kernel(
    seg_ref, cos_ref, sin_ref, out_ref, *, detrend: bool, block_s: int
):
    cosm = cos_ref[...]  # (L, F) taper-folded twiddles
    sinm = sin_ref[...]
    for j in range(block_s):
        y = seg_ref[j].astype(jnp.float32)  # (L, d)
        if detrend:
            y = y - jnp.mean(y, axis=0, keepdims=True)
        # Two MXU contractions per segment: every frequency bin of every
        # channel at once, contracted over the resident time axis.
        re = _twiddle_dot(cosm, y)  # (F, d)
        im = _twiddle_dot(sinm, y)
        out_ref[j] = re * re + im * im


def _csd_kernel(
    seg_ref, cos_ref, sin_ref, re_ref, im_ref, *, detrend: bool, block_s: int
):
    cosm = cos_ref[...]  # (L, F) taper-folded twiddles
    sinm = sin_ref[...]
    for j in range(block_s):
        y = seg_ref[j].astype(jnp.float32)  # (L, d)
        if detrend:
            y = y - jnp.mean(y, axis=0, keepdims=True)
        re = _twiddle_dot(cosm, y)  # (F, d)
        im = _twiddle_dot(sinm, y)
        # f_i conj(f_j) with f = re + i·im, emitted as two real planes
        # (Pallas has no complex dtypes); ops.py recombines re + i·im.
        re_ref[j] = re[:, :, None] * re[:, None, :] + im[:, :, None] * im[:, None, :]
        im_ref[j] = im[:, :, None] * re[:, None, :] - re[:, :, None] * im[:, None, :]


def segment_csd_pallas(
    segments: jax.Array,
    cos_mat: jax.Array,
    sin_mat: jax.Array,
    *,
    detrend: bool = True,
    block_s: int = 8,
    vmem_limit: "int | None" = None,
    interpret: bool = False,
) -> tuple:
    """Per-segment cross-spectral products of a zero-padded segment stack.

    Same tiling scheme as :func:`segment_dft_power_pallas`; per segment the
    two twiddle contractions are followed by a VPU batched outer product
    over the channel axis.  Returns (re, im), both (S_padded, F, d, d)
    float32 — the real and imaginary planes of ``rfft_i · conj(rfft_j)``.
    """
    s_pad, L, d = segments.shape
    F = cos_mat.shape[1]
    if cos_mat.shape != (L, F) or sin_mat.shape != (L, F):
        raise ValueError(
            f"twiddle matrices must be ({L}, {F}), got {cos_mat.shape}/{sin_mat.shape}"
        )
    if s_pad % block_s != 0:
        raise ValueError(
            f"padded segment count {s_pad} must be a multiple of block_s={block_s}"
        )
    grid = (s_pad // block_s,)

    return pl.pallas_call(
        functools.partial(_csd_kernel, detrend=detrend, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, L, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((L, F), lambda i: (0, 0)),  # resident twiddles
            pl.BlockSpec((L, F), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, F, d, d), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((block_s, F, d, d), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, F, d, d), jnp.float32),
            jax.ShapeDtypeStruct((s_pad, F, d, d), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
    )(segments, cos_mat, sin_mat)


def segment_dft_power_pallas(
    segments: jax.Array,
    cos_mat: jax.Array,
    sin_mat: jax.Array,
    *,
    detrend: bool = True,
    block_s: int = 8,
    vmem_limit: "int | None" = None,
    interpret: bool = False,
) -> jax.Array:
    """Per-segment one-sided DFT power of a zero-padded segment stack.

    Args:
      segments: (S_padded, L, d) float32 with S_padded % block_s == 0
        (ops.py pads with all-zero segments).
      cos_mat / sin_mat: (L, F) taper-folded twiddle matrices (see module
        docstring); F = L // 2 + 1.
      detrend: subtract each segment's per-channel mean before the taper.
      vmem_limit: scoped VMEM bytes to compile with (None: the default).

    Returns (S_padded, F, d) float32: |rfft((seg − mean) · taper)|².
    """
    s_pad, L, d = segments.shape
    F = cos_mat.shape[1]
    if cos_mat.shape != (L, F) or sin_mat.shape != (L, F):
        raise ValueError(
            f"twiddle matrices must be ({L}, {F}), got {cos_mat.shape}/{sin_mat.shape}"
        )
    if s_pad % block_s != 0:
        raise ValueError(
            f"padded segment count {s_pad} must be a multiple of block_s={block_s}"
        )
    grid = (s_pad // block_s,)

    return pl.pallas_call(
        functools.partial(_dft_power_kernel, detrend=detrend, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, L, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((L, F), lambda i: (0, 0)),  # resident twiddles
            pl.BlockSpec((L, F), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, F, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, F, d), jnp.float32),
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
    )(segments, cos_mat, sin_mat)
