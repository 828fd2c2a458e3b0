"""Pallas TPU megakernel: a whole fused statistics plan per VMEM staging.

The fused-plan layer (`repro.core.plan`) already collapses N estimator
requests into one *logical* traversal — but its chunk kernel still issued
one Pallas launch per primitive family: ``fused_lagged_moments`` for the
lag/moment members plus one ``segment_fft_power`` per Welch member, each
re-staging the same chunk rows from HBM.  This kernel is the paper's
"one map over overlapping windows" claim taken to the device limit: the
grid walks the chunk ONCE, stages each ``(block_t, d)`` tile into VMEM
once (the halo is the usual second BlockSpec view shifted one tile), and
feeds every member family from the same resident block:

  * MXU lag contractions — one ``dot_general`` per lag h ≤ max_lag,
    masked-start left factor against the h-shifted resident rows
    (identical math to ``fused_lag_moments_pallas``);
  * VPU moment accumulation — ascending-window shared accumulator, K
    moment windows for the cost of the widest one;
  * taper-folded segment-DFT power — per Welch member, the tile's
    candidate starts are the resident rows whose global index is a
    multiple of the member's stride, computed in the kernel from the
    chunk's global offset ``z0`` (an SMEM scalar) and the grid position;
    each candidate costs two MXU twiddle contractions and a weighted
    square-accumulate.  Candidates past the tile or outside the start
    mask run with weight 0 — no divergent control flow on the grid.

All accumulator outputs are revisited by every grid step (sequential TPU
grid) and initialized at step 0.  ops.py guarantees the padding contract:
tile-multiple length with a trailing all-zero halo tile whenever any
member's reach extends past its start row.

Inputs may be staged in bf16 (the optional plan-level
``stage_dtype="bfloat16"`` mode): every accumulation still happens in
f32 — values are widened after the VMEM load, so only the HBM↔VMEM
traffic narrows, not the arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..window_stats.kernel import moment_sums, rows_scratch, stage_rows


def _megakernel(
    *refs,
    max_lag: int,
    windows: tuple,
    seg_lens: tuple,
    seg_steps: tuple,
    detrend: bool,
    block_t: int,
):
    n_seg = len(seg_lens)
    it = iter(refs)
    z0_ref = next(it)  # (1, 1) int32 in SMEM: global index of row 0
    head_ref = next(it)  # (block_t, d) mask-zeroed left factor
    y_core_ref = next(it)  # (block_t, d) raw series, core tile
    y_next_ref = next(it)  # (block_t, d) halo view (next tile, clamped)
    m_ref = next(it)  # (block_t, 1) f32 start mask
    cos_refs, sin_refs = [], []
    for _ in range(n_seg):
        cos_refs.append(next(it))  # (L_j, F_j) taper-folded twiddles
        sin_refs.append(next(it))
    lag_ref = next(it)  # (max_lag+1, d, d) accumulator
    mom_ref = next(it) if windows else None  # (K, 2, d) accumulator
    psd_refs = [next(it) for _ in range(n_seg)]  # (F_j, d) accumulators
    rows_ref = next(it)  # (2·block_t, d) f32 scratch: core tile + halo

    i = pl.program_id(0)

    head = head_ref[...].astype(jnp.float32)
    stage_rows(y_core_ref, y_next_ref, rows_ref)  # the ONE staging

    @pl.when(i == 0)
    def _init():
        lag_ref[...] = jnp.zeros_like(lag_ref)
        if mom_ref is not None:
            mom_ref[...] = jnp.zeros_like(mom_ref)
        for r in psd_refs:
            r[...] = jnp.zeros_like(r)

    # -- MXU half: one contraction per lag, every masked start of the tile.
    for h in range(max_lag + 1):
        lag_ref[h, :, :] += jax.lax.dot_general(
            head,
            rows_ref[h : h + block_t, :],
            (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    # -- VPU half: ascending-window shared accumulator (K windows for the
    # cost of the widest), masked reduce over the tile's starts.
    if windows:
        m = m_ref[...]  # (block_t, 1)
        zeros = jnp.zeros((block_t, head.shape[1]), jnp.float32)
        carry = (zeros, zeros)
        prev_w = 0
        for k in sorted(range(len(windows)), key=lambda q: windows[q]):
            carry = moment_sums(rows_ref, prev_w, windows[k], carry)
            prev_w = windows[k]
            acc, acc2 = carry
            mom_ref[k, 0, :] += jnp.sum(m * acc, axis=0)
            mom_ref[k, 1, :] += jnp.sum(m * acc2, axis=0)

    # -- Spectral members: the tile's stride-aligned starts are
    # ``first + c·step`` with ``first`` the smallest local row whose global
    # index z0 + i·block_t + row is a multiple of the member's stride.  The
    # candidate count is a static bound (block_t // step + 1), so the loop
    # fully unrolls — no data-dependent control flow on the TPU grid.  A
    # candidate past the tile, or at a masked start, runs with weight 0.
    tile_z0 = z0_ref[0, 0] + i * block_t
    for j, (L, step) in enumerate(zip(seg_lens, seg_steps)):
        cosm = cos_refs[j][...]
        sinm = sin_refs[j][...]
        first = jnp.remainder(-tile_z0, step)
        for c in range(block_t // step + 1):
            off = first + c * step
            in_tile = off < block_t
            off = jnp.minimum(off, block_t - 1)
            # a scalar (the sum of one mask row): the TPU lowering cannot
            # broadcast a (1, 1) vector across both sublanes and lanes
            weight = jnp.where(in_tile, jnp.sum(m_ref[pl.ds(off, 1), :]), 0.0)
            seg = rows_ref[pl.ds(off, L), :]  # (L, d)
            if detrend:
                seg = seg - jnp.mean(seg, axis=0, keepdims=True)
            re = jax.lax.dot_general(
                cosm,
                seg,
                (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # (F, d)
            im = jax.lax.dot_general(
                sinm,
                seg,
                (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            psd_refs[j][...] += weight * (re * re + im * im)


def fused_plan_megakernel_pallas(
    head: jax.Array,
    y: jax.Array,
    m: jax.Array,
    z0: jax.Array,
    cos_mats: tuple,
    sin_mats: tuple,
    max_lag: int,
    windows: tuple,
    seg_lens: tuple,
    seg_steps: tuple,
    *,
    detrend: bool = True,
    block_t: int = 512,
    interpret: bool = False,
) -> tuple:
    """One persistent grid walk serving lag sums + K moment windows + M
    segment-DFT power accumulators.

    Args:
      head: (n_padded, d) mask-zeroed left factor (rows of ``y`` where the
        start mask holds, zero elsewhere).
      y: (n_padded, d) raw padded series; both padded to a ``block_t``
        multiple, ending with one all-zero halo tile whenever any member
        reaches past its start row (ops.py guarantees this).  ``head``/``y``
        may be bf16 (staging dtype); accumulation is always f32.
      m: (n_padded, 1) f32 start mask (zero past the chunk's starts).
      z0: (1, 1) int32 global index of row 0 — the Welch stride alignment.
      cos_mats / sin_mats: per member, (L_j, F_j) taper-folded twiddles.
      max_lag: H ≤ block_t.  windows: distinct moment windows, each
        ≤ block_t + 1 (may be empty).  seg_lens: per-member segment length
        L_j ≤ block_t + 1.  seg_steps: per-member segment stride.

    Returns (lag (H+1, d, d), mom (K, 2, d) | None, psds tuple of
    (F_j, d)) — raw sums, all f32; normalization happens in the callers.
    """
    n, d = y.shape
    windows = tuple(windows)
    seg_lens = tuple(int(L) for L in seg_lens)
    if head.shape != y.shape:
        raise ValueError(f"head/y shapes must match, got {head.shape} vs {y.shape}")
    if m.shape != (n, 1):
        raise ValueError(f"mask must be ({n}, 1), got {m.shape}")
    if n % block_t != 0:
        raise ValueError(f"padded length {n} must be a multiple of block_t={block_t}")
    if max_lag > block_t:
        raise ValueError(f"max_lag={max_lag} must be ≤ block_t={block_t}")
    if windows and max(windows) > block_t + 1:
        raise ValueError(f"windows={windows} must all be ≤ block_t+1={block_t + 1}")
    if seg_lens and max(seg_lens) > block_t + 1:
        raise ValueError(
            f"seg_lens={seg_lens} must all be ≤ block_t+1={block_t + 1}"
        )
    if not (len(seg_steps) == len(cos_mats) == len(sin_mats) == len(seg_lens)):
        raise ValueError("per-member argument tuples must have equal length")
    if z0.shape != (1, 1):
        raise ValueError(f"z0 must be (1, 1), got {z0.shape}")
    grid = (n // block_t,)
    num_tiles = grid[0]
    K = len(windows)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # z0 scalar
        pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # head core tile
        pl.BlockSpec((block_t, d), lambda i: (i, 0)),  # y core tile
        pl.BlockSpec(  # halo: next y tile (clamped; last tile is zeros)
            (block_t, d), lambda i: (jnp.minimum(i + 1, num_tiles - 1), 0)
        ),
        pl.BlockSpec((block_t, 1), lambda i: (i, 0)),  # start-mask tile
    ]
    operands = [z0, head, y, y, m]
    for j, L in enumerate(seg_lens):
        F = cos_mats[j].shape[1]
        if cos_mats[j].shape != (L, F) or sin_mats[j].shape != (L, F):
            raise ValueError(
                f"twiddle matrices for member {j} must be ({L}, {F}), got "
                f"{cos_mats[j].shape}/{sin_mats[j].shape}"
            )
        in_specs.append(pl.BlockSpec((L, F), lambda i: (0, 0)))  # resident
        in_specs.append(pl.BlockSpec((L, F), lambda i: (0, 0)))
        operands += [cos_mats[j], sin_mats[j]]

    out_specs = [pl.BlockSpec((max_lag + 1, d, d), lambda i: (0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((max_lag + 1, d, d), jnp.float32)]
    if K:
        out_specs.append(pl.BlockSpec((K, 2, d), lambda i: (0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((K, 2, d), jnp.float32))
    for j, L in enumerate(seg_lens):
        F = cos_mats[j].shape[1]
        out_specs.append(pl.BlockSpec((F, d), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((F, d), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(
            _megakernel,
            max_lag=max_lag,
            windows=windows,
            seg_lens=seg_lens,
            seg_steps=tuple(int(v) for v in seg_steps),
            detrend=detrend,
            block_t=block_t,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[rows_scratch(block_t, d)],
        interpret=interpret,
    )(*operands)

    lag = outs[0]
    mom = outs[1] if K else None
    psds = tuple(outs[1 + (1 if K else 0) :])
    return lag, mom, psds
