"""Shared tiling plumbing for every Pallas kernel package.

Before this module each ``kernels/*/ops.py`` carried its own copy of the
tile-size clamp, the pad-to-tile-multiple helper, and a hard-coded
``block_t=512`` / ``block_s=8`` / ``block_rows=256`` literal.  Three
problems with that:

  * the copies drift (the old ``_pad_tiles`` always appended a full
    all-zero halo tile even when the kernel's reach is 0 — one wasted
    HBM→VMEM staging per call for halo-free kernels);
  * a tuned tile size measured by `repro.core.calibrate` had no way to
    reach the kernels — the literals in the source were the policy;
  * a new kernel package (the fused-plan megakernel) would have added a
    fourth copy.

Now every ops entry point funnels through here:

  :func:`resolve_block`    explicit caller override > the platform's
                           calibrated block table
                           (``CalibrationTable.blocks``, persisted by
                           ``calibrate(tune_blocks=True)``) > the built-in
                           default.  Resolution never triggers a
                           measurement pass — an un-calibrated process
                           just gets the defaults.
  :func:`clamp_block_t`    positive, contract-satisfying tile size for ANY
                           series length (grid ≥ 1, tile ≥ per-tile window
                           requirement).
  :func:`pad_tiles`        zero-pad to a tile multiple, appending the
                           all-zero halo tile ONLY when the kernel reaches
                           past its core tile (``halo > 0``).
  :func:`pad_to_multiple`  ceil-round a count to a block multiple.

This module is a kernels-layer leaf: it imports nothing from ``repro.core``
at module scope (the calibration lookup is a lazy function-level import),
so the kernels ↔ core layering stays acyclic.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_BLOCKS",
    "resolve_block",
    "align_rows",
    "clamp_block_t",
    "pad_tiles",
    "pad_to_multiple",
]

# Built-in per-primitive tile defaults — the values the scattered literals
# used to pin.  A calibrated table (``CalibrationTable.blocks``) overrides
# these per platform; an explicit ops argument overrides everything.
DEFAULT_BLOCKS: Dict[str, Dict[str, int]] = {
    "lagged_sums": {"block_t": 512},
    "masked_lagged_sums": {"block_t": 512},
    "windowed_moments": {"block_t": 512},
    "fused_lagged_moments": {"block_t": 512},
    "fused_plan_update": {"block_t": 512},
    "segment_fft_power": {"block_s": 8},
    "segment_csd": {"block_s": 8},
    "banded_matvec": {"block_rows": 256},
}


def default_block(primitive: str, param: str) -> int:
    try:
        return DEFAULT_BLOCKS[primitive][param]
    except KeyError:
        raise KeyError(
            f"no built-in default for {primitive}.{param}; known: "
            f"{sorted(DEFAULT_BLOCKS)}"
        ) from None


def resolve_block(
    primitive: str, param: str, override: Optional[int] = None
) -> int:
    """The tile size an ops entry point should use for ``primitive``.

    Precedence: ``override`` (an explicit caller argument — tests and the
    tuner itself) > the active platform's calibrated block table > the
    built-in :data:`DEFAULT_BLOCKS` entry.  The table lookup never triggers
    a calibration run: it reads the in-process table if one was already
    resolved, else the persisted cache, else the defaults
    (`repro.core.calibrate.active_blocks`).
    """
    if override is not None:
        return int(override)
    from ..core.calibrate import active_blocks  # lazy: keeps layering acyclic

    tuned = active_blocks(primitive).get(param)
    if tuned is not None:
        return int(tuned)
    return default_block(primitive, param)


# Row tiles are rounded up to this many rows: the TPU lays a block's
# second-to-last axis out in tiles of 8 rows for f32 and 16 for bf16 (the
# optional staging dtype), and refuses a block that is not a whole number
# of them.  Zero rows past the series contribute nothing to any kernel.
SUBLANE_ALIGN = 16


def align_rows(rows: int) -> int:
    """``rows`` rounded up to a multiple of :data:`SUBLANE_ALIGN`."""
    return -(-max(rows, 1) // SUBLANE_ALIGN) * SUBLANE_ALIGN


def clamp_block_t(block_t: int, n: int, min_tile: int) -> int:
    """Positive, contract-satisfying tile size for ANY series length.

    The tile never exceeds the (rounded-up) series length, never drops below
    the kernel's per-tile window requirement (``min_tile``: max_lag for the
    lag kernels, window for the moments kernel, the full reach for the
    fused-plan megakernel), and is a whole number of TPU row tiles
    (:func:`align_rows`) — so the grid ``n_pad // block_t`` is always ≥ 1,
    including tiny series with n < max_lag and the degenerate n == 0.
    """
    return align_rows(max(min(block_t, max(n, 1)), min_tile))


def pad_tiles(x: jax.Array, block_t: int, halo: int = 1) -> jax.Array:
    """Zero-pad (n, d) to a multiple of ``block_t``, plus one all-zero halo
    tile when the kernel's reach extends past its core tile.

    ``halo`` is the number of rows past a window start the kernel may read
    (max_lag, window − 1, …).  With ``halo == 0`` the kernel only ever
    touches its core tile, so the extra zero tile the old per-package
    ``_pad_tiles`` unconditionally appended was a pure waste: one dead
    HBM→VMEM staging per grid walk.  With ``halo > 0`` the trailing zero
    tile realizes the kernels' boundary contract — the last core tile's
    "next" view is all zeros, so out-of-range products vanish without
    masking.
    """
    n = x.shape[0]
    n_pad = -(-max(n, 1) // block_t) * block_t
    if halo > 0:
        n_pad += block_t
    return jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))


def pad_to_multiple(count: int, block: int) -> int:
    """Smallest multiple of ``block`` ≥ max(count, 1)."""
    return -(-max(count, 1) // block) * block
