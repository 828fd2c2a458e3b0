"""Pallas TPU kernel: banded matrix-vector product, row-tiled with halos.

Paper §6.1: with a b-banded transition A, node i computes its rows of
x̂ = A x from x^{P_i⁺} (own rows ± b halo) — O(d·(2b+1)) total work.  The
VMEM instantiation: each grid step stages its row tile of the diagonals plus
THREE x tiles (previous/core/next — the spatial halo, copied side by side
into one VMEM scratch) and contracts the 2b+1 shifted views with the
diagonal columns on the VPU.

Requires b ≤ block_rows (one-tile halo), the same constraint as the paper's
b ≪ d partitioning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(diags_ref, x_prev_ref, x_core_ref, x_next_ref, y_ref, xs_ref, *, bandwidth: int, block_rows: int, d: int):
    i = pl.program_id(0)
    b = bandwidth
    r = block_rows

    diags = diags_ref[...]  # (r, 2b+1)
    # previous/core/next x tiles side by side in one VMEM scratch, so each
    # band offset is a static ref slice
    xs_ref[:r, :] = x_prev_ref[...]
    xs_ref[r : 2 * r, :] = x_core_ref[...]
    xs_ref[2 * r :, :] = x_next_ref[...]
    # global row of tile start; rows are i·r + [0, r)
    row0 = i * r
    acc = jnp.zeros(y_ref.shape, jnp.float32)
    for o in range(-b, b + 1):
        # x[row + o] lives at local index (r + o) + [0, r) within xs
        xo = xs_ref[r + o : 2 * r + o, :]  # (r, nrhs)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
        valid = ((rows + o) >= 0) & ((rows + o) < d)
        contrib = diags[:, b + o][:, None] * xo
        acc = acc + jnp.where(valid, contrib, 0.0)
    y_ref[...] = acc.astype(y_ref.dtype)


def banded_matvec_pallas(
    diags: jax.Array,
    x: jax.Array,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """y = A x from stacked diagonals.

    Args:
      diags: (d, 2b+1) with d % block_rows == 0 (ops.py pads) and
        b ≤ block_rows.
      x: (d, nrhs).

    Returns (d, nrhs) float32.
    """
    d, w = diags.shape
    b = (w - 1) // 2
    nrhs = x.shape[1]
    if d % block_rows:
        raise ValueError(f"d={d} must be a multiple of block_rows={block_rows}")
    if b > block_rows:
        raise ValueError(f"bandwidth {b} must be ≤ block_rows {block_rows}")
    n_tiles = d // block_rows
    grid = (n_tiles,)
    return pl.pallas_call(
        functools.partial(_kernel, bandwidth=b, block_rows=block_rows, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, nrhs), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((block_rows, nrhs), lambda i: (i, 0)),
            pl.BlockSpec(
                (block_rows, nrhs), lambda i: (jnp.minimum(i + 1, n_tiles - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((block_rows, nrhs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, nrhs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((3 * block_rows, nrhs), jnp.float32)],
        interpret=interpret,
    )(diags, x, x, x)
