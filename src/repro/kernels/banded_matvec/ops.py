"""Public wrapper for the banded matvec kernel (paper §6.1 predictor).

The op is differentiable: a custom VJP makes the Pallas forward usable
inside `jax.grad` (the §6.2 conditional-MLE loss of
`repro.core.estimators.spatial.fit_banded_ar`), where previously the jnp
backend was pinned.  Both cotangents are banded-local:

  * ∂L/∂x = Aᵀ g — ANOTHER banded matvec, run through the same Pallas
    kernel against the transposed band (:func:`band_transpose`);
  * ∂L/∂diags[r, b+o] = g_r · x_{r+o} — a (d, 2b+1)-shaped neighbourhood
    gather-product (VPU-shaped, evaluated as one fused jnp contraction on
    device; there is no matmul to tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tiling import align_rows, resolve_block
from .kernel import banded_matvec_pallas
from .ref import banded_matvec_ref


def band_transpose(diags: jax.Array) -> jax.Array:
    """Diagonal storage of Aᵀ from the diagonal storage of A.

    ``Aᵀ[r, r+o] = A[r+o, r]``, so ``out[r, b+o] = diags[r+o, b−o]`` with
    zeros where ``r+o`` falls off the matrix.
    """
    d, w = diags.shape
    b = (w - 1) // 2
    rows = jnp.arange(d)[:, None] + jnp.arange(-b, b + 1)[None, :]
    valid = (rows >= 0) & (rows < d)
    cols = jnp.arange(w - 1, -1, -1)[None, :]
    vals = diags[jnp.clip(rows, 0, d - 1), jnp.broadcast_to(cols, rows.shape)]
    return jnp.where(valid, vals, 0.0)


def _forward(diags, x, block_rows: int, interpret: bool):
    """Padded Pallas forward for (d, 2b+1) diags and (d, nrhs) x."""
    d, w = diags.shape
    b = (w - 1) // 2
    br = align_rows(max(min(block_rows, d), b))
    d_pad = -(-d // br) * br
    if d_pad != d:
        diags = jnp.pad(diags, ((0, d_pad - d), (0, 0)))
        x = jnp.pad(x, ((0, d_pad - d), (0, 0)))
    # NOTE: the kernel masks by the PADDED d; rows beyond the true d have
    # zero diagonals so their outputs are zero, and true rows reading into
    # the pad region read zero x — both exact.
    return banded_matvec_pallas(
        diags.astype(jnp.float32),
        x.astype(jnp.float32),
        block_rows=br,
        interpret=interpret,
    )[:d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _banded_matvec_vjp(diags, x, block_rows, interpret):
    return _forward(diags, x, block_rows, interpret)


def _banded_matvec_fwd(diags, x, block_rows, interpret):
    return _forward(diags, x, block_rows, interpret), (diags, x)


def _banded_matvec_bwd(block_rows, interpret, res, g):
    diags, x = res
    d, w = diags.shape
    b = (w - 1) // 2
    # dx = Aᵀ g: the same tiled kernel, transposed band.
    dx = _forward(band_transpose(diags), g, block_rows, interpret)
    # ddiags[r, b+o] = Σ_n g[r, n] · x[r+o, n] (0 where r+o off-range).
    cols = jnp.arange(d)[:, None] + jnp.arange(-b, b + 1)[None, :]
    valid = (cols >= 0) & (cols < d)
    xn = x.astype(jnp.float32)[jnp.clip(cols, 0, d - 1)]  # (d, w, nrhs)
    xn = jnp.where(valid[..., None], xn, 0.0)
    ddiags = jnp.einsum("dn,dwn->dw", g.astype(jnp.float32), xn)
    return ddiags.astype(diags.dtype), dx.astype(x.dtype)


_banded_matvec_vjp.defvjp(_banded_matvec_fwd, _banded_matvec_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _banded_matvec_jit(
    diags: jax.Array,
    x: jax.Array,
    *,
    block_rows: int,
    interpret: bool,
) -> jax.Array:
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    y = _banded_matvec_vjp(diags, x, block_rows, interpret)
    return y[:, 0] if squeeze else y


def banded_matvec(
    diags: jax.Array,
    x: jax.Array,
    *,
    block_rows: "int | None" = None,
    interpret: bool = False,
) -> jax.Array:
    """y = A x with b-banded A in diagonal storage.  Differentiable (custom
    VJP; both cotangents stay banded-local — see the module docstring).

    ``block_rows=None`` resolves through the calibrated block table
    (`repro.kernels.tiling.resolve_block`), outside the jit boundary.

    Args:
      diags: (d, 2b+1);  x: (d,) or (d, nrhs).

    Returns y with x's trailing shape, float32.
    """
    block_rows = resolve_block("banded_matvec", "block_rows", block_rows)
    return _banded_matvec_jit(
        diags, x, block_rows=block_rows, interpret=interpret
    )


def banded_matvec_reference(diags: jax.Array, x: jax.Array) -> jax.Array:
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    y = banded_matvec_ref(diags.astype(jnp.float32), x.astype(jnp.float32))
    return y[:, 0] if squeeze else y
