"""Weak-memory map-reduce engine (paper §7, §8, §10.2.1).

An *order-(h_left, h_right) weak-memory estimator* is

    Est(X)  =  Σ_{t}  k( window(t) ),      window(t) = X[t-h_left : t+h_right]

for a commutative-associative ⊕ (here: pytree sum, or any user ⊕).  This
module provides three execution strategies that are **bit-identical** in
result (property-tested):

  * :func:`serial_window_map_reduce` — the obvious single-node loop
    (vectorized with vmap), the correctness oracle;
  * :func:`block_window_map_reduce` — per-block partial reduction over an
    overlapping block structure (`repro.core.overlap`), then a global
    reduce.  Each block only touches its own padded data — zero shuffle:
    the paper's embarrassingly-parallel scheme;
  * :func:`sharded_window_map_reduce` — the same, with the block axis
    sharded over a mesh axis via shard_map and the final reduce as a single
    `psum` — the cluster-level instantiation.

Estimators that admit a faster algebraic form (autocovariance = lagged
matmuls feeding the MXU) bypass the per-center vmap by passing a
``chunk_kernel`` (masked-window reducer) built from a `repro.core.backend`
primitive — the same registry that picks between pure jnp and the Pallas
VMEM tile kernels of `repro.kernels.window_stats`; see
`repro.core.estimators.stats.block_lag_sums`.

A fourth strategy lives in `repro.core.streaming`: the same ⊕ exposed as an
explicit **PartialState monoid** (init / update(chunk) / merge / finalize)
for data that is not fully materialized — chunks of arbitrary uneven sizes,
arriving over time, possibly on different machines, with an optional
vmapped batch axis over independent series.  Estimators opt in by providing
a ``ChunkKernel`` (masked-window reducer) front-end: `stats.lag_sum_engine`
(autocovariance → Yule-Walker → ARMA) and `spectral.welch_engine` are the
references.  All four strategies are pinned to each other by
`tests/test_streaming.py`.  On a mesh, per-shard partials built from
halo-complete blocks merge with the single psum of
`repro.parallel.sharding.psum_tree`.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .overlap import OverlapSpec, make_overlapping_blocks

__all__ = [
    "tree_sum",
    "tree_zeros_like",
    "serial_window_map_reduce",
    "block_window_map_reduce",
    "scan_window_map_reduce",
    "sharded_window_map_reduce",
    "block_partials",
]

KernelFn = Callable[[jax.Array], Any]  # (window, d) -> pytree contribution


def tree_sum(a: Any, b: Any) -> Any:
    return jax.tree.map(jnp.add, a, b)


def tree_zeros_like(a: Any) -> Any:
    return jax.tree.map(jnp.zeros_like, a)


def _mask_tree(tree: Any, mask: jax.Array) -> Any:
    """Zero out contributions of invalid centers.  mask: (...,) bools matching
    the leading axes of every leaf."""

    def m(leaf):
        mb = mask.reshape(mask.shape + (1,) * (leaf.ndim - mask.ndim))
        return jnp.where(mb, leaf, 0)

    return jax.tree.map(m, tree)


def _windows(x: jax.Array, h_left: int, h_right: int) -> jax.Array:
    """All width-(h_l+1+h_r) windows of x: (n_centers, W, d).

    Centers run over t ∈ [h_left, n - h_right); edge samples with incomplete
    windows are *not* centers (they are exactly the paper's halo samples —
    owned by the neighbouring computation).
    """
    n = x.shape[0]
    w = h_left + 1 + h_right
    n_centers = n - h_left - h_right
    if n_centers <= 0:
        raise ValueError(f"series of length {n} has no full window of width {w}")
    starts = jnp.arange(n_centers)
    return jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(x, s, w, axis=0))(starts)


def serial_window_map_reduce(
    kernel: KernelFn,
    x: jax.Array,
    h_left: int,
    h_right: int,
) -> Any:
    """Oracle path: Σ_t k(X[t-h_l : t+h_r]) over all complete windows."""
    if x.ndim == 1:
        x = x[:, None]
    wins = _windows(x, h_left, h_right)
    contribs = jax.vmap(kernel)(wins)
    return jax.tree.map(lambda l: jnp.sum(l, axis=0), contribs)


def block_partials(
    kernel: Optional[KernelFn],
    blocks: jax.Array,
    spec: OverlapSpec,
    block_offset: jax.Array | int = 0,
    chunk_kernel: Optional[Callable] = None,
) -> Any:
    """Per-block partial sums: pytree with leading axis P_local.

    Every center in a block's *core* whose full window is globally valid
    contributes; centers whose window would cross the global series boundary
    are masked out (matching the serial estimator's center range exactly).

    ``block_offset`` is the global id of ``blocks[0]`` — pass
    ``jax.lax.axis_index(axis) * blocks_per_device`` when calling from inside
    shard_map on a sharded block axis (it participates in tracing).

    ``chunk_kernel`` (the `repro.core.streaming.ChunkKernel` contract:
    ``(y_padded, start_mask) → pytree``) replaces the per-center vmap with a
    fused masked-window reducer — a halo-padded block IS a valid
    ``y_padded`` with its core starts as the mask.  Build one from a
    `repro.core.backend` primitive (e.g. ``masked_lagged_sums``) to run the
    block engine through the Pallas tile path; ``kernel`` may then be None.
    """
    p_local = blocks.shape[0]
    per_block = _block_reducer(kernel, chunk_kernel, spec)
    block_ids = jnp.asarray(block_offset) + jnp.arange(p_local)
    return jax.vmap(per_block)(blocks, _core_valid_mask(block_ids, spec))


def _core_valid_mask(block_ids: jax.Array, spec: OverlapSpec) -> jax.Array:
    """Validity of each block-core center's full window against the GLOBAL
    series boundary (matching the serial estimator's center range)."""
    centers = block_ids[..., None] * spec.block_size + jnp.arange(spec.block_size)
    valid = (centers - spec.h_left >= 0) & (centers + spec.h_right <= spec.n - 1)
    # Tail padding in the last block duplicates clamped centers; mask those too.
    return valid & (centers < spec.n)


def _block_reducer(
    kernel: Optional[KernelFn], chunk_kernel: Optional[Callable], spec: OverlapSpec
) -> Callable:
    """(block, valid_mask) → pytree partial — shared by the vmapped
    (`block_partials`) and scan-folded (`scan_window_map_reduce`) paths."""
    if chunk_kernel is not None:
        return chunk_kernel
    if kernel is None:
        raise ValueError("need a per-window kernel or a chunk_kernel")

    def per_block(block, mask):
        wins = _windows(block, spec.h_left, spec.h_right)  # (block_size, W, d)
        contribs = jax.vmap(kernel)(wins)
        contribs = _mask_tree(contribs, mask)
        return jax.tree.map(lambda l: jnp.sum(l, axis=0), contribs)

    return per_block


def block_window_map_reduce(
    kernel: Optional[KernelFn],
    x: jax.Array,
    spec: OverlapSpec,
    chunk_kernel: Optional[Callable] = None,
) -> Any:
    """Embarrassingly-parallel path on one host: build overlapping blocks,
    reduce each independently, sum the P partials."""
    blocks, _ = make_overlapping_blocks(x, spec)
    partials = block_partials(kernel, blocks, spec, chunk_kernel=chunk_kernel)
    return jax.tree.map(lambda l: jnp.sum(l, axis=0), partials)


def scan_window_map_reduce(
    kernel: Optional[KernelFn],
    x: jax.Array,
    spec: OverlapSpec,
    chunk_kernel: Optional[Callable] = None,
) -> Any:
    """`block_window_map_reduce` with ``lax.scan`` accumulation: identical
    result, but the running ⊕-carry replaces the materialized (P, …)
    partial stack — O(1) memory in the block count and ONE device program
    for the whole sweep (no per-block Python dispatch).

    This is the single-host analogue of the streaming engine's
    ``consume`` path: use it when P is large enough that a stacked
    partial pytree (or the XLA fusion over it) stops fitting, or when the
    sweep runs inside a jit where sequential accumulation pipelines better
    than a P-way vmap.
    """
    blocks, _ = make_overlapping_blocks(x, spec)
    per_block = _block_reducer(kernel, chunk_kernel, spec)
    masks = _core_valid_mask(jnp.arange(blocks.shape[0]), spec)
    init = jax.eval_shape(per_block, blocks[0], masks[0])
    init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), init)

    def step(acc, inputs):
        block, mask = inputs
        return tree_sum(acc, per_block(block, mask)), None

    acc, _ = jax.lax.scan(step, init, (blocks, masks))
    return acc


def sharded_window_map_reduce(
    kernel: Optional[KernelFn],
    blocks: jax.Array,
    spec: OverlapSpec,
    mesh: Mesh,
    axis: str = "data",
    chunk_kernel: Optional[Callable] = None,
) -> Any:
    """Cluster path: block axis sharded over ``axis``; one psum at the end.

    ``blocks`` must already be device-put with the leading (P) axis sharded
    over ``axis`` (see `repro.timeseries.dataset.TimeSeriesStore`).  This is
    the paper's Spark scheme verbatim: the only cross-device communication is
    the final reduction of the (tiny) sufficient statistics, never the data.
    """
    if spec.num_blocks % mesh.shape[axis] != 0:
        raise ValueError(
            f"num_blocks {spec.num_blocks} must divide evenly over mesh axis "
            f"{axis}={mesh.shape[axis]}"
        )

    blocks_per_device = spec.num_blocks // mesh.shape[axis]

    def local(blocks_local):
        from ..parallel.sharding import psum_tree

        offset = jax.lax.axis_index(axis) * blocks_per_device
        partials = block_partials(
            kernel, blocks_local, spec, block_offset=offset, chunk_kernel=chunk_kernel
        )
        local_sum = jax.tree.map(lambda l: jnp.sum(l, axis=0), partials)
        return psum_tree(local_sum, axis)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )
    return fn(blocks)
