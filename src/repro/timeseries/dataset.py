"""TimeSeriesStore — the distributed overlapping dataset (paper §10, Fig. 4).

Owns a (possibly huge) series partitioned **along time** across a mesh axis.
Construction replicates the halo once at ingest (the paper's scheme); the
store then serves embarrassingly-parallel estimator sweeps with zero data
motion.  Alternatively a disjoint store can materialize halos on demand via
collective-permute (`halo_mode="exchange"`) — the beyond-paper variant.

On one host this degrades gracefully to a (P, W, d) array with a vmap axis;
on a mesh the leading axis is sharded (NamedSharding over ``axis``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Literal, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.overlap import OverlapSpec, make_overlapping_blocks, reconstruct
from ..core.mapreduce import block_partials
from ..core import halo as halo_mod

HaloMode = Literal["replicate", "exchange"]

__all__ = ["TimeSeriesStore"]


@functools.partial(
    jax.jit, static_argnames=("B", "width"), donate_argnums=0
)
def _scatter_append_rows(blocks, chunk, n0, *, B: int, width: int):
    """Scatter ``chunk`` (global rows [n0, n0+c)) into every padded block
    slot that owns it: row g lives in block j at slot g − j·B for every j
    with j·B ≤ g < j·B + width — its core block plus the right-halo region
    of up to ⌈(width−B)/B⌉ predecessors.  The ``blocks`` buffer is donated:
    steady-state ingest rewrites the store in place.  Out-of-range copies
    are dropped by routing them to a past-the-end block index."""
    c = chunk.shape[0]
    g = n0 + jnp.arange(c)
    copies = (width - 1) // B + 1
    for k in range(copies):
        j = g // B - k
        slot = g - j * B
        valid = (j >= 0) & (slot < width)
        jj = jnp.where(valid, j, blocks.shape[0])
        blocks = blocks.at[jj, slot].set(chunk, mode="drop")
    return blocks


@dataclasses.dataclass
class TimeSeriesStore:
    """Distributed overlapping time-series container.

    Attributes:
      blocks: (P, width, d) — padded blocks (replicate mode) or disjoint
        cores (exchange mode).
      spec: the overlap geometry.
      mesh / axis: where the block axis lives (None → single host).
      halo_mode: "replicate" (paper) or "exchange" (ppermute on demand).
    """

    blocks: jax.Array
    spec: OverlapSpec
    mesh: Optional[Mesh] = None
    axis: str = "data"
    halo_mode: HaloMode = "replicate"

    # -- construction ------------------------------------------------------
    @classmethod
    def from_series(
        cls,
        x: jax.Array,
        block_size: int,
        h_left: int,
        h_right: int,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
        halo_mode: HaloMode = "replicate",
    ) -> "TimeSeriesStore":
        if x.ndim == 1:
            x = x[:, None]
        spec = OverlapSpec(
            n=x.shape[0], block_size=block_size, h_left=h_left, h_right=h_right
        )
        if halo_mode == "replicate":
            blocks, _ = make_overlapping_blocks(x, spec)
        else:
            # Disjoint cores; halos materialized per sweep by ppermute.
            pad = spec.num_blocks * spec.block_size - spec.n
            xp = jnp.pad(x, ((0, pad), (0, 0)))
            blocks = xp.reshape(spec.num_blocks, spec.block_size, x.shape[1])
        if mesh is not None:
            if spec.num_blocks % mesh.shape[axis] != 0:
                raise ValueError(
                    f"num_blocks={spec.num_blocks} must divide over mesh axis "
                    f"{axis}={mesh.shape[axis]}"
                )
            sharding = NamedSharding(mesh, P(axis))
            blocks = jax.device_put(blocks, sharding)
        return cls(blocks=blocks, spec=spec, mesh=mesh, axis=axis, halo_mode=halo_mode)

    # -- growth --------------------------------------------------------------
    def append_rows(self, chunk: jax.Array) -> None:
        """Absorb ``chunk`` new samples at the end of the stored series with
        ONE donated device scatter — no host-side re-placement, no re-read
        of the existing blocks.

        Each appended row lands in its owning block's core AND in the
        right-halo slots of up to ``ceil(h_right / block_size)`` earlier
        blocks, so the store stays exactly
        ``from_series(concat(series, chunk), ...)`` (property-tested).  The
        block array grows by whole zero blocks only when the appended rows
        overflow the allocated capacity.  Single-host replicate-mode stores
        with causal halos only (``h_left == 0``, no mesh): a mesh-sharded
        store would need a resharding collective per growth step — callers
        there fall back to carrying the chunk in their own partial state.
        """
        if self.mesh is not None:
            raise ValueError("append_rows is single-host only (mesh stores "
                             "re-place on the next full traversal)")
        if self.halo_mode != "replicate":
            raise ValueError("append_rows requires replicate-mode halos")
        if self.spec.h_left != 0:
            raise ValueError("append_rows requires causal halos (h_left == 0)")
        if chunk.ndim == 1:
            chunk = chunk[:, None]
        c = chunk.shape[0]
        if c == 0:
            return
        if chunk.shape[1] != self.blocks.shape[-1]:
            raise ValueError(
                f"chunk has d={chunk.shape[1]}, store has d={self.blocks.shape[-1]}"
            )
        s = self.spec
        B = s.block_size
        width = s.h_left + B + s.h_right
        new_n = s.n + c
        blocks = self.blocks
        need_blocks = -(-new_n // B)
        if need_blocks > blocks.shape[0]:
            # Geometric growth: capacity at least doubles, so a steady
            # append stream pays O(log n) full-store copies (amortized O(1)
            # per row) and O(log n) retraces of the donated scatter —
            # growing to the exact need would copy the whole store every
            # block_size rows.  Over-allocated trailing blocks are all-zero
            # and sliced off by the num_blocks-aware readers.
            new_cap = max(need_blocks, 2 * blocks.shape[0])
            blocks = jnp.concatenate(
                [
                    blocks,
                    jnp.zeros(
                        (new_cap - blocks.shape[0], width, blocks.shape[-1]),
                        blocks.dtype,
                    ),
                ]
            )
        self.blocks = _scatter_append_rows(
            blocks,
            chunk.astype(blocks.dtype),
            jnp.asarray(s.n, jnp.int32),
            B=B,
            width=width,
        )
        self.spec = dataclasses.replace(s, n=new_n)

    # -- views ---------------------------------------------------------------
    def padded_blocks_local(self, blocks_local: jax.Array) -> jax.Array:
        """Inside shard_map: return halo-padded blocks for local computation.

        replicate mode: identity (halos were materialized at ingest).
        exchange mode: stitch neighbouring cores with one collective-permute.
        The two paths are bit-identical (property-tested).
        """
        if self.halo_mode == "replicate":
            return blocks_local
        s = self.spec
        p_local, nb, d = blocks_local.shape
        flat = blocks_local.reshape(p_local * nb, d)
        padded_flat = halo_mod.halo_exchange(
            flat, s.h_left, s.h_right, self.axis, time_axis=0
        )
        # Re-window into per-block padded views.
        idx = (
            jnp.arange(p_local)[:, None] * nb
            + jnp.arange(s.h_left + nb + s.h_right)[None, :]
        )
        return padded_flat[idx]

    def padded_blocks_single_host(self) -> jax.Array:
        """Single-host padded view (for tests / examples without a mesh):
        exactly ``spec.num_blocks`` blocks — any over-allocated growth
        capacity from :meth:`append_rows` is sliced off."""
        if self.halo_mode == "replicate":
            k = self.spec.num_blocks
            return self.blocks if self.blocks.shape[0] == k else self.blocks[:k]
        s = self.spec
        flat = self.blocks.reshape(-1, self.blocks.shape[-1])[: s.n]
        blocks, _ = make_overlapping_blocks(flat, s)
        return blocks

    # -- compute ---------------------------------------------------------------
    def map_reduce(self, kernel: Callable[[jax.Array], Any]) -> Any:
        """Run a weak-memory estimator over the store (paper §10.2.1).

        Single reduction of the sufficient statistic; data never moves.
        """
        s = self.spec
        if self.mesh is None:
            blocks = self.padded_blocks_single_host()
            partials = block_partials(kernel, blocks, s)
            return jax.tree.map(lambda l: jnp.sum(l, axis=0), partials)

        blocks_per_device = s.num_blocks // self.mesh.shape[self.axis]

        def local(blocks_local):
            from ..parallel.sharding import psum_tree

            offset = jax.lax.axis_index(self.axis) * blocks_per_device
            padded = self.padded_blocks_local(blocks_local)
            partials = block_partials(kernel, padded, s, block_offset=offset)
            local_sum = jax.tree.map(lambda l: jnp.sum(l, axis=0), partials)
            return psum_tree(local_sum, self.axis)

        fn = jax.shard_map(
            local, mesh=self.mesh, in_specs=P(self.axis), out_specs=P(),
            check_vma=False,
        )
        return fn(self.blocks)

    def iter_chunks(self, chunk_size: int):
        """Yield contiguous ``(≤chunk_size, d)`` chunks of the series in time
        order — the ingestion-side view of the store, consumed by
        `repro.timeseries.streaming.StreamingEstimator`.

        The final chunk may be shorter; the streaming monoid is indifferent
        to chunk granularity (property-tested).  Small-data path: gathers
        the series on the host first.
        """
        if chunk_size <= 0:
            raise ValueError(f"chunk_size must be positive, got {chunk_size}")
        x = self.to_series()
        for start in range(0, self.spec.n, chunk_size):
            yield x[start : min(start + chunk_size, self.spec.n)]

    def to_series(self) -> jax.Array:
        """Gather back the contiguous (n, d) series (small-data paths only)."""
        if self.halo_mode == "replicate":
            return reconstruct(self.padded_blocks_single_host(), self.spec)
        flat = self.blocks.reshape(-1, self.blocks.shape[-1])
        return flat[: self.spec.n]

    @property
    def replication_overhead(self) -> float:
        from ..core.overlap import replication_overhead as ro

        return ro(self.spec) if self.halo_mode == "replicate" else 0.0
