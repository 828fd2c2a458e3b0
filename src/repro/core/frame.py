"""SeriesFrame — the lazy, placement-aware front door to every read path.

The paper's algebra says every weak-memory statistic is one computational
pattern: map a short-window kernel, ⊕-reduce the partials over an
overlapping distributed structure.  The repo grew four public spellings of
that pattern — raw estimator calls, `plan.analyze`, `StreamingEstimator`,
`RollingStatsService` — each forcing the caller to pick a traversal
strategy by hand.  This module is the single front door that removes the
choice:

  * a :class:`SeriesFrame` holds data **placement** (a materialized array,
    a stream of chunks, or mesh-placed overlapping shards) plus a set of
    **deferred estimator requests**.  ``.autocovariance(h)``,
    ``.yule_walker(p)``, ``.arma(p, q)``, ``.moments(w)``, ``.welch(...)``
    and ``.map_reduce(kernel, ...)`` each return a :class:`Deferred` handle
    and read nothing;
  * ``.collect()`` compiles everything pending into ONE fused
    `repro.core.plan.StatPlan` and picks the execution strategy **from the
    placement**: a monolithic jitted traversal for arrays, a
    ``consume``-style ``lax.scan`` over equal-length chunk stacks for
    streams, and halo-complete per-shard partials reduced with the single
    psum of `repro.parallel.sharding.psum_tree` for mesh-placed frames.
    However many requests are pending, the series is walked once;
  * results are **memoized**: a second ``.collect()`` (or
    ``Deferred.result()``) with no ingest in between reads the cache —
    zero traversals, zero primitive calls;
  * ``.append(chunk)`` invalidates the memo and folds the new samples into
    the carried fused `PartialState` — the weak-memory ⊕, so re-collecting
    after an append costs one walk of the *new* samples only.  History is
    never re-read.

Placement-aware laziness goes one level deeper for ``from_sharded``: when
built from a raw series, the overlapping blocks are not placed until the
first ``.collect()`` — by which point the fused plan knows the widest
member window, so the replicated halo is sized exactly (``W_fused − 1``)
instead of guessed.

:class:`FrameSession` is the multi-tenant variant (the ROADMAP
"multi-tenant plan serving" item): the same deferred-request surface, but
the carried state is one stacked per-user fused-plan state inside
`repro.serving.rolling.RollingStatsService` — ingest is a single donated
scatter program shared by every user, queries gather + ⊕-fold + finalize.
``window=`` turns on the sliding-window eviction mode (a ring of
window-aligned sub-states; see `RollingStatsService`), so served
statistics cover only the retained horizon.

`plan.analyze` and `repro.timeseries.StreamingEstimator` are thin shims
over this module — there is exactly one query path to maintain.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .backend import BackendSpec, get_backend
from .plan import (
    StatPlan,
    StatRequest,
    anomaly_request,
    arma_request,
    autocovariance_request,
    forecast_request,
    kernel_request,
    moments_request,
    welch_request,
    yule_walker_request,
)
from .streaming import PartialState, StreamingEngine

__all__ = ["SeriesFrame", "FrameSession", "Deferred"]


@dataclasses.dataclass(frozen=True, eq=False)
class Deferred:
    """Handle to one pending request of a frame.

    ``result()`` triggers the frame's (memoized) ``collect()`` and returns
    this request's entry — so touching N handles still costs one traversal.
    """

    frame: "SeriesFrame"
    name: str

    def result(self) -> Any:
        return self.frame.collect()[self.name]


class _DeferredRequests:
    """The deferred-request surface shared by SeriesFrame and FrameSession.

    Subclasses implement ``_defer(request) -> handle``; every method below
    records one `repro.core.plan.StatRequest` and reads no data.
    """

    def _defer(self, req: StatRequest):
        raise NotImplementedError

    def _unique_name(self, base: str) -> str:
        counts = self._name_counts
        counts[base] = counts.get(base, 0) + 1
        return base if counts[base] == 1 else f"{base}_{counts[base]}"

    def autocovariance(self, max_lag: int, normalization: str = "paper",
                       name: Optional[str] = None):
        """Defer γ̂(0..max_lag) — shares the plan's lagged-sum entry."""
        return self._defer(autocovariance_request(max_lag, normalization, name))

    def yule_walker(self, p: int, normalization: str = "standard",
                    name: Optional[str] = None):
        """Defer an order-p AR fit (A, Σ)."""
        return self._defer(yule_walker_request(p, normalization, name))

    def arma(self, p: int, q: int, m: Optional[int] = None,
             name: Optional[str] = None):
        """Defer an ARMA(p, q) fit (A, B, Σ)."""
        return self._defer(arma_request(p, q, m, name))

    def moments(self, window: int, name: Optional[str] = None):
        """Defer aggregate windowed moments ({"mean", "var", "count"}).

        Distinct windows across several ``moments`` calls still ride ONE
        traversal: the backend's multi-window ``fused_lagged_moments``
        accumulates every window from the same resident tile.
        """
        return self._defer(moments_request(window, name))

    def welch(self, nperseg: int = 256, overlap: Optional[int] = None,
              fs: float = 1.0, name: Optional[str] = None):
        """Defer a Welch PSD (freqs, psd)."""
        return self._defer(welch_request(nperseg, overlap, fs, name))

    def forecast(self, horizon: int, model: str = "ar", p: int = 4,
                 q: int = 1, m: Optional[int] = None,
                 max_period: Optional[int] = None,
                 name: Optional[str] = None):
        """Defer a multi-horizon forecast served from the plan's carried
        lag state: ``{"pred": (horizon, d), "sigma": (d, d)}`` (plus
        ``"period"`` when ``model="auto"``, which also needs a deferred
        ``.welch(...)`` member for periodicity detection).  See
        `repro.core.forecast.forecast_request`."""
        return self._defer(
            forecast_request(horizon, model, p, q, m, max_period, name)
        )

    def anomaly_scores(self, model: str = "ar", p: int = 4, q: int = 1,
                       m: Optional[int] = None,
                       max_period: Optional[int] = None,
                       name: Optional[str] = None):
        """Defer standardized innovation residuals over the carried tail
        window (per-dim ``z`` and a Mahalanobis ``score``, with a validity
        mask).  See `repro.core.forecast.anomaly_request`."""
        return self._defer(anomaly_request(model, p, q, m, max_period, name))

    def map_reduce(self, chunk_kernel: Callable, h_right: int, h_left: int = 0,
                   stride: int = 1, takes_offset: bool = False,
                   finalizer: Optional[Callable] = None,
                   name: str = "map_reduce"):
        """Defer a generic weak-memory member (any `ChunkKernel`); see
        `repro.core.plan.kernel_request` for the kernel/finalizer contract."""
        return self._defer(
            kernel_request(name, chunk_kernel, h_right, h_left, stride,
                           takes_offset, finalizer)
        )


class SeriesFrame(_DeferredRequests):
    """Lazy dataframe-style session over one series: defer, collect, append.

    Build with :meth:`from_array`, :meth:`from_chunks`, :meth:`from_sharded`
    (or :meth:`from_engine` for the raw-engine mode `StreamingEstimator`
    wraps).  See the module docstring for the execution model.
    """

    def __init__(self, placement: str, d: Optional[int], backend: BackendSpec):
        self._placement = placement
        self._d = d
        self._backend = get_backend(backend)
        # deferred requests (names already deduped) not yet / already compiled
        self._recorded: list[StatRequest] = []
        self._name_counts: dict[str, int] = {}
        self._new_requests = False
        # compiled query state
        self._plan: Optional[StatPlan] = None
        self._states: Optional[tuple] = None
        self._results: Optional[dict] = None
        # placement payloads
        self._x: Optional[jax.Array] = None          # array placement
        self._chunk_source = None                    # chunks: undrained source
        self._chunk_list: Optional[list] = None      # chunks: drained, pre-ingest
        self._store = None                           # sharded: TimeSeriesStore
        self._mesh: Optional[Mesh] = None
        self._axis = "data"
        self._block_size = 8192
        self._store_owned = False                    # frame built the store
        self._appended: list = []                    # array appends (lazy concat)
        self._pending: list = []                     # sharded appends (replay)
        self._replayable = True
        self._n = 0

    # ------------------------------------------------------------ builders
    @classmethod
    def from_array(cls, x: jax.Array, backend: BackendSpec = None) -> "SeriesFrame":
        """Frame over a fully materialized (n,) or (n, d) series.

        Collect strategy: ONE monolithic jitted traversal.  The array is
        retained, so adding new requests after a collect replans (one fresh
        traversal serving everything) instead of failing.
        """
        x = _as_2d(jnp.asarray(x))
        frame = cls("array", x.shape[1], backend)
        frame._x = x
        frame._n = x.shape[0]
        return frame

    @classmethod
    def from_chunks(
        cls,
        chunks,
        backend: BackendSpec = None,
        chunk_size: int = 4096,
    ) -> "SeriesFrame":
        """Frame over a stream of time-ordered chunks.

        ``chunks`` is any iterable of (c, d) arrays — or a
        `repro.timeseries.TimeSeriesStore`, streamed via
        ``iter_chunks(chunk_size)``.  Nothing is read until ``collect()``,
        which folds equal-length runs with the scan-driven ``consume``
        ingest (one ``lax.scan`` program, donated carry) and then discards
        the raw chunks — the weak-memory placement.  Consequently new
        requests after the first collect raise: declare everything up
        front, or use :meth:`from_array`.
        """
        frame = cls("chunks", None, backend)
        frame._chunk_source = (chunks, chunk_size)
        return frame

    @classmethod
    def from_sharded(
        cls,
        data,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
        block_size: int = 8192,
        backend: BackendSpec = None,
    ) -> "SeriesFrame":
        """Frame over mesh-placed overlapping shards (paper §10).

        ``data`` is a raw series — placed lazily at the first ``collect()``,
        when the compiled plan knows the widest member window, so the
        replicated halo is sized exactly ``W_fused − 1`` — or an existing
        `TimeSeriesStore` (``h_left`` must be 0 and ``h_right`` must cover
        the plan's widest window).  Collect strategy: per-shard
        halo-complete partials, reduced with the single psum of
        `repro.parallel.sharding.psum_tree`; the raw data never moves.
        """
        frame = cls("sharded", None, backend)
        if hasattr(data, "spec") and hasattr(data, "blocks"):  # TimeSeriesStore
            frame._store = data
            frame._d = data.blocks.shape[-1]
            frame._n = data.spec.n
            frame._mesh = data.mesh
            frame._axis = data.axis
        else:
            x = _as_2d(jnp.asarray(data))
            frame._x = x
            frame._d = x.shape[1]
            frame._n = x.shape[0]
            frame._mesh = mesh
            frame._axis = axis
            frame._block_size = block_size
        return frame

    @classmethod
    def from_engine(
        cls,
        engine: StreamingEngine,
        batch: Optional[int] = None,
        t0: int | jax.Array = 0,
    ) -> "SeriesFrame":
        """Raw-engine mode: the frame carries ONE engine's PartialState and
        only provides the ingest machinery (update / scan consume / merge)
        plus :meth:`finalize_with`.  This is the state-keeping core
        `repro.timeseries.StreamingEstimator` is a shim over; request-mode
        frames compile their fused plan onto the same machinery.
        """
        frame = cls("engine", engine.d, engine.backend)
        frame._engine = engine
        frame._batch = batch
        if batch is None:
            frame._e_state = engine.init(t0)
            frame._e_update = engine.update_jit
            frame._e_merge = engine.merge_jit
            frame._e_consume = engine.consume
        else:
            frame._e_state = engine.init_batch(batch, t0)
            frame._e_update = engine.update_batch
            frame._e_merge = engine.merge_batch
            frame._e_consume = engine.consume_batch
        return frame

    # ------------------------------------------------------- request intake
    def _defer(self, req: StatRequest) -> Deferred:
        if self._placement == "engine":
            raise ValueError(
                "engine-mode frames carry a raw StreamingEngine state; "
                "deferred estimator requests need a data-placement frame "
                "(from_array / from_chunks / from_sharded)"
            )
        if not isinstance(req, StatRequest):
            raise TypeError(
                f"requests must be StatRequest (see the *_request factories), "
                f"got {type(req).__name__}"
            )
        name = self._unique_name(req.name or req.default_name())
        self._recorded.append(dataclasses.replace(req, name=name))
        self._new_requests = True
        return Deferred(self, name)

    # -------------------------------------------------------------- collect
    def collect(self) -> dict:
        """Run (or read back) every deferred request: ``{name: result}``.

        First call compiles ONE fused plan and traverses the data once with
        the placement's strategy; repeated calls with no ingest in between
        return the memoized results without touching the data.
        """
        if self._placement == "engine":
            raise ValueError("engine-mode frames finalize with finalize_with()")
        if not self._recorded:
            raise ValueError(
                "nothing to collect — defer at least one request first "
                "(.autocovariance / .yule_walker / .arma / .moments / "
                ".welch / .map_reduce)"
            )
        if self._plan is not None and not self._new_requests:
            if self._results is None:
                self._results = self._plan.finalize(self._states)
            return dict(self._results)

        if self._plan is not None and not self._replayable:
            raise ValueError(
                "new requests after the first collect need the history, but "
                "this placement discarded it (weak memory); declare every "
                "request before collecting, or build with from_array"
            )
        plan = StatPlan(list(self._recorded), d=self._require_d(),
                        backend=self._backend)
        self._states = self._traverse(plan)
        self._plan = plan
        self._new_requests = False
        self._results = plan.finalize(self._states)
        return dict(self._results)

    @property
    def num_traversals(self) -> int:
        """Traversal groups one evaluation costs (1 unless non-offset-aware
        strided generic kernels force grouped sub-plans)."""
        if self._plan is None:
            plan = StatPlan(list(self._recorded), d=self._require_d(),
                            backend=self._backend)
            return plan.num_traversals
        return self._plan.num_traversals

    # --------------------------------------------------------------- append
    def append(self, chunk: jax.Array) -> "SeriesFrame":
        """Absorb new samples at the end of the series.

        Invalidates the memoized results; if a plan is already compiled the
        chunk folds into the carried fused `PartialState` with the
        weak-memory ⊕ — history is never re-read, so a following
        ``collect()`` costs one walk of these samples only.  The fold runs
        through the engines' cached *donated* jitted updates: the carried
        states' buffers are reused in place, so a steady append stream of
        same-shape chunks re-traces nothing and allocates nothing per
        chunk, with zero device→host copies on the whole path.
        """
        if self._placement == "engine":
            self._e_state = self._e_update(self._e_state, chunk)
            return self
        chunk = _as_2d(jnp.asarray(chunk))
        if self._d is not None and chunk.shape[1] != self._d:
            raise ValueError(f"chunk has d={chunk.shape[1]}, frame has d={self._d}")
        self._results = None
        if self._placement == "array":
            # buffered, not concatenated: an O(history) copy per append
            # would defeat the incremental fold.  The buffer is only
            # materialized if a replan (new requests) re-reads the series.
            self._appended.append(chunk)
        elif self._placement == "chunks":
            if self._plan is None:
                self._tail_chunks().append(chunk)
        elif self._can_scatter_append():
            # sharded with an owned single-host store: the chunk scatters
            # INTO the device store (one donated scatter program), so
            # replans re-read a complete series — no host-side replay list.
            self._store.append_rows(chunk)
        else:  # sharded pre-plan / mesh / user store: retained for replans
            self._pending.append(chunk)
        if self._plan is not None:
            self._states = self._plan.update_donated(self._states, chunk)
        self._n += chunk.shape[0]
        return self

    def _can_scatter_append(self) -> bool:
        """Sharded appends scatter into the store when the frame owns a
        single-host replicate-mode store with causal halos — the
        `TimeSeriesStore.append_rows` contract.  Mesh-placed or caller-owned
        stores keep the host-side pending list (a growth step there would
        reshard or mutate shared state)."""
        return (
            self._store is not None
            and self._store_owned
            and self._store.mesh is None
            and self._store.halo_mode == "replicate"
            and self._store.spec.h_left == 0
        )

    @property
    def length(self) -> int | jax.Array:
        """Samples ingested so far (engine mode: per the carried state)."""
        if self._placement == "engine":
            return self._e_state.length
        return self._n

    @property
    def backend(self):
        """The compute backend every traversal runs through."""
        if self._placement == "engine":
            return self._engine.backend
        return self._backend

    # ----------------------------------------------------- engine-mode API
    @property
    def state(self) -> PartialState:
        """The carried PartialState (engine mode)."""
        self._require_engine()
        return self._e_state

    @state.setter
    def state(self, value: PartialState) -> None:
        self._require_engine()
        self._e_state = value

    def consume(self, chunk_stack: jax.Array) -> "SeriesFrame":
        """Scan-driven ingest of an equal-length chunk stack (engine mode):
        one ``lax.scan`` program, carried state donated."""
        self._require_engine()
        self._e_state = self._e_consume(self._e_state, chunk_stack)
        return self

    def merge_state(self, other: PartialState) -> "SeriesFrame":
        """⊕ a peer's PartialState into this frame's (engine mode)."""
        self._require_engine()
        self._e_state = self._e_merge(self._e_state, other)
        return self

    def finalize_with(self, finalizer: Callable, *args, **kwargs) -> Any:
        """Apply an estimator front-end ``finalizer(engine, state, ...)`` to
        the carried state (engine mode); vmapped over the batch axis."""
        self._require_engine()
        if self._batch is None:
            return finalizer(self._engine, self._e_state, *args, **kwargs)
        return jax.vmap(
            lambda s: finalizer(self._engine, s, *args, **kwargs)
        )(self._e_state)

    def _require_engine(self):
        if self._placement != "engine":
            raise ValueError("this frame is not in engine mode (from_engine)")

    # ------------------------------------------------------------ internals
    def _require_d(self) -> int:
        if self._d is None:
            self._drain_chunks()
        if self._d is None:
            raise ValueError("cannot infer the series dimension from an empty "
                             "chunk source; ingest at least one chunk")
        return self._d

    def _tail_chunks(self) -> list:
        if self._chunk_list is None:
            self._chunk_list = []
        return self._chunk_list

    def _drain_chunks(self) -> list:
        """Materialize the chunk source exactly once (chunks placement)."""
        if self._chunk_source is not None:
            source, chunk_size = self._chunk_source
            if hasattr(source, "iter_chunks"):  # TimeSeriesStore
                source = source.iter_chunks(chunk_size)
            drained = [_as_2d(jnp.asarray(c)) for c in source]
            # user appends recorded before the first collect come after the
            # source, in arrival order (their lengths are already counted)
            self._chunk_list = drained + (self._chunk_list or [])
            self._chunk_source = None
            for c in drained:
                self._n += c.shape[0]
            if self._chunk_list:
                self._d = self._chunk_list[0].shape[1]
        return self._chunk_list or []

    def _traverse(self, plan: StatPlan) -> tuple:
        if self._placement == "array":
            if self._appended:
                self._x = jnp.concatenate([self._x] + self._appended)
                self._appended = []
            return jax.jit(plan.from_chunk)(self._x)
        if self._placement == "chunks":
            return self._traverse_chunks(plan)
        return self._traverse_sharded(plan)

    def _traverse_chunks(self, plan: StatPlan) -> tuple:
        chunks = self._drain_chunks()
        states = plan.init()
        i = 0
        while i < len(chunks):
            j = i
            while (
                j < len(chunks)
                and chunks[j].shape[0] == chunks[i].shape[0]
                and chunks[j].shape[0] > 0
            ):
                j += 1
            if j == i:  # zero-length chunk: neutral, skip
                i += 1
                continue
            run = chunks[i:j]
            if len(run) > 1:
                states = plan.consume(states, jnp.stack(run))
            else:
                states = plan.update(states, run[0])
            i = j
        # weak memory: the raw chunks are gone once folded
        self._chunk_list = []
        self._replayable = False
        return states

    # -- sharded strategy ---------------------------------------------------
    def _ensure_store(self, plan: StatPlan):
        carry_max = max(g.engine.carry for g in plan.groups)
        if self._store is not None:
            spec = self._store.spec
            if spec.h_left != 0 or spec.h_right < carry_max:
                if not self._store_owned:
                    raise ValueError(
                        f"the supplied store's halo (h_left={spec.h_left}, "
                        f"h_right={spec.h_right}) cannot serve the plan's "
                        f"widest window ({carry_max + 1}); rebuild it with "
                        f"h_left=0, h_right>={carry_max}"
                    )
                # frame-built store from an earlier, narrower plan: re-place
                # with the exact halo (a replan is already a full traversal)
                self._x = self._store.to_series()
                self._store = None
        if self._store is None:
            from ..timeseries.dataset import TimeSeriesStore

            self._store = TimeSeriesStore.from_series(
                self._x,
                block_size=min(self._block_size, max(self._x.shape[0], 1)),
                h_left=0,
                h_right=carry_max,
                mesh=self._mesh,
                axis=self._axis,
            )
            self._store_owned = True
            self._x = None  # the store owns the data now
        return self._store

    def _traverse_sharded(self, plan: StatPlan) -> tuple:
        store = self._ensure_store(plan)
        spec = store.spec
        B, n = spec.block_size, spec.n
        groups = plan.groups

        def per_block(block, bid):
            g_starts = bid * B + jnp.arange(B)
            stats = []
            for g in groups:
                # same start set as the monolithic walk: full fused window
                # inside the global series, group-stride aligned
                mask = g_starts + g.engine.window <= n
                if g.stride > 1:
                    mask = mask & (g_starts % g.stride == 0)
                stats.append(
                    g.engine._call_kernel(
                        block[: B + g.engine.carry], mask, bid * B
                    )
                )
            core_valid = (g_starts < n)[:, None]
            ssum = jnp.sum(jnp.where(core_valid, block[:B], 0.0), axis=0)
            return tuple(stats), ssum

        if store.mesh is None:
            blocks = store.padded_blocks_single_host()
            stats, ssums = jax.vmap(per_block)(
                blocks, jnp.arange(spec.num_blocks)
            )
            stat_sum = jax.tree.map(lambda l: jnp.sum(l, axis=0), stats)
            sample_sum = jnp.sum(ssums, axis=0)
        else:
            from ..parallel.sharding import psum_tree

            per_dev = spec.num_blocks // store.mesh.shape[store.axis]

            def local(blocks_local):
                offset = jax.lax.axis_index(store.axis) * per_dev
                padded = store.padded_blocks_local(blocks_local)
                stats, ssums = jax.vmap(per_block)(
                    padded, offset + jnp.arange(per_dev)
                )
                partial = (
                    jax.tree.map(lambda l: jnp.sum(l, axis=0), stats),
                    jnp.sum(ssums, axis=0),
                )
                return psum_tree(partial, store.axis)

            fn = jax.shard_map(
                local, mesh=store.mesh, in_specs=P(store.axis), out_specs=P(),
                check_vma=False,
            )
            stat_sum, sample_sum = fn(store.blocks)

        carry_max = max(g.engine.carry for g in groups)
        head_full, tail_full = self._series_edges(store, carry_max)
        # Each group's state must own ITS OWN buffers: the donated append
        # path (`StatPlan.update_donated`) consumes group states in place
        # one by one, so a leaf shared between two groups would be freed by
        # the first group's update and read-after-delete by the second.
        # Single-group plans (every built-in request) skip the copies.
        own = (lambda a: a) if len(groups) == 1 else jnp.copy
        states = []
        for g, stat in zip(groups, stat_sum):
            c = g.engine.carry
            states.append(
                PartialState(
                    stat=stat,
                    sample_sum=own(sample_sum),
                    head=own(head_full[:c]),
                    tail=own(tail_full[carry_max - c :]) if c > 0
                    else jnp.zeros((0, self._d)),
                    length=jnp.asarray(n, jnp.int32),
                    t0=jnp.asarray(0, jnp.int32),
                )
            )
        states = tuple(states)
        for chunk in self._pending:
            states = plan.update(states, chunk)
        if self._pending and self._can_scatter_append():
            # appends buffered before the store existed migrate into it now
            # (one donated scatter each), so future replans re-read a
            # complete series and the host-side replay list dies here.
            for chunk in self._pending:
                self._store.append_rows(chunk)
            self._pending = []
        return states

    def _series_edges(self, store, carry_max: int):
        """First / last ``carry_max`` samples of the stored series, gathered
        from the block cores (a ``carry_max × d`` read, never the series):
        head left-aligned, tail right-aligned, zero where off-range — the
        exact `PartialState` halo contract."""
        spec = store.spec
        n, B = spec.n, spec.block_size
        d = store.blocks.shape[-1]
        if carry_max == 0:
            empty = jnp.zeros((0, d))
            return empty, empty
        rows = jnp.arange(carry_max)
        hv = rows < n
        hr = jnp.clip(rows, 0, n - 1)
        head = jnp.where(hv[:, None], store.blocks[hr // B, hr % B], 0.0)
        gidx = n - carry_max + rows
        tv = gidx >= 0
        tr = jnp.clip(gidx, 0, n - 1)
        tail = jnp.where(tv[:, None], store.blocks[tr // B, tr % B], 0.0)
        return head, tail


class FrameSession(_DeferredRequests):
    """Multi-tenant deferred statistics: one fused plan, millions of users.

    The session compiles its deferred requests into ONE
    `repro.core.plan.StatPlan` at the first ingest and carries a single
    stacked per-user fused-plan state inside
    `repro.serving.rolling.RollingStatsService` — so every user's N
    statistics ride one donated scatter-ingest program on the write path
    and one gather + ⊕-fold + fused finalize on the read path.  Per-user
    results equal a dedicated per-user :class:`SeriesFrame` to float
    round-off (pinned by tests/test_frame.py).

    Args:
      d: series dimension.
      num_users: number of user series served.
      requests: optional pre-built `StatRequest` list; the deferred-request
        methods (``.autocovariance(...)`` etc.) also work until the first
        ingest compiles the plan.
      num_shards: independent ingest lanes (growing mode only).
      window / num_buckets: sliding-window eviction mode — per-user state
        is a ring of ``num_buckets`` window-aligned sub-states retaining
        the last ≤ ``window`` samples; queries cover only the retained
        horizon (see `RollingStatsService`).
      backend: compute-backend spec for every traversal.
      compensated: thread Neumaier error companions through every group's
        ⊕-folds (long-horizon drift control for always-on sessions; see
        `repro.core.integrity`).  Snapshots from a compensated session only
        restore into a compensated session (the extra companion leaves are
        part of the state's structure).
      devices: chips the tenant axis is split over, in contiguous blocks
        (growing mode only; see `RollingStatsService`).  Not to be confused
        with ``num_shards``, which counts ingest lanes.  Batched answers
        stay split on the tenant axis, each chip's tenants finalized on
        that chip.
    """

    def __init__(
        self,
        d: int,
        num_users: int,
        requests: Optional[Sequence[StatRequest]] = None,
        num_shards: int = 1,
        window: Optional[int] = None,
        num_buckets: Optional[int] = None,
        backend: BackendSpec = None,
        compensated: bool = False,
        devices: int = 1,
    ):
        if window is not None and devices > 1:
            raise ValueError(
                f"eviction mode (window={window}) keeps its tenants on one "
                f"chip; devices={devices} is not supported with it"
            )
        self.d = d
        self.num_users = num_users
        self.num_shards = num_shards
        self.devices = devices
        self.window = window
        self._num_buckets = num_buckets
        self._backend = backend
        self.compensated = compensated
        self._recorded: list[StatRequest] = []
        self._name_counts: dict[str, int] = {}
        self._plan: Optional[StatPlan] = None
        self._services: Optional[list] = None
        for req in requests or []:
            self._defer(req)

    def _defer(self, req: StatRequest) -> str:
        if self._plan is not None:
            raise ValueError(
                "the session's fused plan is compiled at the first ingest; "
                "declare every request before ingesting"
            )
        if not isinstance(req, StatRequest):
            raise TypeError(
                f"requests must be StatRequest (see the *_request factories), "
                f"got {type(req).__name__}"
            )
        name = self._unique_name(req.name or req.default_name())
        self._recorded.append(dataclasses.replace(req, name=name))
        return name

    @property
    def plan(self) -> StatPlan:
        self._ensure_plan()
        return self._plan

    @property
    def request_names(self) -> tuple:
        """Names of every deferred request, in declaration order — the keys
        of ``query`` / ``query_batch`` results (and the valid values for
        the gateway's ``only=`` query-kind filter)."""
        return tuple(r.name for r in self._recorded)

    def _ensure_plan(self):
        if self._plan is not None:
            return
        if not self._recorded:
            raise ValueError("a session needs at least one deferred request")
        self._plan = StatPlan(list(self._recorded), d=self.d,
                              backend=self._backend,
                              compensated=self.compensated)
        from ..serving.rolling import TENANTS, RollingStatsService

        self._services = [
            RollingStatsService(
                g.engine,
                self.num_users,
                num_shards=self.num_shards,
                window=self.window,
                num_buckets=self._num_buckets,
                devices=self.devices,
            )
            for g in self._plan.groups
        ]
        # jit caches one trace per requested batch size, so a steady read
        # load (the gateway's per-tick coalesced query) re-traces nothing:
        # the whole multi-user read is the services' gather/⊕-fold programs
        # plus this ONE vmapped fused-finalize program, run by each chip
        # over its own tenants.
        finalize = jax.vmap(
            lambda states: self._plan.finalize(tuple(states), cache=False))

        def finalize_batch(states):
            return jax.shard_map(finalize, mesh=self._services[0].mesh,
                                 in_specs=P(TENANTS), out_specs=P(TENANTS),
                                 check_vma=False)(states)

        self._finalize_batch = jax.jit(
            finalize_batch,
            out_shardings=NamedSharding(self._services[0].mesh, P(TENANTS)))

    # -- write path ----------------------------------------------------------
    def ingest(
        self,
        user_ids: jax.Array,
        chunks: jax.Array,
        shard: int = 0,
        t0: Optional[jax.Array] = None,
    ) -> list:
        """Absorb one arrival batch: ``chunks[i]`` extends user
        ``user_ids[i]``'s series (see `RollingStatsService.ingest`).
        Built-in requests compile to a single plan group, so this is ONE
        donated scatter-update program however many statistics the session
        tracks.  Returns the batch as each plan group placed it."""
        self._ensure_plan()
        return [svc.ingest(user_ids, chunks, shard=shard, t0=t0)
                for svc in self._services]

    # -- read path -----------------------------------------------------------
    def query(self, user_id: int) -> dict:
        """All deferred statistics for one user: ``{request_name: result}``,
        equal to a dedicated per-user SeriesFrame's ``collect()``."""
        self._ensure_plan()
        states = tuple(svc.partial(user_id) for svc in self._services)
        return self._plan.finalize(states, cache=False)

    def lay_out(self, user_ids) -> tuple:
        """``(laid, rows)``: the batch's ids arranged by owner chip, and
        each id's row there (None where they already are); see
        `RollingStatsService.lay_out`."""
        self._ensure_plan()
        return self._services[0].lay_out(user_ids)

    def query_batch(self, user_ids) -> dict:
        """Vmapped multi-user read: one gather + one compiled ⊕-fold per
        plan group, then ONE jit-cached vmapped fused finalize — results
        have a leading ``len(user_ids)`` axis.  Ids laid out by chip
        (:meth:`lay_out`) get answers split on the tenant axis as the
        chips computed them; other ids are laid out, and the answers
        gathered back into the given order."""
        self._ensure_plan()
        laid, rows = self.lay_out(user_ids)
        merged = tuple(svc.partials_batch(laid) for svc in self._services)
        out = self._finalize_batch(merged)
        return out if rows is None else jax.tree.map(lambda l: l[rows], out)

    # -- durability ----------------------------------------------------------
    def export_state(self) -> dict:
        """Host snapshot of everything the session serves from: one entry
        per plan group, each the stacked lane pytree + eviction cursor of
        its `RollingStatsService` (host copies — safe across later donating
        ingests).  The snapshot round-trips through
        `repro.checkpoint.manager.save_pytree` / ``restore_pytree`` with
        this same dict as the restore template; :meth:`import_state` on a
        freshly built session with the same requests/config then serves
        answers identical to the exporter's, with zero re-ingest.  This is
        the durability hook `repro.serving.gateway.StatsGateway` snapshots
        through."""
        self._ensure_plan()
        return {
            f"group_{i}": svc.export_state()
            for i, svc in enumerate(self._services)
        }

    def import_state(self, state: dict) -> None:
        """Install an :meth:`export_state` snapshot (same requests, same
        num_users/num_shards/window/backend config)."""
        self._ensure_plan()
        keys = {f"group_{i}" for i in range(len(self._services))}
        if set(state) != keys:
            raise ValueError(
                f"snapshot has groups {sorted(state)} but this session's "
                f"plan compiled {sorted(keys)} — the deferred requests must "
                "match the exporter's"
            )
        for i, svc in enumerate(self._services):
            svc.import_state(state[f"group_{i}"])

    def state_template(self) -> dict:
        """Zero-copy view of the live state with :meth:`export_state`'s
        structure — shapes/dtypes for checkpoint-restore templates without
        a full device→host transfer."""
        self._ensure_plan()
        return {
            f"group_{i}": svc.state_template()
            for i, svc in enumerate(self._services)
        }

    # -- integrity -----------------------------------------------------------
    def audit(self):
        """Finite-sweep every tenant's stacked lane state on-device: one
        compiled program + one host sync per plan group.  Returns a host
        (num_users,) bool — True where every group's every lane is healthy
        (see `RollingStatsService.audit`)."""
        self._ensure_plan()
        healthy = None
        for svc in self._services:
            h = svc.audit()
            healthy = h if healthy is None else healthy & h
        return healthy

    def export_tenant(self, user_id: int) -> dict:
        """Host snapshot of ONE tenant's slice of every group's state
        (:meth:`import_tenant`'s input; also produced by
        `repro.checkpoint.manager.restore_tenant_pytree` from a full
        session checkpoint)."""
        self._ensure_plan()
        return {
            f"group_{i}": svc.export_tenant(user_id)
            for i, svc in enumerate(self._services)
        }

    def import_tenant(self, user_id: int, state: dict) -> None:
        """Surgically restore ONE tenant's lanes from a per-tenant snapshot,
        leaving every other tenant's live state untouched and re-tracing
        nothing (see `RollingStatsService.import_tenant`)."""
        self._ensure_plan()
        keys = {f"group_{i}" for i in range(len(self._services))}
        if set(state) != keys:
            raise ValueError(
                f"tenant snapshot has groups {sorted(state)} but this "
                f"session's plan compiled {sorted(keys)}"
            )
        for i, svc in enumerate(self._services):
            svc.import_tenant(user_id, state[f"group_{i}"])

    def tenant_slice(self, state: dict, user_id: int) -> dict:
        """Extract ONE tenant's slice from a full :meth:`export_state`
        snapshot (host-side; no device work)."""
        self._ensure_plan()
        keys = {f"group_{i}" for i in range(len(self._services))}
        if set(state) != keys:
            raise ValueError(
                f"snapshot has groups {sorted(state)}, expected {sorted(keys)}"
            )
        return {
            f"group_{i}": svc.tenant_slice(state[f"group_{i}"], user_id)
            for i, svc in enumerate(self._services)
        }

    def tenant_axes(self) -> dict:
        """Flat checkpoint-key → tenant-axis map for every leaf of
        :meth:`export_state`, keyed exactly as
        `repro.checkpoint.manager.save_pytree` flattens them.  Recorded
        into each snapshot's manifest (``meta["tenant_axes"]``) so
        ``restore_tenant_pytree`` can slice ONE tenant out of a checkpoint
        without loading the session: lane leaves carry tenants on axis 1
        (``(num_lanes, num_users, ...)``), eviction cursors on axis 0."""
        from ..checkpoint.manager import path_key

        axes = {}
        for path, _leaf in jax.tree_util.tree_flatten_with_path(
            self.state_template()
        )[0]:
            field = getattr(path[1], "key", None)
            axes[path_key(path)] = 1 if field == "lanes" else 0
        return axes

    def lengths(self) -> jax.Array:
        """(num_users,) samples ingested per user (total, incl. evicted)."""
        self._ensure_plan()
        return self._services[0].lengths()

    def retained_lengths(self) -> jax.Array:
        """(num_users,) samples a query covers right now (= ``lengths`` in
        growing mode; the ring-retained span in eviction mode)."""
        self._ensure_plan()
        return self._services[0].retained_lengths()


def _as_2d(x: jax.Array) -> jax.Array:
    return x[:, None] if x.ndim == 1 else x
