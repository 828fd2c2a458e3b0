"""Rolling-statistics serving endpoint over streaming partial states.

The production shape of the paper's thesis (ROADMAP north star): millions
of user series, each receiving samples over time, each wanting rolling
statistics (mean / autocovariance / AR fits / spectra) on demand.  Because
weak-memory partials form a mergeable monoid (`repro.core.streaming`), the
service never stores raw series — only per-user `PartialState`s, which are

  * updated in place by batched, vmapped chunk ingestion (one device pass
    for a whole arrival batch),
  * held in ``num_shards`` independent ingest lanes (e.g. one per ingest
    node or mesh host) that never coordinate on the write path,
  * merged **on request**: a query ⊕-combines the user's per-lane partials
    and finalizes.  On a mesh, lane partials built from halo-complete
    blocks reduce with the single ``psum`` of
    `repro.parallel.sharding.psum_tree` — the read path's only collective.

Lane storage is ONE stacked pytree with a leading ``(num_lanes,
num_users)`` axis pair — not a Python list of per-lane states — so every
lane shares a single jit program: ingest scatter-updates into the stacked
buffers (which are **donated**, so steady-state ingest allocates nothing),
and a batched query gathers all lanes of all requested users with one
indexed read and ⊕-folds the lane axis inside one compiled reduce.

**Sliding-window eviction mode** (``window=``): instead of growing
forever, each user's state is a ring of ``num_buckets`` *window-aligned
sub-states*, each covering a contiguous ``window / num_buckets``-sample
span.  Ingest lands in the bucket owning the chunk's global index,
resetting it to the neutral element when a new span begins — which is the
eviction: the span from ``num_buckets`` rings ago vanishes in O(1),
without ever revisiting data.  A query ⊕-folds the ring exactly like
lanes (the merge orders operands by global start index), so served
statistics cover the retained horizon: the last ``w`` samples with
``window − bucket_len < w ≤ window``, bucket-aligned.  Because bucket
``t0``s are global, strided members (Welch segments) stay aligned across
evictions.  The multi-statistic front door over this machinery is
`repro.core.frame.FrameSession`.

**Tail fidelity is a serving contract.**  The merged cross-lane state a
query hands to finalizers carries the *exact* last ``W − 1`` samples of
the user's (retained) series in ``tail``, right-aligned and zero-filled —
not just lag sums.  Downstream this is load-bearing beyond the ragged-tail
correction: the forecast/anomaly members of `repro.core.forecast` seed
their companion-matrix recurrence and innovations filter from that very
window, so ⊕-fold order, eviction resets, and `export_state` /
``import_state`` round-trips must all preserve it bit-exactly (the
kill-and-restart forecast determinism pin in tests/test_gateway.py).

**State is never recomputed — integrity is a serving contract too.**  The
raw series is gone the moment a chunk is absorbed; every answer the
service will ever give is a ⊕-fold of the carried partials.  Two
consequences, and the machinery that answers them (`repro.core.integrity`):

  * one non-finite sample scatter-merged into a lane poisons that
    tenant's answers *permanently* (NaN + x = NaN; no later data dilutes
    it out).  Prevention belongs at the boundary — the gateway's ingest
    sentinel (`repro.serving.gateway`) — and detection/repair here:
    :meth:`audit` finite-sweeps the stacked lane pytree on-device into a
    host per-(lane, user) health mask, and :meth:`import_tenant`
    surgically restores ONE tenant's lanes from a per-tenant checkpoint
    slice (`repro.checkpoint.manager.restore_tenant_pytree`) without
    touching any other tenant's live state or re-tracing the donated
    scatter programs;
  * float rounding in the ⊕-folds drifts monotonically for the session's
    lifetime.  Engines built with ``compensated=True`` carry a Neumaier
    error companion per stat leaf so readout recovers what rounding
    discarded (pinned by benchmarks/bench_integrity.py).

**Tenants across chips** (``devices=``): the stacked buffers' tenant
axis is split over a one-axis mesh of ``devices`` chips in contiguous
blocks, tenant ``t`` on chip ``t // block`` at local index ``t % block``
(the axis is padded up to ``devices × block``; padding tenants are never
addressed).  Every program is one SPMD program over the mesh
(`jax.shard_map`) in which each chip gathers, updates, scatters and folds
only its own tenants, from its own rows of the batch: no state moves
between chips and no collective runs.  A batch reaches the programs laid
out by chip (:meth:`RollingStatsService.lay_out`): ``devices`` blocks of
equal length, block j holding chip j's tenants and padding rows (id
``-1``), which change and answer nothing.  One chip is the same code on a
one-chip mesh, where every batch is already laid out.

The compute substrate of the ingest hot loop is the engine's backend
(`repro.core.backend`): build the engine with
``lag_sum_engine(..., backend="pallas")`` and every batched ``ingest``
update — and the ragged-tail correction at query finalize — runs the VMEM
tile kernels; with ``"auto"`` the registry picks by platform and size.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.integrity import lane_health
from ..core.streaming import PartialState, StreamingEngine

__all__ = ["RollingStatsService"]

TENANTS = "tenants"      # the mesh axis the tenant axis is split over
# stacked lane leaves carry tenants on axis 1; per-row batch arrays on axis 0
_LANES, _ROWS = P(None, TENANTS), P(TENANTS)


def _coerce_import_leaf(key: str, want: np.dtype, new):
    """Dtype-validate one snapshot leaf against the live buffer it replaces.

    Equal dtype passes through; a same-kind mismatch (float64 snapshot into
    a float32 session — numpy checkpoints default to f64) is cast
    explicitly; a kind change (float↔int↔complex↔bool) raises: it means
    the snapshot was produced by a different engine config, and silently
    casting it would both corrupt values and compile duplicate scatter
    programs keyed on the stray dtype (the PR 6 ``t0`` int32 bug class).
    The leaf stays where it is (host or device); the caller places it.
    """
    arr = new if hasattr(new, "dtype") else np.asarray(new)
    have = np.dtype(arr.dtype)
    want = np.dtype(want)
    if have == want:
        return arr
    if have.kind == want.kind:
        return arr.astype(want)
    raise ValueError(
        f"snapshot leaf {key!r} has dtype {have} but this service holds "
        f"{want} — a {have.kind!r}→{want.kind!r} kind change cannot come "
        "from a matching exporter config; refusing to cast"
    )


class RollingStatsService:
    """Batched per-user rolling statistics with mergeable ingest lanes.

    Args:
      engine: streaming engine defining the tracked statistic.
      num_users: number of user series served.
      num_shards: independent ingest lanes.  A user's stream may be split
        across lanes in contiguous time segments (pass ``t0`` at the first
        ingest of a mid-stream lane); queries merge lanes in any order.
      window: sliding-window eviction mode — retain only (about) the last
        ``window`` samples per user, in a ring of ``num_buckets``
        window-aligned sub-states (see the module docstring).  Requires
        ``num_shards == 1``; every ingested chunk must tile the bucket
        grid (chunk length ≤ bucket span, never straddling a boundary).
      num_buckets: ring size in eviction mode (default 8); ``window`` must
        divide evenly into it.
      devices: chips the tenant axis is split over (the first ``devices``
        of ``jax.devices()``), in contiguous blocks of tenants (see the
        module docstring).  Growing mode only.
    """

    def __init__(
        self,
        engine: StreamingEngine,
        num_users: int,
        num_shards: int = 1,
        window: Optional[int] = None,
        num_buckets: Optional[int] = None,
        devices: int = 1,
    ):
        if num_users <= 0 or num_shards <= 0:
            raise ValueError("num_users and num_shards must be positive")
        if devices <= 0:
            raise ValueError(f"devices must be positive, got {devices}")
        if window is not None and devices > 1:
            raise ValueError(
                f"eviction mode (window={window}) keeps its tenants on one "
                f"chip; devices={devices} is not supported with it"
            )
        chips = jax.devices()
        if devices > len(chips):
            raise ValueError(
                f"devices={devices} but JAX sees {len(chips)} device(s)"
            )
        self.engine = engine
        self.num_users = num_users
        self.num_shards = num_shards
        self.window = window
        self.devices = devices
        # tenants per chip; the tenant axis holds devices × block slots
        self.block = -(-num_users // devices)
        self.mesh = Mesh(np.asarray(chips[:devices]), (TENANTS,))
        self._lane_sharding = NamedSharding(self.mesh, _LANES)
        self._row_sharding = NamedSharding(self.mesh, _ROWS)
        if window is None:
            if num_buckets is not None:
                raise ValueError("num_buckets only applies with window= set")
            self.num_buckets = None
            self.bucket_len = None
            num_lanes = num_shards
        else:
            if num_shards != 1:
                raise ValueError(
                    "eviction mode is a single ingest lane (num_shards=1); "
                    "the lane axis is the eviction ring"
                )
            self.num_buckets = 8 if num_buckets is None else num_buckets
            if self.num_buckets < 2:
                raise ValueError("eviction needs at least 2 ring buckets")
            if window <= 0 or window % self.num_buckets != 0:
                raise ValueError(
                    f"window={window} must be a positive multiple of "
                    f"num_buckets={self.num_buckets}"
                )
            self.bucket_len = window // self.num_buckets
            num_lanes = self.num_buckets
        self._num_lanes = num_lanes
        # One stacked pytree, leading axes (num_lanes, tenant slots): every
        # lane lives in the same buffers and every ingest/query below is a
        # single jit program regardless of which lane it addresses.  Made
        # in place on the mesh: no chip ever holds another's block.
        slots = self.block * devices
        self._lanes = jax.jit(
            lambda: jax.tree.map(
                lambda l: jnp.broadcast_to(l, (num_lanes,) + l.shape),
                engine.init_batch(slots),
            ),
            out_shardings=self._lane_sharding,
        )()
        # Total samples ever ingested per user — the eviction ring's global
        # cursor.  Kept as a HOST array: the cursor is only ever read for
        # alignment checks and bucket derivation, and a device-resident
        # counter would force one device→host sync per ingest batch (the
        # hot path).  Growing mode reads lengths straight off the lane
        # states and never touches this.
        self._counts = np.zeros((num_users,), np.int64)
        # Host per-(lane, user) health mask, refreshed by audit() — the
        # hot ingest/query paths never touch it.
        self._lane_health = np.ones((num_lanes, num_users), bool)
        self._audit_sweep = jax.jit(lane_health)

        def per_chip(body, *specs, out=_LANES):
            # body over one chip's block of the lanes and its own rows of
            # the batch; ids are local (block = a padding row: the gather
            # clamps it, the scatter drops it)
            return jax.shard_map(body, mesh=self.mesh, in_specs=(_LANES,) + specs,
                                 out_specs=out, check_vma=False)

        def scatter_rows(lanes, shard, user_ids, chunks, t0):
            sub = jax.tree.map(lambda l: l[shard, user_ids], lanes)
            new = jax.vmap(engine.update)(sub, chunks, t0)
            return jax.tree.map(
                lambda l, nl: l.at[shard, user_ids].set(nl), lanes, new
            )

        def scatter_update(lanes, shard, user_ids, chunks, t0):
            return per_chip(scatter_rows, P(), _ROWS, _ROWS, _ROWS)(
                lanes, shard, user_ids, chunks, t0)

        # jit caches one program per (arrival batch, chunk length) shape —
        # shared by ALL lanes (shard is a traced scalar) — and donates the
        # lane buffers: steady-state ingest updates them in place.
        # The output's sharding is pinned: on a one-chip mesh XLA would
        # hand some leaves back unsplit, and the next call would compile
        # again for them.
        self._scatter_update = jax.jit(scatter_update, donate_argnums=0,
                                       out_shardings=self._lane_sharding)

        def evict_rows(lanes, user_ids, chunks, counts):
            # Ring ingest: the chunk's bucket is derived from the user's
            # global cursor; a cursor on a bucket boundary means the slot
            # holds the span from num_buckets rings ago — reset it to the
            # neutral element (THE eviction) before absorbing the chunk.
            bucket = (counts // self.bucket_len) % self.num_buckets
            sub = jax.tree.map(lambda l: l[bucket, user_ids], lanes)
            fresh = engine.init_batch(user_ids.shape[0], t0=counts)
            boundary = counts % self.bucket_len == 0

            def pick(cur, new):
                b = boundary.reshape(boundary.shape + (1,) * (cur.ndim - 1))
                return jnp.where(b, new, cur)

            cur = jax.tree.map(pick, sub, fresh)
            new = jax.vmap(engine.update)(cur, chunks, counts)
            return jax.tree.map(
                lambda l, nl: l.at[bucket, user_ids].set(nl), lanes, new
            )

        def scatter_evict(lanes, user_ids, chunks, counts):
            return per_chip(evict_rows, _ROWS, _ROWS, _ROWS)(
                lanes, user_ids, chunks, counts)

        self._scatter_evict = jax.jit(scatter_evict, donate_argnums=0,
                                      out_shardings=self._lane_sharding)

        def lane_fold(stacked):
            # ⊕-fold the leading lane axis of a stacked (S, k, …) pytree
            # with the vmapped merge: one compiled reduce, no per-lane
            # Python-indexed tree.map gathers.  The merge combines
            # *adjacent* segments, so the running ⊕-accumulator must stay
            # contiguous at every step: in eviction mode the ring slots are
            # time-rotated per user, so sort them by global start first
            # (empty slots last — they are neutral).  Growing-mode lanes
            # are caller-ordered contiguous splits; slot order is already
            # time order there.
            if window is not None:
                key = jnp.where(
                    stacked.length > 0,
                    stacked.t0,
                    jnp.iinfo(jnp.int32).max,
                )
                order = jnp.argsort(key, axis=0)  # (S, k)
                stacked = jax.tree.map(
                    lambda leaf: jnp.take_along_axis(
                        leaf,
                        order.reshape(order.shape + (1,) * (leaf.ndim - 2)),
                        axis=0,
                    ),
                    stacked,
                )
            acc = jax.tree.map(lambda l: l[0], stacked)
            for s in range(1, num_lanes):
                acc = jax.vmap(engine.merge)(
                    acc, jax.tree.map(lambda l: l[s], stacked)
                )
            return acc

        def gather_rows(lanes, user_ids):
            return lane_fold(jax.tree.map(lambda l: l[:, user_ids], lanes))

        def gather_merge(lanes, user_ids):
            # each chip's merged states stay on it, split on the tenant axis
            return per_chip(gather_rows, _ROWS, out=_ROWS)(lanes, user_ids)

        self._gather_merge = jax.jit(gather_merge,
                                     out_shardings=self._row_sharding)

    @property
    def backend(self):
        """The compute backend every ingest lane's updates run through."""
        return self.engine.backend

    # -- durability ---------------------------------------------------------
    def export_state(self) -> dict:
        """Host snapshot of the full serving state: the stacked lane pytree
        plus the eviction cursor.  Leaves are HOST copies (``device_get``),
        so the snapshot survives the next ingest donating the live lane
        buffers — safe to hand to an async checkpoint writer
        (`repro.checkpoint.manager.CheckpointManager.save`).  The lanes'
        tenant axis holds every slot, padding included (tenant ``t`` at
        index ``t``), so a snapshot restores into a service of the same
        ``devices``."""
        return {
            "lanes": jax.device_get(self._lanes),
            "counts": np.array(self._counts),
        }

    def import_state(self, state: dict) -> None:
        """Install a snapshot produced by :meth:`export_state` on a service
        built with the same engine/num_users/num_shards/window config —
        after this, queries answer exactly as they did at snapshot time
        without re-ingesting any history."""
        lanes = state["lanes"]
        want = jax.tree.structure(self._lanes)
        got = jax.tree.structure(lanes)
        if want != got:
            raise ValueError(
                f"snapshot lane structure {got} does not match this "
                f"service's {want} — was it exported from a service with a "
                f"different plan or engine?"
            )
        mismatched = [
            (a.shape, b.shape)
            for a, b in zip(jax.tree.leaves(self._lanes), jax.tree.leaves(lanes))
            if tuple(a.shape) != tuple(b.shape)
        ]
        if mismatched:
            raise ValueError(
                f"snapshot lane shapes {[m[1] for m in mismatched]} do not "
                f"match this service's {[m[0] for m in mismatched]} — "
                "num_users / num_shards / window / devices must equal the "
                "exporter's"
            )
        cur_flat, treedef = jax.tree_util.tree_flatten_with_path(self._lanes)
        new_leaves = [
            jax.device_put(_coerce_import_leaf(
                "lanes" + jax.tree_util.keystr(path), cur.dtype, new
            ), self._lane_sharding)
            for (path, cur), new in zip(cur_flat, jax.tree.leaves(lanes))
        ]
        self._lanes = jax.tree.unflatten(treedef, new_leaves)
        counts = np.asarray(state["counts"])
        if counts.dtype.kind not in "iu":
            raise ValueError(
                f"snapshot counts must be integer-typed, got {counts.dtype}"
            )
        counts = counts.astype(np.int64)
        if counts.shape != self._counts.shape:
            raise ValueError(
                f"snapshot counts shape {counts.shape} != {self._counts.shape}"
            )
        self._counts = counts.copy()
        self._lane_health = np.ones((self._num_lanes, self.num_users), bool)

    def state_template(self) -> dict:
        """Zero-copy view with :meth:`export_state`'s structure — the live
        lane pytree and cursor themselves, for shape/dtype templates
        (checkpoint restore) where a host snapshot would waste a full
        device→host transfer.  Do NOT mutate or retain across a donating
        ingest."""
        return {"lanes": self._lanes, "counts": self._counts}

    # -- integrity ----------------------------------------------------------
    def audit(self) -> np.ndarray:
        """Finite-sweep the stacked lane pytree on-device: ONE compiled
        program (`repro.core.integrity.lane_health`, jitted once at
        construction) + one host sync, refreshing the per-(lane, user)
        health mask.  Returns a host (num_users,) bool — True where every
        lane of the user is healthy."""
        # np.array (not asarray): own the buffer — device_get views are
        # read-only and import_tenant writes the mask in place.
        sweep = np.asarray(self._audit_sweep(self._lanes))
        mask = np.array(sweep[:, : self.num_users])
        self._lane_health = mask
        return mask.all(axis=0)

    @property
    def lane_health(self) -> np.ndarray:
        """(num_lanes, num_users) health mask from the last :meth:`audit`
        (all-True before any audit, and reset on import/rebuild)."""
        return self._lane_health.copy()

    def tenant_slice(self, state: dict, user_id: int) -> dict:
        """Extract ONE user's slice from an :meth:`export_state` snapshot:
        lane leaves keep their lane axis, drop the user axis (axis 1);
        the cursor becomes a scalar.  Host-side; no device work."""
        u = self._check_user(user_id)
        return {
            "lanes": jax.tree.map(lambda l: np.asarray(l)[:, u], state["lanes"]),
            "counts": np.int64(np.asarray(state["counts"])[u]),
        }

    def export_tenant(self, user_id: int) -> dict:
        """Host snapshot of ONE user's lane states + cursor (the
        :meth:`import_tenant` payload)."""
        u = self._check_user(user_id)
        return {
            "lanes": jax.tree.map(
                lambda l: jax.device_get(l[:, u]), self._lanes
            ),
            "counts": np.int64(self._counts[u]),
        }

    def import_tenant(self, user_id: int, state: dict) -> None:
        """Surgically restore ONE user's lane states from a per-tenant
        snapshot (:meth:`export_tenant` / :meth:`tenant_slice` /
        `repro.checkpoint.manager.restore_tenant_pytree`).

        Every other user's live state is untouched, and nothing re-traces:
        the write is an eager per-leaf ``.at[:, u].set`` — the donated
        scatter-ingest and gather-query programs key on the (unchanged)
        stacked buffer shapes and keep serving from their caches.
        """
        u = self._check_user(user_id)
        lanes = state["lanes"]
        want = jax.tree.structure(self._lanes)
        got = jax.tree.structure(lanes)
        if want != got:
            raise ValueError(
                f"tenant snapshot lane structure {got} does not match this "
                f"service's {want}"
            )
        cur_flat, treedef = jax.tree_util.tree_flatten_with_path(self._lanes)
        new_flat = jax.tree.leaves(lanes)
        out = []
        for (path, cur), new in zip(cur_flat, new_flat):
            key = "lanes" + jax.tree_util.keystr(path)
            expect = (cur.shape[0],) + tuple(cur.shape[2:])
            if tuple(np.shape(new)) != expect:
                raise ValueError(
                    f"tenant snapshot leaf {key!r} has shape "
                    f"{tuple(np.shape(new))}, expected {expect}"
                )
            coerced = _coerce_import_leaf(key, cur.dtype, new)
            # kept on the lanes' sharding, so the cached programs still fit
            out.append(jax.device_put(cur.at[:, u].set(coerced), cur.sharding))
        self._lanes = jax.tree.unflatten(treedef, out)
        count = np.asarray(state["counts"])
        if count.dtype.kind not in "iu" or count.shape != ():
            raise ValueError(
                f"tenant snapshot counts must be an integer scalar, got "
                f"{count.dtype} with shape {count.shape}"
            )
        self._counts[u] = int(count)
        self._lane_health[:, u] = True

    def _check_user(self, user_id: int) -> int:
        u = int(user_id)
        if not 0 <= u < self.num_users:
            raise ValueError(f"user_id {u} out of range [0, {self.num_users})")
        return u

    # -- layout ------------------------------------------------------------
    def lay_out(self, user_ids) -> tuple:
        """Arrange a batch's tenant ids by the chip that owns them.

        Returns ``(laid, rows)``.  ``laid`` holds ``devices`` blocks of
        equal length; block j holds chip j's tenants of the batch, in the
        batch's order, and ``-1`` (a padding row) up to the longest block.
        ``rows[i]`` is the position of ``user_ids[i]`` in ``laid``.  Where
        the ids already are laid out so (always on one chip), ``laid`` is
        the ids and ``rows`` is None.  ``-1`` ids are accepted only there.
        Host-side; no device work.
        """
        ids = np.asarray(user_ids)
        n, chips = ids.shape[0], self.devices
        if chips == 1:
            return ids, None
        if n % chips == 0:
            blocks = ids.reshape(chips, n // chips)
            owner = np.arange(chips)[:, None]
            if np.all((blocks < 0) | (blocks // self.block == owner)):
                return ids, None
        if n and ids.min() < 0:
            raise ValueError(
                "padding ids (-1) are only accepted in a batch laid out by "
                "chip (see lay_out)"
            )
        owner = ids // self.block
        counts = np.bincount(owner, minlength=chips)
        width = int(counts.max(initial=0))
        order = np.argsort(owner, kind="stable")
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n) - (np.cumsum(counts) - counts)[owner[order]]
        rows = owner * width + rank
        laid = np.full(chips * width, -1, np.int32)
        laid[rows] = ids
        return laid, rows

    def _local(self, laid: np.ndarray) -> jax.Array:
        """Laid-out ids → each chip's local ids (``block`` for padding),
        placed on the chips that own them."""
        local = np.where(laid < 0, self.block, laid % self.block)
        return jax.device_put(local.astype(np.int32), self._row_sharding)

    # -- write path --------------------------------------------------------
    def ingest(
        self,
        user_ids: jax.Array,
        chunks: jax.Array,
        shard: int = 0,
        t0: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Absorb one arrival batch: ``chunks[i]`` extends user
        ``user_ids[i]``'s series on lane ``shard``.  Returns the batch as
        placed on the chips; it is ready once they hold their rows.

        Args:
          user_ids: (k,) int — distinct users in this batch; ``-1`` marks
            a padding row where the batch is laid out by chip
            (:meth:`lay_out`).  A batch that is not is laid out here, at
            the cost of one copy of the chunks.
          chunks: (k, c, d) — equal-length chunk per user (pad+resend
            shorter arrivals separately; chunk granularity is free in
            growing mode; in eviction mode chunks must tile the bucket
            grid).
          t0: (k,) global start indices, used only for users whose lane
            state is still empty (a lane that picks up mid-stream).
            Growing mode only — the eviction ring owns the global cursor.
        """
        # Validation runs on a HOST view of the ids: when the caller passes
        # host data (a list, a numpy batch straight off the wire) the whole
        # check costs zero device round-trips — the old jnp form issued a
        # device dispatch plus a blocking device→host read per ingest call.
        ids = np.asarray(user_ids)
        if ids.dtype.kind != "i":
            # match the old jnp.asarray(user_ids, jnp.int32) coercion —
            # float-typed ids ingested fine before the host-side validation
            ids = ids.astype(np.int64)
        real = ids[ids >= 0]
        # .at[ids].set would silently keep only one of two conflicting
        # scattered states, and jit scatter silently DROPS out-of-bounds
        # ids (the gather on read would clamp to another user) — reject the
        # caller slips instead of losing or cross-wiring data.
        if np.unique(real).shape[0] != real.shape[0]:
            raise ValueError("user_ids must be distinct within one ingest batch")
        if ids.shape[0] and not (-1 <= ids.min() and ids.max() < self.num_users):
            raise ValueError(
                f"user_ids must lie in [0, {self.num_users}) (or be -1, "
                "a padding row)"
            )
        # num_shards is the caller-facing lane count in BOTH modes: the
        # eviction ring pins it to 1, and its internal bucket lanes are not
        # addressable (the old check tested _num_lanes — the ring size — so
        # the message promised a range the check didn't enforce).
        if not 0 <= shard < self.num_shards:
            raise ValueError(f"shard {shard} out of range [0, {self.num_shards})")
        laid, rows = self.lay_out(ids)
        if rows is not None:
            src = np.zeros(laid.shape[0], np.int64)
            src[rows] = np.arange(ids.shape[0])
            chunks = chunks[src] if isinstance(chunks, jax.Array) \
                else np.asarray(chunks)[src]
            if t0 is not None:
                t0 = np.asarray(t0)[src]
        elif not isinstance(chunks, jax.Array):
            chunks = np.asarray(chunks)
        with TraceAnnotation("repro.ingest.h2d", chips=self.devices) as span:
            user_ids = self._local(laid)
            chunks = jax.device_put(chunks, self._row_sharding)
            span.set_metadata(bytes=chunks.nbytes)
        if chunks.shape[1] == 0:
            # nothing to absorb — and in eviction mode the boundary reset
            # below must not fire for an empty arrival (it would wipe a
            # still-retained bucket without advancing the cursor)
            return chunks
        if self.window is not None:
            if t0 is not None:
                raise ValueError(
                    "eviction mode owns the global cursor; t0 is not accepted"
                )
            c = int(chunks.shape[1])
            if c > self.bucket_len:
                raise ValueError(
                    f"chunk length {c} exceeds the eviction bucket span "
                    f"{self.bucket_len} (= window / num_buckets)"
                )
            # host cursor: no device sync; a padding row's is 0
            starts = np.where(ids >= 0, self._counts[np.maximum(ids, 0)], 0)
            if np.any(
                starts // self.bucket_len != (starts + c - 1) // self.bucket_len
            ):
                raise ValueError(
                    "chunk would straddle an eviction bucket boundary; "
                    f"chunks must tile the {self.bucket_len}-sample bucket grid"
                )
            with TraceAnnotation("repro.ingest.dispatch", rows=len(laid)):
                self._lanes = self._scatter_evict(
                    self._lanes, user_ids, chunks,
                    jax.device_put(starts.astype(np.int32), self._row_sharding),
                )
            self._counts[real] += c
        else:
            # update() falls back to each state's own cursor where t0 is 0;
            # pin the dtype: a caller-dependent one compiled (and cached)
            # duplicate donated scatter programs for the same shapes
            t0 = np.zeros(laid.shape, np.int32) if t0 is None \
                else np.asarray(t0, np.int32)
            with TraceAnnotation("repro.ingest.dispatch", rows=len(laid)):
                self._lanes = self._scatter_update(
                    self._lanes,
                    jnp.asarray(shard, jnp.int32),
                    user_ids,
                    chunks,
                    jax.device_put(t0, self._row_sharding),
                )
        return chunks

    # -- read path ---------------------------------------------------------
    def partial(self, user_id: int) -> PartialState:
        """The user's merged cross-lane PartialState (lane order free)."""
        batched = self.partials_batch(jnp.asarray([user_id], jnp.int32))
        return jax.tree.map(lambda l: l[0], batched)

    def partials_batch(self, user_ids: Sequence[int] | jax.Array) -> PartialState:
        """Merged cross-lane PartialStates for many users in one program
        (leading ``len(user_ids)`` axis): one gather pulls every requested
        user's lane states, one compiled reduce ⊕-folds the lane axis.
        The batched read path multi-statistic front-ends
        (`repro.core.frame.FrameSession`) build on.  Ids laid out by chip
        (:meth:`lay_out`, ``-1`` rows answer nothing meaningful) stay split
        on the tenant axis, each chip's on that chip; other ids are laid
        out and their rows gathered back into the given order."""
        laid, rows = self.lay_out(user_ids)
        merged = self._gather_merge(self._lanes, self._local(laid))
        return merged if rows is None else jax.tree.map(lambda l: l[rows], merged)

    def query(self, user_id: int, finalizer: Callable, *args, **kwargs) -> Any:
        """Rolling estimate for one user: merge lanes, then finalize with an
        estimator front-end, e.g.
        ``svc.query(7, streaming_autocovariance, normalization="standard")``.
        """
        return finalizer(self.engine, self.partial(user_id), *args, **kwargs)

    def query_batch(
        self, user_ids: Sequence[int] | jax.Array, finalizer: Callable, *args, **kwargs
    ) -> Any:
        """Vmapped multi-user read: ONE gather pulls every requested user's
        lane states from the stacked buffers, one compiled reduce ⊕-folds
        the lane axis, then the finalizer runs vmapped over users."""
        merged = self.partials_batch(user_ids)
        return jax.vmap(
            lambda s: finalizer(self.engine, s, *args, **kwargs)
        )(merged)

    def lengths(self) -> jax.Array:
        """(num_users,) samples ingested per user (total, incl. evicted)."""
        if self.window is None:
            total = jnp.sum(self._lanes.length, axis=0)
            return total if total.shape[0] == self.num_users \
                else total[: self.num_users]
        return jnp.asarray(self._counts, jnp.int32)

    def retained_lengths(self) -> jax.Array:
        """(num_users,) samples a query covers right now: all of them in
        growing mode; in eviction mode the ring-retained span — the last
        ``w`` samples, ``window − bucket_len < w ≤ window`` once the ring
        has wrapped."""
        if self.window is None:
            return self.lengths()
        cnt = jnp.asarray(self._counts, jnp.int32)
        evicted = (
            jnp.maximum(
                (cnt - 1) // self.bucket_len - (self.num_buckets - 1), 0
            )
            * self.bucket_len
        )
        return jnp.where(cnt > 0, cnt - evicted, 0)
