"""Async serving gateway over `repro.core.frame.FrameSession`.

The paper's thesis — weak-memory statistics are mergeable partials — is
exactly what makes them *servable*: per-tenant state is a fixed-size
stacked pytree, ingest is a scatter-⊕, queries are a gather-⊕-finalize.
What was missing is a concurrency front door.  This module is it:

  * clients call ``await gateway.ingest(tenant, chunk)`` and
    ``await gateway.query(tenant)`` concurrently, from any number of
    asyncio tasks;
  * the gateway **coalesces per tick**: every admitted ingest in a tick is
    stacked into one arrival batch and absorbed by ONE donated
    scatter-ingest program, every admitted query rides ONE gather/⊕-fold
    plus ONE jit-cached vmapped fused finalize — the hot loop stays a
    single compiled device program per tick (per equal-chunk-length run /
    batch size) regardless of how many clients are connected.  Same-tenant
    ingests in one tick are ordered: the later ones carry over to the next
    tick, so the scatter never sees a duplicate id;
  * **admission control**: bounded queues (reject, don't buffer unbounded)
    and per-tenant token-bucket rate classes refilled per tick — an
    over-rate tenant is rejected at submit with :class:`RateLimited`
    without stalling anyone else;
  * **metrics**: p50/p99 query latency (submit → answer on the host) and
    ingest latency (submit → acknowledgement: an ingest future resolves
    once its scatter program is dispatched, not when the device has run
    it; completion shows only in a profiler trace, at the end of
    ``jit_scatter_update`` on the device), queue depths, per-program
    batch occupancy, rejected-request counters, tick-time straggler flags;
  * **the answers' fetch**: a batch whose largest answer leaf reaches
    ``_PIECE_BYTES`` leaves an accelerator in pieces below the C
    library's mmap threshold, and malloc is told to keep freed pages, so
    each tick's answers land in host memory that stays resident instead
    of in pages the kernel maps and faults in anew (``_fetch``);
  * **the ingest batch's staging**: a coalesced batch of ``_PIECE_BYTES``
    or more is stacked into a host buffer the gateway keeps across ticks
    (one a batch shape), rewritten only once the chips hold their copy of
    the last batch placed from it; smaller batches are a fresh
    ``np.stack``, whose pages the heap recycles (``_stage``);
  * **tracing**: each tick is a ``repro.tick`` step span, with one
    ``repro.ingest.*`` / ``repro.query.*`` span per phase inside it, in
    the profiler's trace when one is being taken (never one per request);
  * **durability**: every ``snapshot_every`` ticks the stacked session
    state (host copies — safe across donating ingests) is saved through
    `repro.checkpoint.manager.CheckpointManager`, and a restarted gateway
    resumes via `repro.runtime.fault.FaultTolerantLoop.restore_or`: a
    killed process comes back serving identical answers with zero
    re-ingest of history;
  * **data-plane integrity** (`repro.core.integrity`): with
    ``GatewayConfig(sentinel=True)`` every coalesced ingest batch gets ONE
    fused jitted all-finite verdict before it can touch session state (no
    host sync beyond the verdict itself — the sanitized batch stays on
    device).  A poisoned chunk is handled by the tenant's policy —
    ``reject`` (fail the future with :class:`PoisonedChunk`), ``sanitize``
    (mask non-finite values to 0 and ingest the rest), or ``quarantine``
    (fence the tenant off from ingest AND query until repaired).  Poisoning
    is seedable/replayable through the ``ingest.payload`` chaos site.
    Detection and repair for state that is already poisoned (the sentinel
    was off, or a kernel mis-ran): :meth:`audit` finite-sweeps every
    tenant's lanes on-device, and :meth:`rebuild_tenant` surgically
    restores ONE tenant from the newest intact checkpoint generation
    (per-tenant extraction via the manifest's ``tenant_axes`` metadata)
    without touching other tenants' live state or re-tracing anything;
  * **degraded mode**: when ``tick_deadline`` is set, a tick that blows
    its wall-clock budget (straggler device, injected stall — the
    ``gateway.tick`` chaos site fires inside the timed window) flips the
    gateway to ``degraded``: pending queries of the lowest-priority rate
    class are shed with :class:`Degraded` (distinct from
    :class:`RateLimited` — the client should back off, not retry-at-rate),
    snapshots are deferred so the writer doesn't compound the overrun, and
    after ``degraded_recovery`` consecutive in-budget ticks the gateway
    returns to ``ok`` and takes the deferred snapshot.  :meth:`health`
    reports ``ok`` / ``degraded`` / ``draining`` plus circuit-breaker trip
    counts when the session's backend is a
    `repro.core.backend.CircuitBreakerBackend`.

Forecasts and anomaly scores are served the same way as every other
statistic: a session whose plan carries `repro.core.forecast` members
(``session.forecast(...)`` / ``session.anomaly_scores(...)``) resolves
them inside the tick's ONE batched finalize — the vmapped companion-matrix
recurrence runs across every queried tenant in the same compiled program.
``submit_query(tenant, only="forecast")`` narrows a waiter's answer to
specific query kinds without changing what the tick executes.

The gateway is transport-agnostic: `examples/gateway_demo.py` drives it
in-process; an HTTP/gRPC front end would call the same ``submit_*``
surface from its handlers.
"""
from __future__ import annotations

import asyncio
import collections
import ctypes
import dataclasses
import functools
import math
import resource
import time
from typing import Any, Deque, Dict, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..core.frame import FrameSession
from ..core.integrity import SENTINEL_POLICIES, sentinel_scan
from ..runtime import chaos

__all__ = [
    "Degraded",
    "GatewayConfig",
    "GatewayRejected",
    "PoisonedChunk",
    "QueueFull",
    "RateClass",
    "RateLimited",
    "StatsGateway",
]


class GatewayRejected(RuntimeError):
    """Base class for admission-control rejections (backpressure)."""


class QueueFull(GatewayRejected):
    """The bounded request queue is at capacity — shed load upstream."""


class RateLimited(GatewayRejected):
    """The tenant's rate class has no tokens left this tick."""


class Degraded(GatewayRejected):
    """Shed because the gateway is over its tick deadline and dropping
    lowest-priority queries to recover.  Distinct from :class:`RateLimited`:
    the tenant did nothing wrong — back off instead of retrying at rate."""


class PoisonedChunk(GatewayRejected):
    """The ingest sentinel found non-finite values in the payload (or the
    tenant is quarantined from an earlier poisoning).  Retrying the same
    bytes will fail the same way — fix the producer, or ask the operator
    to :meth:`StatsGateway.rebuild_tenant` a quarantined tenant."""


@dataclasses.dataclass(frozen=True)
class RateClass:
    """Token-bucket admission limits, refilled once per tick.

    ``inf`` rates disable the limit.  ``burst`` caps the bucket (defaults
    to 2× the per-tick rate, min 1), so an idle tenant can catch up a
    little but can never dump an unbounded backlog into one tick.
    ``priority`` orders classes for degraded-mode shedding: when the
    gateway is over its tick deadline, queries from the lowest-priority
    class(es) are dropped first.
    """

    name: str = "default"
    ingest_per_tick: float = math.inf
    query_per_tick: float = math.inf
    burst: Optional[float] = None
    priority: int = 0

    def bucket_cap(self, rate: float) -> float:
        if self.burst is not None:
            return self.burst
        if math.isinf(rate):
            return math.inf
        return max(2.0 * rate, 1.0)


@dataclasses.dataclass
class GatewayConfig:
    tick_interval: float = 0.005           # serve_forever pacing (seconds)
    max_pending_ingest: int = 4096         # bounded queues: reject beyond
    max_pending_query: int = 4096
    snapshot_every: int = 0                # ticks between snapshots (0=off)
    checkpoint_dir: Optional[str] = None   # durability off when None
    keep_checkpoints: int = 3
    rate_classes: Dict[str, RateClass] = dataclasses.field(
        default_factory=lambda: {"default": RateClass()}
    )
    default_class: str = "default"
    latency_window: int = 16384            # latency samples kept per kind
    straggler_threshold: float = 4.0       # tick-time straggler flagging
    tick_deadline: float = 0.0             # per-tick wall budget (s, 0=off)
    degraded_recovery: int = 2             # in-budget ticks to leave degraded
    bucket_idle_ticks: int = 512           # evict buckets idle this long (0=off)
    sentinel: bool = False                 # all-finite verdict per ingest batch
    sentinel_policy: str = "reject"        # default: reject|sanitize|quarantine


# glibc serves every block under this size from its heap (the largest
# mmap threshold it accepts), and the host receives a large query batch's
# answers in pieces below it; the heap keeps up to _KEEP_BYTES of freed
# memory (mallopt's largest value), so each tick's answers land in pages
# that earlier ticks already faulted in.  An ingest batch of this size or
# more is stacked into a buffer the gateway keeps instead.
_PIECE_BYTES = 32 << 20
_KEEP_BYTES = (1 << 31) - 1
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3


def _minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _row_major(leaf: jax.Array) -> bool:
    """Whether the device holds ``leaf`` in the host's own layout."""
    layout = leaf.format.layout
    return (not layout.tiling
            and tuple(layout.major_to_minor) == tuple(range(leaf.ndim)))


@functools.cache
def _keep_freed_pages() -> bool:
    """Make the C library's malloc keep the pages of freed blocks below
    ``_PIECE_BYTES`` instead of returning them to the kernel.  Process-wide
    and set once; False where the C library has no ``mallopt``."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    return bool(mallopt(_M_MMAP_THRESHOLD, _PIECE_BYTES)
                and mallopt(_M_TRIM_THRESHOLD, _KEEP_BYTES))


@functools.partial(jax.jit, static_argnums=1)
def answer_pieces(leaves: list, bounds: tuple) -> list:
    """Every answer leaf cut along its tenant axis at ``bounds``."""
    return [[leaf[lo:hi] for leaf in leaves]
            for lo, hi in zip(bounds, bounds[1:])]


def _chip_rows(leaves: list) -> list:
    """Each chip's rows of every leaf (split on the leading tenant axis),
    in row order: no copy, no device work."""
    if len(leaves[0].sharding.device_set) == 1:
        return [leaves]

    def rows(leaf):
        return [sh.data for sh in sorted(leaf.addressable_shards,
                                         key=lambda sh: sh.index[0].start)]

    return [list(chip) for chip in zip(*map(rows, leaves))]


def _fetch(results) -> tuple:
    """Copy one query batch's answers (leading tenant axis, split evenly
    over the chips that computed them) to the host.

    Returns ``(path, size, pieces)``: each chip's rows come in pieces of
    ``size`` (its last may be shorter), chip after chip, so that
    ``pieces[c * k + j]`` (k pieces a chip) is the tree of answers of the
    chip's rows ``j * size`` to ``(j + 1) * size``.  A ``jax.device_get``
    of the batch puts each leaf in a fresh host allocation, and a leaf of
    ``_PIECE_BYTES`` or more is mapped anew from the kernel every tick:
    the copy then pays a first-touch fault on every page, which on a TPU
    host costs many times the copy itself.  So where a leaf is that large
    and the device does not hold it in the host's layout (where it does,
    the CPU, ``device_get`` copies nothing), each chip cuts its rows into
    pieces below ``_PIECE_BYTES``, which the host's heap serves from pages
    it keeps.  One ``device_get`` fetches from every chip.
    """
    leaves, treedef = jax.tree.flatten(results)
    chips = _chip_rows(leaves)
    n = chips[0][0].shape[0]
    # a sixteenth of room below the threshold for the allocator's rounding
    row_bytes = max(leaf.nbytes // leaf.shape[0] for leaf in leaves)
    size = max(1, _PIECE_BYTES * 15 // 16 // row_bytes)
    if size >= n or all(map(_row_major, leaves)) or not _keep_freed_pages():
        host = jax.device_get(chips)
        return "device_get", n, [treedef.unflatten(chip) for chip in host]
    bounds = tuple(range(0, n, size)) + (n,)
    host = jax.device_get([answer_pieces(chip, bounds) for chip in chips])
    return "pieces", size, [treedef.unflatten(piece)
                            for chip in host for piece in chip]


def _aliases(placed: jax.Array, buf: np.ndarray) -> bool:
    """Whether a device array placed from ``buf`` is ``buf`` itself, as a
    device that reads host memory in place (the CPU) may make it."""
    lo = buf.ctypes.data
    return any(lo <= shard.data.unsafe_buffer_pointer() < lo + buf.nbytes
               for shard in placed.addressable_shards)


def _event_loop() -> asyncio.AbstractEventLoop:
    try:
        return asyncio.get_running_loop()
    except RuntimeError:  # submit from sync setup code, pre-loop
        return asyncio.get_event_loop_policy().get_event_loop()


@dataclasses.dataclass
class _Pending:
    tenant: int
    future: asyncio.Future
    t_submit: float
    chunk: Optional[np.ndarray] = None     # ingest only
    only: Optional[tuple] = None           # query only: request-name filter


@dataclasses.dataclass
class _Kept:
    """A host buffer that ingest batches of one shape are stacked into."""

    buf: np.ndarray
    placed: list = dataclasses.field(default_factory=list)  # device copies


class _TokenBuckets:
    """Per-tenant token buckets with lazy per-tick refill."""

    def __init__(self, rate_of, cap_of):
        self._rate_of = rate_of            # tenant -> tokens per tick
        self._cap_of = cap_of              # tenant -> bucket cap
        self._state: Dict[int, tuple] = {}  # tenant -> (tokens, tick)

    def admit(self, tenant: int, tick: int) -> bool:
        rate = self._rate_of(tenant)
        if math.isinf(rate):
            return True
        tokens, last = self._state.get(tenant, (self._cap_of(tenant), tick))
        tokens = min(self._cap_of(tenant), tokens + rate * (tick - last))
        if tokens < 1.0:
            self._state[tenant] = (tokens, tick)
            return False
        self._state[tenant] = (tokens - 1.0, tick)
        return True

    def evict_idle(self, tick: int, idle_ticks: int) -> int:
        """Drop buckets untouched for ``idle_ticks`` ticks; returns the
        eviction count.  A bucket that idle has (almost always) refilled
        to cap, so re-creating it lazily at full cap on the tenant's next
        request is the same state — this just bounds the map to tenants
        actually active in the last N ticks instead of every tenant ever
        seen.  (Lossless whenever ``idle_ticks >= cap / rate``; a
        pathologically slow-refill class trades a one-off full bucket for
        the memory bound.)"""
        stale = [t for t, (_, last) in self._state.items()
                 if tick - last >= idle_ticks]
        for t in stale:
            del self._state[t]
        return len(stale)

    def __len__(self) -> int:
        return len(self._state)


class StatsGateway:
    """Asyncio request engine serving one multi-tenant `FrameSession`.

    Args:
      session: the FrameSession to serve.  Its deferred requests must be
        declared before the gateway is constructed (the durability restore
        compiles the plan).
      config: see :class:`GatewayConfig`.

    Drive it either with :meth:`serve_forever` (background ticking at
    ``tick_interval``) or by awaiting :meth:`tick` directly (deterministic
    — what the tests and benchmark do).
    """

    def __init__(self, session: FrameSession, config: Optional[GatewayConfig] = None):
        self.session = session
        self.config = config or GatewayConfig()
        cfg = self.config
        if cfg.default_class not in cfg.rate_classes:
            raise ValueError(
                f"default_class {cfg.default_class!r} is not one of the "
                f"configured rate classes {sorted(cfg.rate_classes)}"
            )
        if cfg.sentinel_policy not in SENTINEL_POLICIES:
            raise ValueError(
                f"sentinel_policy {cfg.sentinel_policy!r} is not one of "
                f"{list(SENTINEL_POLICIES)}"
            )
        self._tenant_class: Dict[int, str] = {}
        # -- integrity -------------------------------------------------------
        self._tenant_policy: Dict[int, str] = {}  # per-tenant overrides
        self.quarantined: set = set()
        self._ingest_buckets = _TokenBuckets(
            lambda t: self._class_of(t).ingest_per_tick,
            lambda t: self._class_of(t).bucket_cap(
                self._class_of(t).ingest_per_tick),
        )
        self._query_buckets = _TokenBuckets(
            lambda t: self._class_of(t).query_per_tick,
            lambda t: self._class_of(t).bucket_cap(
                self._class_of(t).query_per_tick),
        )
        self._ingest_q: Deque[_Pending] = collections.deque()
        # (rows, length, d, dtype) -> the buffer such batches are stacked into
        self._kept: Dict[tuple, _Kept] = {}
        self._query_q: Deque[_Pending] = collections.deque()
        self._tick_lock = asyncio.Lock()
        self._serve_task: Optional[asyncio.Task] = None
        self._closed = False
        self._draining = False

        # -- health ----------------------------------------------------------
        self._health = "ok"
        self._healthy_streak = 0
        self._snapshot_deferred = False

        # -- metrics ---------------------------------------------------------
        self._lat_ingest: Deque[float] = collections.deque(
            maxlen=cfg.latency_window)
        self._lat_query: Deque[float] = collections.deque(
            maxlen=cfg.latency_window)
        self._occ_ingest: Deque[int] = collections.deque(maxlen=4096)
        self._occ_query: Deque[int] = collections.deque(maxlen=4096)
        self.counters = collections.Counter()     # monotonic — never reset
        self._counter_base = collections.Counter()  # reset_metrics() window

        # -- durability ------------------------------------------------------
        self._loop_rt = None
        self._tick = 0
        self._dirty = False
        if cfg.checkpoint_dir is not None:
            from ..runtime.fault import FaultTolerantLoop

            # every=0: the gateway owns the snapshot cadence (a fresh host
            # export must be taken at exactly the saving tick); the loop
            # contributes restore-resume, the async manager, and the
            # straggler monitor.
            self._loop_rt = FaultTolerantLoop(
                cfg.checkpoint_dir,
                every=0,
                keep=cfg.keep_checkpoints,
                straggler_threshold=cfg.straggler_threshold,
            )
            # the template only supplies structure/shapes/dtypes — the
            # zero-copy view skips a full device→host export at startup
            template = session.state_template()
            state, start_tick = self._loop_rt.restore_or(template)
            if start_tick > 0:
                session.import_state(state)
                self.counters["restored_from_snapshot"] += 1
            self._tick = start_tick
            self.monitor = self._loop_rt.monitor
        else:
            from ..runtime.fault import StragglerMonitor

            self.monitor = StragglerMonitor(threshold=cfg.straggler_threshold)

    # ------------------------------------------------------------ admission
    def _class_of(self, tenant: int) -> RateClass:
        name = self._tenant_class.get(tenant, self.config.default_class)
        return self.config.rate_classes[name]

    def _min_priority(self) -> int:
        return min(rc.priority for rc in self.config.rate_classes.values())

    def set_tenant_class(self, tenant: int, class_name: str) -> None:
        if class_name not in self.config.rate_classes:
            raise ValueError(
                f"unknown rate class {class_name!r}; configured: "
                f"{sorted(self.config.rate_classes)}"
            )
        self._tenant_class[int(tenant)] = class_name

    def set_tenant_policy(self, tenant: int, policy: str) -> None:
        """Override the sentinel policy for one tenant (the config's
        ``sentinel_policy`` applies to everyone else)."""
        if policy not in SENTINEL_POLICIES:
            raise ValueError(
                f"unknown sentinel policy {policy!r}; one of "
                f"{list(SENTINEL_POLICIES)}"
            )
        self._tenant_policy[self._check_tenant(tenant)] = policy

    def _policy_of(self, tenant: int) -> str:
        return self._tenant_policy.get(tenant, self.config.sentinel_policy)

    def _check_tenant(self, tenant: int) -> int:
        tenant = int(tenant)
        if not 0 <= tenant < self.session.num_users:
            raise ValueError(
                f"tenant {tenant} out of range [0, {self.session.num_users})"
            )
        return tenant

    def submit_ingest(self, tenant: int, chunk) -> asyncio.Future:
        """Admit one ingest request; resolves in the absorbing tick, once
        its scatter program has been dispatched (an acknowledgement: the
        device may still be running it).

        Raises :class:`QueueFull` / :class:`RateLimited` immediately when
        admission fails (the rejection is the backpressure signal), and
        :class:`PoisonedChunk` for a quarantined tenant.
        """
        if self._closed:
            raise RuntimeError("gateway is closed")
        tenant = self._check_tenant(tenant)
        if tenant in self.quarantined:
            self.counters["rejected_ingest_quarantined"] += 1
            raise PoisonedChunk(
                f"tenant {tenant} is quarantined (poisoned state); "
                "rebuild_tenant() restores service"
            )
        chunk = np.asarray(chunk)
        if chunk.ndim == 1:
            chunk = chunk[:, None]
        if chunk.ndim != 2 or chunk.shape[1] != self.session.d:
            raise ValueError(
                f"chunk must be (c, {self.session.d}), got {chunk.shape}"
            )
        if len(self._ingest_q) >= self.config.max_pending_ingest:
            self.counters["rejected_ingest_queue_full"] += 1
            raise QueueFull(
                f"ingest queue at capacity ({self.config.max_pending_ingest})"
            )
        if not self._ingest_buckets.admit(tenant, self._tick):
            self.counters["rejected_ingest_rate"] += 1
            raise RateLimited(
                f"tenant {tenant} over its "
                f"{self._tenant_class.get(tenant, self.config.default_class)!r}"
                " ingest rate"
            )
        if chaos.should_corrupt("ingest.payload"):
            # seeded data-plane poisoning: the payload arrives torn (NaN)
            # exactly as a buggy producer or a bit-flipped wire would
            # deliver it — drawn once per admitted submission, so a given
            # (seed, calls) schedule replays the same poisoned arrivals
            chunk = np.array(chunk, dtype=(
                chunk.dtype if np.issubdtype(chunk.dtype, np.floating)
                else np.float32
            ))
            chunk[0, 0] = np.nan
            self.counters["chaos_poisoned_ingest"] += 1
        fut = _event_loop().create_future()
        self._ingest_q.append(
            _Pending(tenant, fut, time.perf_counter(), chunk=chunk)
        )
        return fut

    def submit_query(self, tenant: int, only=None) -> asyncio.Future:
        """Admit one query request; resolves to ``{request_name: result}``
        (this tenant's slice of the tick's batched read).

        ``only`` — a request name or iterable of names (e.g. a forecast or
        anomaly member) — narrows the resolved dict to those query kinds.
        The filter is applied host-side to the tenant's slice: every admitted
        query still rides the SAME one-per-tick batched finalize, so asking
        for just the forecast costs no extra device program.
        """
        if self._closed:
            raise RuntimeError("gateway is closed")
        tenant = self._check_tenant(tenant)
        if tenant in self.quarantined:
            self.counters["rejected_query_quarantined"] += 1
            raise PoisonedChunk(
                f"tenant {tenant} is quarantined (poisoned state); its "
                "answers would be garbage — rebuild_tenant() restores service"
            )
        if only is not None:
            only = (only,) if isinstance(only, str) else tuple(only)
            unknown = set(only) - set(self.session.request_names)
            if unknown:
                raise ValueError(
                    f"unknown query kinds {sorted(unknown)}; this session "
                    f"serves {list(self.session.request_names)}"
                )
        if (
            self._health == "degraded"
            and self._class_of(tenant).priority <= self._min_priority()
        ):
            self.counters["rejected_query_degraded"] += 1
            raise Degraded(
                f"gateway degraded (tick over {self.config.tick_deadline}s "
                f"budget); shedding lowest-priority queries"
            )
        if len(self._query_q) >= self.config.max_pending_query:
            self.counters["rejected_query_queue_full"] += 1
            raise QueueFull(
                f"query queue at capacity ({self.config.max_pending_query})"
            )
        if not self._query_buckets.admit(tenant, self._tick):
            self.counters["rejected_query_rate"] += 1
            raise RateLimited(
                f"tenant {tenant} over its "
                f"{self._tenant_class.get(tenant, self.config.default_class)!r}"
                " query rate"
            )
        fut = _event_loop().create_future()
        self._query_q.append(
            _Pending(tenant, fut, time.perf_counter(), only=only)
        )
        return fut

    async def ingest(self, tenant: int, chunk) -> int:
        """Coroutine front door: admitted, then resolved at the next tick.
        Returns the tick index that absorbed the chunk."""
        return await self.submit_ingest(tenant, chunk)

    async def query(self, tenant: int, only=None) -> dict:
        """Coroutine front door: this tenant's deferred statistics as of
        the resolving tick (optionally narrowed to the ``only`` kinds —
        e.g. ``await gw.query(7, only="forecast")``)."""
        return await self.submit_query(tenant, only=only)

    # ------------------------------------------------------------- the tick
    async def tick(self) -> dict:
        """Run one coalescing round: drain the queues, execute the batched
        device programs, resolve futures, maybe snapshot.  Returns per-tick
        stats (mostly for the benchmark's narrator)."""
        async with self._tick_lock:
            with StepTraceAnnotation("repro.tick", step_num=self._tick):
                t_start = time.perf_counter()
                shed = self._shed_if_degraded()
                # the gateway.tick chaos site lives INSIDE the timed window:
                # an injected stall looks exactly like a straggler device to
                # the deadline watchdog; an injected fail is a survivable
                # tick-level fault (counted, the tick still serves)
                try:
                    chaos.fire("gateway.tick")
                except Exception:
                    self.counters["tick_faults"] += 1
                n_ing = self._run_ingests()
                n_qry = self._run_queries()
                tick = self._tick
                self._tick += 1
                dt = time.perf_counter() - t_start
                self._update_health(tick, dt)
                self._maybe_snapshot(tick)
                if n_ing or n_qry:
                    self.monitor.record(tick, dt)
                self.counters["ticks"] += 1
                idle = self.config.bucket_idle_ticks
                if idle and tick and tick % idle == 0:
                    evicted = self._ingest_buckets.evict_idle(tick, idle)
                    evicted += self._query_buckets.evict_idle(tick, idle)
                    self.counters["buckets_evicted"] += evicted
        # hand control back so awaiting clients observe their futures
        await asyncio.sleep(0)
        return {"tick": tick, "ingests": n_ing, "queries": n_qry,
                "shed": shed, "seconds": dt}

    def _shed_if_degraded(self) -> int:
        """In degraded mode, drop queued queries of the lowest-priority
        rate class before doing any work this tick (with a single class,
        every pending query is lowest).  Ingests are never shed — dropping
        reads costs a retry, dropping writes loses data."""
        if self._health != "degraded" or not self._query_q:
            return 0
        floor = self._min_priority()
        keep: list = []
        shed = 0
        for req in self._query_q:
            if self._class_of(req.tenant).priority <= floor:
                if not req.future.done():
                    req.future.set_exception(Degraded(
                        f"query shed at tick {self._tick}: gateway degraded"
                    ))
                shed += 1
            else:
                keep.append(req)
        self._query_q.clear()
        self._query_q.extend(keep)
        self.counters["shed_query_degraded"] += shed
        return shed

    def _update_health(self, tick: int, dt: float) -> None:
        deadline = self.config.tick_deadline
        if not deadline:
            return
        if dt > deadline:
            self.counters["ticks_deadline_blown"] += 1
            self._healthy_streak = 0
            if self._health != "degraded":
                self._health = "degraded"
                self.counters["degraded_entries"] += 1
        elif self._health == "degraded":
            self._healthy_streak += 1
            if self._healthy_streak >= self.config.degraded_recovery:
                self._health = "ok"
                self.counters["degraded_recoveries"] += 1
                if (self._snapshot_deferred and self._loop_rt is not None
                        and self._dirty):
                    self._snapshot(tick)
                self._snapshot_deferred = False

    def _run_ingests(self) -> int:
        """Coalesce the admitted ingest backlog into the fewest possible
        scatter programs: one per run of equal chunk lengths, duplicate
        tenants deferred to the next tick (a scatter must see distinct
        ids, and a tenant's chunks must land in arrival order).  With the
        sentinel enabled, each coalesced batch gets one fused all-finite
        verdict before it can touch session state.  Futures resolve once
        every batch of the tick has been dispatched."""
        if not self._ingest_q:
            return 0
        with TraceAnnotation("repro.ingest.stack") as span:
            pending = list(self._ingest_q)
            self._ingest_q.clear()
            carry: list = []
            seen: set = set()
            groups: Dict[int, list] = {}
            for req in pending:
                if req.tenant in self.quarantined:
                    # quarantined between admission and this tick (a
                    # carried request, or an audit() ran mid-backlog)
                    if not req.future.done():
                        req.future.set_exception(PoisonedChunk(
                            f"tenant {req.tenant} is quarantined; "
                            "rebuild_tenant() restores service"
                        ))
                    self.counters["rejected_ingest_quarantined"] += 1
                    continue
                if req.tenant in seen:
                    carry.append(req)   # next tick: ordering + distinctness
                    continue
                seen.add(req.tenant)
                groups.setdefault(req.chunk.shape[0], []).append(req)
            self._ingest_q.extend(carry)
            batches = []
            staged = set()
            for length, reqs in sorted(groups.items()):
                if not length:
                    continue
                # rows in owner-chip order, each chip's padded to the
                # longest with a repeat of a chunk of the batch (its id
                # -1: the scatter drops it), stacked once
                ids, at = self.session.lay_out(
                    np.asarray([r.tenant for r in reqs], np.int32))
                chunks = [r.chunk for r in reqs]
                if at is not None:
                    laid = [chunks[0]] * len(ids)
                    for i, row in enumerate(at):
                        laid[row] = chunks[i]
                    chunks = laid
                batch, key, how = self._stage(chunks)
                staged.add(how)
                batches.append((reqs, ids, at, batch, key))
            # a shape no batch of this tick used gives its buffer up
            for key in self._kept.keys() - {b[4] for b in batches}:
                del self._kept[key]
            pad = sum(len(b[1]) - len(b[0]) for b in batches)
            self.counters["ingest_pad_rows"] += pad
            # length: the longest chunk (the only one when the tick runs
            # one scatter program)
            span.set_metadata(rows=sum(len(b[1]) for b in batches),
                              length=max(groups, default=0),
                              bytes=sum(b[3].nbytes for b in batches),
                              chips=self.session.devices, pad_rows=pad,
                              staged="new" if "new" in staged else
                              "kept" if "kept" in staged else "heap")
        absorbed = list(groups.get(0, ()))  # empty chunks: no-ops, resolved too
        done = 0
        for reqs, ids, at, batch, key in batches:
            if self.config.sentinel:
                # ONE fused jitted program: per-chunk verdict + sanitized
                # copy together; the verdict is the only host sync, and the
                # clean batch (bit-identical when everything is finite)
                # stays on device for the scatter below.
                verdict, clean = sentinel_scan(batch)
                self.counters["sentinel_scans"] += 1
                if at is not None:
                    verdict = verdict[at]
                if not verdict.all():
                    keep = self._apply_sentinel(reqs, verdict)
                    if not keep:
                        continue
                    if len(keep) < len(reqs):
                        # a dropped chunk's row becomes a padding row
                        drop = np.setdiff1d(np.arange(len(reqs)), keep)
                        ids = ids.copy()
                        ids[drop if at is None else at[drop]] = -1
                        reqs = [reqs[i] for i in keep]
                batch = clean
            try:
                placed = self.session.ingest(ids, batch)
            except Exception as e:
                # a copy from a kept buffer may still be in flight
                self._kept.pop(key, None)
                for r in reqs:
                    if not r.future.done():
                        r.future.set_exception(e)
                self.counters["failed_ingest"] += len(reqs)
                continue
            if key in self._kept:
                self._kept[key].placed = placed
            self.counters["programs_ingest"] += 1
            self._occ_ingest.append(len(reqs))
            self._dirty = True
            absorbed.extend(reqs)
            done += len(reqs)
        with TraceAnnotation("repro.ingest.resolve", n=len(absorbed)):
            for r in absorbed:
                self._resolve(r, self._tick, self._lat_ingest)
        return done

    def _stage(self, chunks: list) -> tuple:
        """Stack equal-shaped ``chunks`` into one batch; returns ``(batch,
        key, how)``.  Below ``_PIECE_BYTES`` a fresh ``np.stack`` (``how``
        "heap", ``key`` None): the heap serves it from pages it keeps.  A
        fresh batch of that size or more would be mapped anew every tick,
        and the stack would pay a first-touch fault on every page, so it
        goes into the buffer kept for its ``key`` (rows, length, d, dtype):
        "new" where this allocates it, "kept" where an earlier tick did.
        A kept buffer is rewritten only once every device copy placed from
        it is complete."""
        if len(chunks) * chunks[0].nbytes < _PIECE_BYTES:
            return np.stack(chunks), None, "heap"
        dtype = functools.reduce(np.promote_types, {c.dtype for c in chunks})
        key = (len(chunks),) + chunks[0].shape + (dtype,)
        kept = self._kept.get(key)
        if kept is None:
            kept = self._kept[key] = _Kept(np.empty(key[:-1], dtype))
            self.counters["ingest_stage_new"] += 1
            how = "new"
        else:
            jax.block_until_ready(kept.placed)
            if any(_aliases(p, kept.buf) for p in kept.placed):
                # the device batch is the buffer: wait for the programs
                # that read it, which end in the session's state
                jax.block_until_ready(
                    jax.tree.leaves(self.session.state_template()))
            kept.placed = []
            how = "kept"
        np.stack(chunks, out=kept.buf)
        self.counters["ingest_stage_kept"] += 1
        return kept.buf, key, how

    def _apply_sentinel(self, reqs, verdict) -> list:
        """Dispatch each poisoned chunk to its tenant's policy; returns the
        indices of requests that still ingest (finite ones, plus sanitized
        poisoned ones)."""
        keep: list = []
        for i, r in enumerate(reqs):
            if verdict[i]:
                keep.append(i)
                continue
            policy = self._policy_of(r.tenant)
            if policy == "sanitize":
                # the sanitized device row (non-finite → 0) ingests
                self.counters["sanitized_chunks"] += 1
                keep.append(i)
                continue
            self.counters["rejected_ingest_poisoned"] += 1
            if policy == "quarantine":
                self.quarantined.add(r.tenant)
                self.counters["tenants_quarantined"] += 1
                msg = (
                    f"tenant {r.tenant} quarantined: non-finite values in "
                    "ingest payload; rebuild_tenant() restores service"
                )
            else:  # reject
                msg = (
                    f"ingest rejected: non-finite values in tenant "
                    f"{r.tenant}'s chunk"
                )
            if not r.future.done():
                r.future.set_exception(PoisonedChunk(msg))
        return keep

    def _run_queries(self) -> int:
        """Coalesce the admitted query backlog into ONE batched read:
        distinct tenants gathered once, every waiter handed its slice."""
        pending = list(self._query_q)
        self._query_q.clear()
        if self.quarantined:
            alive = []
            for req in pending:
                if req.tenant in self.quarantined:
                    if not req.future.done():
                        req.future.set_exception(PoisonedChunk(
                            f"tenant {req.tenant} is quarantined; "
                            "rebuild_tenant() restores service"
                        ))
                    self.counters["rejected_query_quarantined"] += 1
                else:
                    alive.append(req)
            pending = alive
        if not pending:
            return 0
        order: Dict[int, int] = {}
        for req in pending:
            order.setdefault(req.tenant, len(order))
        ids, at = self.session.lay_out(
            np.fromiter(order.keys(), np.int32, len(order)))
        self.counters["query_pad_rows"] += len(ids) - len(order)
        try:
            with TraceAnnotation("repro.query.dispatch", tenants=len(order)):
                results = self.session.query_batch(ids)
        except Exception as e:
            for r in pending:
                if not r.future.done():
                    r.future.set_exception(e)
            self.counters["failed_query"] += len(pending)
            return 0
        self.counters["programs_finalize"] += 1
        self._occ_query.append(len(order))
        # the whole batch leaves the device in one fetch; per-waiter slicing
        # is then numpy views, not thousands of tiny device index dispatches
        nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(results))
        chips = self.session.devices
        with TraceAnnotation("repro.query.fetch", bytes=nbytes,
                             chips=chips) as span:
            faults = _minor_faults()
            path, size, pieces = _fetch(results)
            span.set_metadata(minflt=_minor_faults() - faults, path=path,
                              pieces=len(pieces))
        # each chip's rows come in len(pieces) // chips pieces of size
        rows, per_chip = len(ids) // chips, len(pieces) // chips
        with TraceAnnotation("repro.query.resolve", n=len(pending)):
            for req in pending:
                pos = order[req.tenant]
                chip, row = divmod(pos if at is None else int(at[pos]), rows)
                host = pieces[chip * per_chip + row // size]
                value = jax.tree.map(lambda l: l[row % size], host)
                if req.only is not None:
                    value = {k: value[k] for k in req.only}
                self._resolve(req, value, self._lat_query)
        return len(pending)

    def _resolve(self, req: _Pending, value: Any, lat: Deque[float]) -> None:
        if not req.future.done():       # client may have given up (cancel)
            req.future.set_result(value)
        lat.append(time.perf_counter() - req.t_submit)

    # ----------------------------------------------------------- durability
    def _maybe_snapshot(self, tick: int) -> None:
        cfg = self.config
        if (
            self._loop_rt is None
            or not cfg.snapshot_every
            or not self._dirty
            or (tick + 1) % cfg.snapshot_every != 0
        ):
            return
        if self._health == "degraded":
            # don't compound an over-budget tick with a state export; the
            # recovery transition takes the deferred snapshot
            self._snapshot_deferred = True
            self.counters["snapshots_deferred"] += 1
            return
        self._snapshot(tick)

    def _snapshot(self, tick: int) -> None:
        # export_state hands out HOST copies, so the async writer is immune
        # to the next tick's donating scatter deleting the live buffers.
        # tenant_axes in the manifest is what lets rebuild_tenant extract
        # ONE tenant from this generation later.
        self._loop_rt.manager.save(
            self.session.export_state(), tick,
            meta={"tenant_axes": self.session.tenant_axes()},
        )
        self._dirty = False
        self.counters["snapshots"] += 1

    # ------------------------------------------------------------- integrity
    def audit(self, quarantine: bool = True) -> dict:
        """On-device finite sweep of every tenant's lane state (ONE compiled
        program + one host sync per plan group — see `FrameSession.audit`).

        ``quarantine=True`` (default) fences every unhealthy tenant off
        from ingest and query until :meth:`rebuild_tenant` repairs it.
        Returns ``{"unhealthy": [...], "quarantined": [...newly...]}``.
        """
        healthy = self.session.audit()
        self.counters["audits"] += 1
        unhealthy = [int(t) for t in np.flatnonzero(~healthy)]
        self.counters["audit_unhealthy"] += len(unhealthy)
        newly: list = []
        if quarantine:
            for t in unhealthy:
                if t not in self.quarantined:
                    self.quarantined.add(t)
                    self.counters["tenants_quarantined"] += 1
                    newly.append(t)
        return {"unhealthy": unhealthy, "quarantined": newly}

    def rebuild_tenant(self, tenant: int) -> dict:
        """Surgically restore ONE tenant from the newest checkpoint
        generation whose slice verifies, release its quarantine, and leave
        every other tenant's live state untouched (nothing re-traces — see
        `RollingStatsService.import_tenant`).

        The restored tenant serves answers as of its last snapshot —
        freshness between that snapshot and the poisoning is lost (state is
        never recomputed; there is no raw data to replay), availability is
        restored.  Returns ``{"tenant", "step", "skipped", "released"}``.
        """
        tenant = self._check_tenant(tenant)
        if self._loop_rt is None:
            raise RuntimeError(
                "rebuild_tenant needs durability — construct the gateway "
                "with GatewayConfig(checkpoint_dir=...)"
            )
        from ..checkpoint.manager import restore_tenant_latest_intact

        # queued async snapshots must land before the newest-intact walk
        self._loop_rt.manager.flush()
        state, step, skipped = restore_tenant_latest_intact(
            self.session.state_template(),
            self._loop_rt.manager.directory,
            tenant,
        )
        self.session.import_tenant(tenant, state)
        released = tenant in self.quarantined
        self.quarantined.discard(tenant)
        self.counters["tenants_rebuilt"] += 1
        return {
            "tenant": tenant,
            "step": step,
            "skipped": skipped,
            "released": released,
        }

    # -------------------------------------------------------------- driving
    async def serve_forever(self) -> None:
        """Tick at ``config.tick_interval`` until :meth:`stop` is called."""
        try:
            while not self._closed:
                await self.tick()
                await asyncio.sleep(self.config.tick_interval)
        except asyncio.CancelledError:
            pass

    def start(self) -> asyncio.Task:
        """Launch :meth:`serve_forever` as a background task."""
        if self._serve_task is None or self._serve_task.done():
            self._serve_task = _event_loop().create_task(self.serve_forever())
        return self._serve_task

    async def stop(self, final_snapshot: bool = True) -> None:
        """Drain one last tick, snapshot if dirty, release the writer."""
        if self._closed:
            return
        self._draining = True
        # drain: carried-over same-tenant duplicates may need extra ticks
        await self.tick()
        while self._ingest_q or self._query_q:
            await self.tick()
        self._closed = True
        if self._serve_task is not None:
            self._serve_task.cancel()
            try:
                await self._serve_task
            except asyncio.CancelledError:
                pass
        for q in (self._ingest_q, self._query_q):
            for req in q:
                if not req.future.done():
                    req.future.set_exception(
                        GatewayRejected("gateway stopped"))
            q.clear()
        if self._loop_rt is not None:
            if final_snapshot and self._dirty:
                self._snapshot(self._tick)
            self._loop_rt.close()

    # -------------------------------------------------------------- metrics
    @staticmethod
    def _pct(samples, q: float) -> float:
        if not samples:
            return 0.0
        return float(np.percentile(np.asarray(samples), q)) * 1e6  # µs

    def health(self) -> dict:
        """Liveness surface: ``ok`` / ``degraded`` / ``draining``, the
        deadline watchdog's tallies, and — when the session's backend is a
        circuit breaker — its per-primitive trip state."""
        state = ("draining" if (self._draining or self._closed)
                 else self._health)
        out = {
            "state": state,
            "tick": self._tick,
            "deadline": {
                "budget_s": self.config.tick_deadline,
                "blown": self.counters["ticks_deadline_blown"],
                "shed": self.counters["shed_query_degraded"]
                + self.counters["rejected_query_degraded"],
                "snapshot_deferred": self._snapshot_deferred,
                "degraded_entries": self.counters["degraded_entries"],
                "degraded_recoveries": self.counters["degraded_recoveries"],
            },
        }
        # the session holds a backend SPEC (None/str/instance); resolve it
        # the same way the session's plan does before sniffing for a breaker
        from ..core.backend import get_backend

        spec = getattr(self.session, "_backend", None)
        try:
            backend = get_backend(spec) if spec is not None else None
        except KeyError:
            backend = None
        breaker = getattr(backend, "breaker_metrics", None)
        if callable(breaker):
            out["breaker"] = breaker()
        out["integrity"] = {
            "sentinel": self.config.sentinel,
            "default_policy": self.config.sentinel_policy,
            "quarantined": sorted(self.quarantined),
            "poisoned_rejected": self.counters["rejected_ingest_poisoned"],
            "sanitized_chunks": self.counters["sanitized_chunks"],
            "audits": self.counters["audits"],
            "audit_unhealthy": self.counters["audit_unhealthy"],
            "tenants_quarantined": self.counters["tenants_quarantined"],
            "tenants_rebuilt": self.counters["tenants_rebuilt"],
        }
        return out

    def reset_metrics(self) -> None:
        """Start a new observation window: clears the latency/occupancy
        sample windows and re-bases the per-window counter deltas exposed
        under ``metrics()["window"]``.  The totals in ``counters`` are
        monotonic and are never reset — rates come from windows, audits
        from totals."""
        self._lat_ingest.clear()
        self._lat_query.clear()
        self._occ_ingest.clear()
        self._occ_query.clear()
        self._counter_base = collections.Counter(self.counters)

    def metrics(self) -> dict:
        """The serving surface's health in one dict (latencies in µs).
        ``query`` latencies run from submit to the answer on the host;
        ``ingest`` latencies from submit to acknowledgement, the absorbing
        scatter dispatched, not completed on the device.
        Rejection/snapshot counts are monotonic totals; ``window`` holds
        the same counters since the last :meth:`reset_metrics`."""
        c = self.counters
        base = self._counter_base
        return {
            "ticks": c["ticks"],
            "tick": self._tick,
            "health": ("draining" if (self._draining or self._closed)
                       else self._health),
            "ingest": {
                "count": len(self._lat_ingest),
                "p50_us": self._pct(self._lat_ingest, 50),
                "p99_us": self._pct(self._lat_ingest, 99),
                "rejected_rate": c["rejected_ingest_rate"],
                "rejected_queue_full": c["rejected_ingest_queue_full"],
                "programs": c["programs_ingest"],
            },
            "query": {
                "count": len(self._lat_query),
                "p50_us": self._pct(self._lat_query, 50),
                "p99_us": self._pct(self._lat_query, 99),
                "rejected_rate": c["rejected_query_rate"],
                "rejected_queue_full": c["rejected_query_queue_full"],
                "rejected_degraded": c["rejected_query_degraded"]
                + c["shed_query_degraded"],
                "programs": c["programs_finalize"],
            },
            "queue_depth": {
                "ingest": len(self._ingest_q),
                "query": len(self._query_q),
            },
            "batch_occupancy": {
                "ingest_mean": float(np.mean(self._occ_ingest))
                if self._occ_ingest else 0.0,
                "query_mean": float(np.mean(self._occ_query))
                if self._occ_query else 0.0,
            },
            "bucket_tenants": len(self._ingest_buckets)
            + len(self._query_buckets),
            "straggler_ticks": list(self.monitor.flagged),
            "snapshots": c["snapshots"],
            "deadline_blown": c["ticks_deadline_blown"],
            "restored_from_snapshot": c["restored_from_snapshot"],
            "window": {k: c[k] - base[k]
                       for k in sorted(set(c) | set(base))},
        }
