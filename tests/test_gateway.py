"""Async serving gateway: per-tick coalescing, durability, backpressure.

Pins the three serving contracts:
  * N concurrent clients in one tick cost exactly ONE ingest scatter and
    ONE batched finalize device program (counting-backend + jit-cache
    assertions — nothing re-traces under steady load);
  * kill-and-restart resumes from the snapshot and serves queries
    identical to pre-crash values with zero re-ingest;
  * backpressure rejects over-rate tenants / full queues immediately,
    without stalling other tenants.
"""
import asyncio
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import get_backend
from repro.core.frame import FrameSession, SeriesFrame
from repro.serving import gateway
from repro.serving.gateway import (
    GatewayConfig,
    QueueFull,
    RateClass,
    RateLimited,
    StatsGateway,
)

D = 2


class CountingBackend:
    """Delegating backend recording every traced primitive invocation
    (mirrors tests/test_frame.py) — a cached jit program records nothing."""

    name = "counting"

    def __init__(self, inner):
        self._inner = inner
        self.calls = []

    def __getattr__(self, prim):
        fn = getattr(self._inner, prim)

        def wrapped(*args, **kwargs):
            self.calls.append(prim)
            return fn(*args, **kwargs)

        return wrapped


def _session(num_users, backend="jnp", **kwargs):
    sess = FrameSession(d=D, num_users=num_users, backend=backend, **kwargs)
    sess.autocovariance(3)
    sess.moments(8)
    return sess


def _chunks(num_users, c=32, seed=0):
    rng = np.random.RandomState(seed)
    return {u: rng.randn(c, D).astype(np.float32) for u in range(num_users)}


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------- (a) one program per tick


def test_tick_coalesces_to_one_ingest_and_one_finalize_program():
    N = 6
    counting = CountingBackend(get_backend("jnp"))
    gw = StatsGateway(_session(N, backend=counting))
    chunks = _chunks(N)

    async def scenario():
        # warm-up tick traces the programs once
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        qfuts = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs, *qfuts)

        counting.calls.clear()
        before = dict(gw.counters)
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        qfuts = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs)
        results = await asyncio.gather(*qfuts)
        return before, results

    before, results = run(scenario())
    # N concurrent clients, one tick: ONE scatter-ingest dispatch, ONE
    # batched finalize dispatch ...
    assert gw.counters["programs_ingest"] - before["programs_ingest"] == 1
    assert gw.counters["programs_finalize"] - before["programs_finalize"] == 1
    # ... and zero primitive traces — the whole tick ran cached compiled
    # programs (the counting backend only ever fires during tracing)
    assert counting.calls == []
    # the jit caches held exactly one entry per program despite N clients
    for svc in gw.session._services:
        assert svc._scatter_update._cache_size() == 1
    assert all(sorted(r) == ["autocovariance", "moments"] for r in results)
    m = gw.metrics()
    assert m["batch_occupancy"]["ingest_mean"] == N
    assert m["batch_occupancy"]["query_mean"] == N


def test_gateway_results_match_direct_session():
    N = 3
    gw = StatsGateway(_session(N))
    chunks = _chunks(N, c=40, seed=3)

    async def scenario():
        for _ in range(2):
            futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
            await gw.tick()
            await asyncio.gather(*futs)
        q = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        return await asyncio.gather(*q)

    results = run(scenario())
    for u in range(N):
        ref = SeriesFrame.from_array(
            np.concatenate([chunks[u], chunks[u]]), backend="jnp"
        )
        ref.autocovariance(3)
        ref.moments(8)
        want = ref.collect()
        np.testing.assert_allclose(
            results[u]["autocovariance"], want["autocovariance"],
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            results[u]["moments"]["mean"], want["moments"]["mean"],
            rtol=1e-5, atol=1e-6,
        )


def test_same_tenant_twice_in_a_tick_carries_over_in_order():
    gw = StatsGateway(_session(2))
    chunks = _chunks(1, c=16, seed=5)
    second = np.ones((16, D), np.float32)

    async def scenario():
        f1 = gw.submit_ingest(0, chunks[0])
        f2 = gw.submit_ingest(0, second)  # same tenant: deferred one tick
        await gw.tick()
        assert f1.done() and not f2.done()
        assert gw.metrics()["queue_depth"]["ingest"] == 1
        await gw.tick()
        await asyncio.gather(f1, f2)
        q = gw.submit_query(0)
        await gw.tick()
        return await q

    got = run(scenario())
    ref = SeriesFrame.from_array(
        np.concatenate([chunks[0], second]), backend="jnp"
    )
    ref.autocovariance(3)
    ref.moments(8)
    np.testing.assert_allclose(
        got["autocovariance"], ref.collect()["autocovariance"],
        rtol=1e-4, atol=1e-5,
    )


# ------------------------------------------------ (b) kill-and-restart


def test_kill_and_restart_serves_identical_answers(tmp_path):
    N = 4
    cfg = GatewayConfig(checkpoint_dir=str(tmp_path), snapshot_every=1)
    gw = StatsGateway(_session(N), cfg)
    chunks = _chunks(N, c=24, seed=7)

    async def before_crash():
        for seed in (0, 1):
            futs = [
                gw.submit_ingest(u, chunks[u] + seed) for u in range(N)
            ]
            await gw.tick()
            await asyncio.gather(*futs)
        q = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        return await asyncio.gather(*q)

    pre = run(before_crash())
    # the snapshot reached the worker queue; let it hit disk, then "crash"
    # (abandon the gateway object — no graceful stop, no final snapshot)
    gw._loop_rt.manager.flush()

    gw2 = StatsGateway(_session(N), cfg)
    assert gw2.counters["restored_from_snapshot"] == 1
    # tick numbering resumes after the last DURABLE tick (tick 1 — the
    # query-only tick 2 was clean and rightly never snapshotted)
    assert gw2._tick == 2

    async def after_restart():
        q = [gw2.submit_query(u) for u in range(N)]
        await gw2.tick()
        return await asyncio.gather(*q)

    post = run(after_restart())
    # identical answers, with zero re-ingest of history
    assert gw2.counters["programs_ingest"] == 0
    np.testing.assert_array_equal(
        np.asarray(gw2.session.lengths()), np.full(N, 48)
    )
    for u in range(N):
        np.testing.assert_array_equal(
            np.asarray(pre[u]["autocovariance"]),
            np.asarray(post[u]["autocovariance"]),
        )
        for k in ("mean", "var", "count"):
            np.testing.assert_array_equal(
                np.asarray(pre[u]["moments"][k]),
                np.asarray(post[u]["moments"][k]),
            )
    run(gw2.stop())


def test_snapshot_only_when_dirty(tmp_path):
    cfg = GatewayConfig(checkpoint_dir=str(tmp_path), snapshot_every=1)
    gw = StatsGateway(_session(2), cfg)

    async def scenario():
        for _ in range(3):
            await gw.tick()  # idle ticks: nothing to snapshot
        f = gw.submit_ingest(0, np.ones((8, D), np.float32))
        await gw.tick()
        await f
        await gw.stop()

    run(scenario())
    assert gw.counters["snapshots"] == 1


def test_import_state_rejects_mismatched_session(tmp_path):
    sess = _session(3)
    other = FrameSession(d=D, num_users=3, backend="jnp")
    other.autocovariance(3)  # different request set → different plan
    sess.ingest(np.asarray([0]), np.ones((1, 8, D), np.float32))
    snap = sess.export_state()
    with pytest.raises(ValueError, match="does not match"):
        other.import_state(snap)
    smaller = _session(2)
    with pytest.raises(ValueError, match="num_users"):
        smaller.import_state(snap)


# ------------------------------------------------ (c) backpressure


def test_over_rate_tenant_rejected_without_stalling_others():
    cfg = GatewayConfig(
        rate_classes={
            "default": RateClass(),
            "limited": RateClass(ingest_per_tick=1, query_per_tick=1,
                                 burst=1),
        },
    )
    gw = StatsGateway(_session(4), cfg)
    gw.set_tenant_class(0, "limited")
    chunk = np.ones((8, D), np.float32)

    async def scenario():
        ok = gw.submit_ingest(0, chunk)  # consumes tenant 0's only token
        with pytest.raises(RateLimited):
            gw.submit_ingest(0, chunk)
        # other tenants sail through in the same tick
        others = [gw.submit_ingest(u, chunk) for u in (1, 2, 3)]
        await gw.tick()
        await asyncio.gather(ok, *others)
        # the bucket refills per tick: tenant 0 is admitted again
        f = gw.submit_ingest(0, chunk)
        await gw.tick()
        await f

    run(scenario())
    assert gw.counters["rejected_ingest_rate"] == 1
    assert gw.counters["programs_ingest"] == 2
    m = gw.metrics()
    assert m["ingest"]["count"] == 5  # 4 + 1 admitted requests resolved


def test_queue_full_rejects_and_recovers():
    cfg = GatewayConfig(max_pending_ingest=2, max_pending_query=1)
    gw = StatsGateway(_session(8), cfg)
    chunk = np.ones((8, D), np.float32)

    async def scenario():
        a = gw.submit_ingest(0, chunk)
        b = gw.submit_ingest(1, chunk)
        with pytest.raises(QueueFull):
            gw.submit_ingest(2, chunk)
        q = gw.submit_query(0)
        with pytest.raises(QueueFull):
            gw.submit_query(1)
        await gw.tick()
        await asyncio.gather(a, b, q)
        # drained: admission works again
        c = gw.submit_ingest(2, chunk)
        await gw.tick()
        await c

    run(scenario())
    assert gw.counters["rejected_ingest_queue_full"] == 1
    assert gw.counters["rejected_query_queue_full"] == 1


def test_tenant_validation_and_closed_gateway():
    gw = StatsGateway(_session(2))
    with pytest.raises(ValueError, match="tenant"):
        gw.submit_ingest(5, np.ones((4, D), np.float32))
    with pytest.raises(ValueError, match="chunk"):
        gw.submit_ingest(0, np.ones((4, D + 1), np.float32))
    run(gw.stop())
    with pytest.raises(RuntimeError, match="closed"):
        gw.submit_query(0)


def test_serve_forever_background_loop():
    gw = StatsGateway(_session(2), GatewayConfig(tick_interval=0.001))
    chunk = np.ones((8, D), np.float32)

    async def scenario():
        gw.start()
        got = await asyncio.wait_for(
            asyncio.gather(gw.ingest(0, chunk), gw.query(0)), timeout=10.0
        )
        await gw.stop()
        return got

    _, res = run(scenario())
    assert sorted(res) == ["autocovariance", "moments"]
    assert gw.metrics()["ticks"] >= 1


def test_kill_and_restart_serves_identical_forecasts(tmp_path):
    """Forecast determinism under serving: the restarted gateway's
    forecasts and anomaly scores are bit-identical to pre-crash — the
    snapshot's retained tail IS the recurrence seed."""
    N = 3

    def forecast_session():
        sess = FrameSession(d=D, num_users=N)
        sess.autocovariance(3)
        sess.forecast(5, model="arma", p=2, q=1)
        sess.anomaly_scores(model="ar", p=2)
        return sess

    cfg = GatewayConfig(checkpoint_dir=str(tmp_path), snapshot_every=1)
    gw = StatsGateway(forecast_session(), cfg)
    chunks = _chunks(N, c=48, seed=11)

    async def before_crash():
        for seed in (0, 1):
            futs = [gw.submit_ingest(u, chunks[u] + seed) for u in range(N)]
            await gw.tick()
            await asyncio.gather(*futs)
        q = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        return await asyncio.gather(*q)

    pre = run(before_crash())
    gw._loop_rt.manager.flush()

    gw2 = StatsGateway(forecast_session(), cfg)
    assert gw2.counters["restored_from_snapshot"] == 1
    assert gw2._tick == 2

    async def after_restart():
        q = [gw2.submit_query(u) for u in range(N)]
        await gw2.tick()
        return await asyncio.gather(*q)

    post = run(after_restart())
    assert gw2.counters["programs_ingest"] == 0
    for u in range(N):
        for key in ("pred", "sigma"):
            np.testing.assert_array_equal(
                np.asarray(pre[u]["forecast"][key]),
                np.asarray(post[u]["forecast"][key]),
            )
        for key in ("z", "score", "valid"):
            np.testing.assert_array_equal(
                np.asarray(pre[u]["anomaly"][key]),
                np.asarray(post[u]["anomaly"][key]),
            )
    run(gw2.stop())


# -------------------------------------------------- (e) query-kind filter


def test_query_only_filters_kinds_without_extra_programs():
    N = 3
    sess = _session(N)
    sess.forecast(4, model="ar", p=2)
    gw = StatsGateway(sess)
    chunks = _chunks(N, c=32, seed=13)

    async def scenario():
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs)
        full = gw.submit_query(0)
        narrow = gw.submit_query(1, only="forecast")
        pair = gw.submit_query(2, only=("moments", "forecast"))
        before = dict(gw.counters)
        await gw.tick()
        res = await asyncio.gather(full, narrow, pair)
        return before, res

    before, (full, narrow, pair) = run(scenario())
    # narrowing is host-side: still ONE batched finalize for the tick
    assert (
        gw.counters["programs_finalize"] - before.get("programs_finalize", 0)
        == 1
    )
    assert sorted(full) == ["autocovariance", "forecast", "moments"]
    assert sorted(narrow) == ["forecast"]
    assert sorted(pair) == ["forecast", "moments"]
    np.testing.assert_array_equal(
        np.asarray(narrow["forecast"]["pred"]).shape, (4, D)
    )


def test_query_only_unknown_kind_rejected_at_submit():
    gw = StatsGateway(_session(2))
    with pytest.raises(ValueError, match="spectrum"):
        gw.submit_query(0, only="spectrum")
    with pytest.raises(ValueError, match="autocovariance"):
        gw.submit_query(0, only=("moments", "nope"))


# ------------------------------------------- (f) the tick's profiler spans

INGEST_SPANS = ("repro.ingest.stack", "repro.ingest.h2d",
                "repro.ingest.dispatch", "repro.ingest.resolve")
QUERY_SPANS = ("repro.query.dispatch", "repro.query.fetch",
               "repro.query.resolve")


def _profiled(tmp_path, coro):
    """Run ``coro`` under the profiler; returns its result and the
    ``repro.*`` host events as (name, start_ns, end_ns, stats)."""
    import glob

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = run(coro)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("repro.")
    ]
    return out, events


def test_each_tick_opens_one_span_per_phase_inside_repro_tick(tmp_path):
    N, c = 3, 16
    gw = StatsGateway(_session(N))
    chunks = _chunks(N, c=c, seed=5)

    async def ingest_then_query():
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs)
        futs = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        return await asyncio.gather(*futs)

    run(ingest_then_query())      # compiles outside the profile
    answers, events = _profiled(tmp_path, ingest_then_query())
    ticks = sorted((e for e in events if e[0] == "repro.tick"), key=lambda e: e[1])
    assert [t[3]["step_num"] for t in ticks] == [2, 3]
    inside = [[e for e in events if e[0] != "repro.tick" and t[1] <= e[1] and e[2] <= t[2]]
              for t in ticks]
    # every phase span lies inside a tick
    assert sum(map(len, inside)) == len(events) - len(ticks)

    batch = N * c * D * 4
    ingest = {e[0]: e[3] for e in inside[0]}
    assert sorted(e[0] for e in inside[0]) == sorted(INGEST_SPANS)
    assert ingest["repro.ingest.stack"] == {"rows": N, "length": c, "bytes": batch,
                                            "chips": 1, "pad_rows": 0,
                                            "staged": "heap"}
    assert ingest["repro.ingest.h2d"] == {"bytes": batch, "chips": 1}
    assert ingest["repro.ingest.dispatch"] == {"rows": N}
    assert ingest["repro.ingest.resolve"] == {"n": N}

    query = {e[0]: e[3] for e in inside[1]}
    assert sorted(e[0] for e in inside[1]) == sorted(QUERY_SPANS)
    assert query["repro.query.dispatch"] == {"tenants": N}
    one = sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(answers[0]))
    fetch = dict(query["repro.query.fetch"])
    assert fetch.pop("minflt") >= 0
    assert fetch == {"bytes": N * one, "path": "device_get", "pieces": 1,
                     "chips": 1}
    assert query["repro.query.resolve"] == {"n": N}


def test_read_path_programs_compile_under_stable_names():
    N = 3
    sess = _session(N)
    sess.ingest(np.arange(N), np.zeros((N, 8, D), np.float32))
    ids = jnp.arange(N, dtype=jnp.int32)
    (svc,) = sess._services
    assert "@jit_gather_merge" in svc._gather_merge.lower(svc._lanes, ids).as_text()
    merged = (svc.partials_batch(ids),)
    assert "@jit_finalize_batch" in sess._finalize_batch.lower(merged).as_text()
    assert "@jit_scatter_update" in svc._scatter_update.lower(
        svc._lanes, jnp.int32(0), ids, jnp.zeros((N, 8, D)),
        jnp.zeros(N, jnp.int32)).as_text()


# ------------------------------------------------- (g) the answers' fetch


def _tenant_minor(monkeypatch, sess, piece_bytes):
    """Make ``sess`` hand its batched answers over with the tenant axis
    minor, as a TPU keeps them, and the gateway cut batches whose leaves
    reach ``piece_bytes`` into pieces below it."""
    from jax.experimental.layout import Format, Layout

    sess._ensure_plan()
    finalize = sess._finalize_batch

    def tenant_minor(merged):
        return jax.tree.map(
            lambda leaf: jax.device_put(leaf, Format(
                Layout(tuple(range(1, leaf.ndim)) + (0,)), leaf.sharding)),
            finalize(merged))

    monkeypatch.setattr(sess, "_finalize_batch", tenant_minor)
    monkeypatch.setattr(gateway, "_PIECE_BYTES", piece_bytes)
    return sess


def _fetches(monkeypatch):
    """Record the path and piece count of every fetch."""
    seen = []
    fetch = gateway._fetch

    def recording(results):
        path, size, pieces = fetch(results)
        seen.append((path, len(pieces)))
        return path, size, pieces

    monkeypatch.setattr(gateway, "_fetch", recording)
    return seen


def _bits(tree):
    return [(leaf.dtype, leaf.shape, np.asarray(leaf).tobytes())
            for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("hold", [True, False], ids=["held", "dropped"])
@pytest.mark.parametrize("layout", ["row_major", "tenant_minor"])
def test_resolved_answers_stay_bit_identical_over_later_ticks(
        monkeypatch, layout, hold):
    N = 5
    sess = _session(N)
    if layout == "tenant_minor":
        # 64-byte lag leaves a tenant: pieces of 2, 2 and 1 tenants
        _tenant_minor(monkeypatch, sess, piece_bytes=150)
    seen = _fetches(monkeypatch)
    gw = StatsGateway(sess)
    held = []

    async def round_(seed):
        chunks = _chunks(N, c=24, seed=seed)
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs)
        futs = [gw.submit_query(u) for u in range(N)]
        await gw.tick()
        return await asyncio.gather(*futs)

    first = run(round_(0))
    want = [_bits(a) for a in first]
    for k in range(1, 4):
        later = run(round_(k))
        assert [_bits(a) for a in later] != want
        if hold:
            held.append(later)
        del later
        # the later ticks' answers landed in freed and reused host memory;
        # the tick-0 answers kept every bit
        assert [_bits(a) for a in first] == want
    assert set(seen) == ({("pieces", 3)} if layout == "tenant_minor"
                         else {("device_get", 1)})


@pytest.mark.parametrize("piece_bytes", [1, 200, 1 << 30],
                         ids=["one_tenant", "short_last", "whole"])
def test_pieces_match_device_get_for_a_mixed_plan(monkeypatch, piece_bytes):
    N = 7

    def mixed():
        sess = _session(N)
        sess.welch(16, overlap=8)
        sess.forecast(4, model="ar", p=2)
        return sess

    chunks = _chunks(N, c=48, seed=21)
    only = [None, "forecast", ("moments", "welch"), None, "autocovariance",
            ("forecast", "welch", "moments"), None]

    async def serve(gw):
        futs = [gw.submit_ingest(u, chunks[u]) for u in range(N)]
        await gw.tick()
        await asyncio.gather(*futs)
        # tenant 3 twice and tenant 0 last: waiters out of tenant order
        order = [3, 1, 3, 6, 2, 5, 4, 0]
        futs = [gw.submit_query(u, only=only[u]) for u in order]
        await gw.tick()
        return order, await asyncio.gather(*futs)

    plain = mixed()
    order, _ = run(serve(StatsGateway(plain)))
    ids = list(dict.fromkeys(order))
    direct = jax.device_get(plain.query_batch(np.asarray(ids, np.int32)))

    seen = _fetches(monkeypatch)
    sess = _tenant_minor(monkeypatch, mixed(), piece_bytes)
    order, answers = run(serve(StatsGateway(sess)))
    # the largest leaf, Welch's PSD, has 72 bytes a tenant: pieces of one
    # tenant, and of two
    assert seen == [{1: ("pieces", 7), 200: ("pieces", 4),
                     1 << 30: ("device_get", 1)}[piece_bytes]]
    for u, got in zip(order, answers):
        want = jax.tree.map(lambda leaf: leaf[ids.index(u)], direct)
        if only[u] is not None:
            kinds = (only[u],) if isinstance(only[u], str) else only[u]
            want = {k: want[k] for k in kinds}
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert _bits(got) == _bits(want)


def test_fetch_path_follows_the_device_layout(monkeypatch):
    from jax.experimental.layout import Format, Layout

    x = jnp.arange(24.0).reshape(2, 3, 4)
    assert gateway._row_major(x)
    assert gateway._row_major(jnp.arange(3.0))
    minor = jax.device_put(x, Format(Layout((1, 2, 0)), x.sharding))
    assert not gateway._row_major(minor)
    # a TPU's f32[n, d]: row-major order, in (8, 128) tiles
    tiled = types.SimpleNamespace(ndim=2, format=types.SimpleNamespace(
        layout=Layout((0, 1), tiling=((8, 128),))))
    assert not gateway._row_major(tiled)

    # leaves of 48 bytes a tenant; a piece is one tenant below 60 bytes
    batch = {"a": minor, "b": jnp.arange(2.0)}
    assert gateway._fetch(batch)[:2] == ("device_get", 2)
    monkeypatch.setattr(gateway, "_PIECE_BYTES", 60)
    assert gateway._keep_freed_pages()
    path, size, pieces = gateway._fetch(batch)
    assert (path, size, len(pieces)) == ("pieces", 1, 2)
    for j, piece in enumerate(pieces):
        np.testing.assert_array_equal(piece["a"], np.asarray(x)[j:j + 1])
        np.testing.assert_array_equal(piece["b"], [float(j)])
    # the host's own layout copies nothing: no pieces
    assert gateway._fetch({"a": x})[:2] == ("device_get", 2)
    # nor where the C library cannot keep freed pages
    monkeypatch.setattr(gateway, "_keep_freed_pages", lambda: False)
    assert gateway._fetch(batch)[:2] == ("device_get", 2)


# ------------------------------------------ (h) the ingest batch's staging


async def _ingest_rounds(gw, feeds, kept=None, ask=()):
    """Ingest each feed ({tenant: chunk}) in a tick of its own, recording
    the kept buffers after each when ``kept`` is a list; then answer the
    tenants in ``ask`` in one more tick."""
    for feed in feeds:
        futs = [gw.submit_ingest(u, chunk) for u, chunk in feed.items()]
        await gw.tick()
        await asyncio.gather(*futs)
        if kept is not None:
            kept.append({key: k.buf for key, k in gw._kept.items()})
    futs = [gw.submit_query(u) for u in ask]
    await gw.tick()
    return await asyncio.gather(*futs)


def test_kept_buffer_is_reused_and_answers_match_np_stack(monkeypatch):
    N, c, rounds = 4, 24, 4
    feeds = [_chunks(N, c=c, seed=30 + k) for k in range(rounds)]
    heap = StatsGateway(_session(N))
    want = run(_ingest_rounds(heap, feeds, ask=range(N)))
    assert not heap._kept

    monkeypatch.setattr(gateway, "_PIECE_BYTES", 1)
    gw, kept = StatsGateway(_session(N)), []
    got = run(_ingest_rounds(gw, feeds, kept, ask=range(N)))
    key = (N, c, D, np.dtype(np.float32))
    assert [list(k) for k in kept] == [[key]] * rounds
    # one buffer, allocated once, written every round
    assert all(k[key] is kept[0][key] for k in kept)
    assert gw.counters["ingest_stage_new"] == 1
    assert gw.counters["ingest_stage_kept"] == rounds
    np.testing.assert_array_equal(kept[0][key],
                                  np.stack([feeds[-1][u] for u in range(N)]))
    # the same bytes reached the device as with a fresh np.stack ...
    assert [_bits(a) for a in got] == [_bits(a) for a in want]
    # ... and the answers are the float64 reference's ("paper"
    # normalization: lag sums over n - h - 1)
    for u in range(N):
        x = np.concatenate([feed[u] for feed in feeds]).astype(np.float64)
        n = len(x)
        acov = np.stack([x[: n - h].T @ x[h:] / (n - h - 1) for h in range(4)])
        np.testing.assert_allclose(got[u]["autocovariance"], acov,
                                   rtol=1e-5, atol=1e-6)
        assert float(got[u]["moments"]["count"]) == n - 8 + 1


@pytest.mark.parametrize("aliased", [False, True], ids=["copied", "aliased"])
def test_kept_buffer_is_rewritten_only_after_its_device_copy_is_ready(
        monkeypatch, aliased):
    monkeypatch.setattr(gateway, "_PIECE_BYTES", 1)
    # aliased: the device batch is the buffer itself, so the programs that
    # read it (ending in the session's state) must have run too
    monkeypatch.setattr(gateway, "_aliases", lambda placed, buf: aliased)
    N, rounds = 3, 3
    sess = _session(N)
    gw = StatsGateway(sess)
    log, placements = [], []
    ingest, block = sess.ingest, jax.block_until_ready

    def recording_ingest(ids, batch):
        (kept,) = gw._kept.values()
        assert batch is kept.buf
        placed = ingest(ids, batch)
        log.append(("placed", len(placements)))
        placements.append((placed, batch.copy()))
        return placed

    def recording_block(x):
        leaves = jax.tree.leaves(x)
        state = jax.tree.leaves(sess.state_template())
        for k, (placed, _) in enumerate(placements):
            if any(leaf is p for leaf in leaves for p in placed):
                log.append(("ready", k))
        if any(leaf is s for leaf in leaves for s in state):
            log.append(("state",))
        if placements:
            # not yet rewritten
            np.testing.assert_array_equal(gw._kept[(N, 16, D, np.dtype(
                np.float32))].buf, placements[-1][1])
        return block(x)

    monkeypatch.setattr(sess, "ingest", recording_ingest)
    monkeypatch.setattr(jax, "block_until_ready", recording_block)
    run(_ingest_rounds(gw, [_chunks(N, c=16, seed=k) for k in range(rounds)]))
    waits = [("state",)] if aliased else []
    assert log == [("placed", 0), ("ready", 0), *waits, ("placed", 1),
                   ("ready", 1), *waits, ("placed", 2)]


@pytest.mark.parametrize("piece_bytes", [None, 1], ids=["heap", "kept"])
def test_stack_span_says_where_the_batch_was_staged(
        monkeypatch, tmp_path, piece_bytes):
    N = 3
    gw = StatsGateway(_session(N))
    if piece_bytes is not None:
        monkeypatch.setattr(gateway, "_PIECE_BYTES", piece_bytes)
    feeds = [_chunks(N, c=16, seed=k) for k in range(3)]
    run(_ingest_rounds(gw, feeds[:1]))    # compiles outside the profile
    _, events = _profiled(tmp_path, _ingest_rounds(gw, feeds[1:]))
    staged = [e[3]["staged"] for e in sorted(events, key=lambda e: e[1])
              if e[0] == "repro.ingest.stack"]
    if piece_bytes is None:
        # small batches: a fresh np.stack, no buffer kept, nothing counted
        assert staged == ["heap", "heap"]
        assert not gw._kept
        assert "ingest_stage_new" not in gw.counters
        assert "ingest_stage_kept" not in gw.counters
    else:
        assert staged == ["kept", "kept"]
        assert gw.counters["ingest_stage_new"] == 1
        assert gw.counters["ingest_stage_kept"] == 3


def test_ragged_and_changing_shapes_keep_one_buffer_per_shape_used(
        monkeypatch):
    rng = np.random.RandomState(40)

    def feed(lengths):
        return {u: rng.randn(c, D).astype(np.float32)
                for u, c in lengths.items()}

    f32 = np.dtype(np.float32)
    ticks = [
        # two chunk lengths in one tick: two batches, two buffers
        (feed({0: 16, 1: 16, 2: 16, 3: 24, 4: 24, 5: 24}),
         {(3, 16, D, f32), (3, 24, D, f32)}),
        # another row count: both earlier buffers given up
        (feed({0: 24, 1: 24, 2: 24, 3: 24}), {(4, 24, D, f32)}),
        (feed({2: 24, 3: 24, 4: 24, 5: 24}), {(4, 24, D, f32)}),
        (feed({0: 16, 5: 16}), {(2, 16, D, f32)}),
    ]
    feeds = [f for f, _ in ticks]
    heap = StatsGateway(_session(6))
    want = run(_ingest_rounds(heap, feeds, ask=range(6)))

    monkeypatch.setattr(gateway, "_PIECE_BYTES", 1)
    gw, kept = StatsGateway(_session(6)), []
    got = run(_ingest_rounds(gw, feeds, kept, ask=range(6)))
    assert [set(k) for k in kept] == [shapes for _, shapes in ticks]
    assert kept[2][(4, 24, D, f32)] is kept[1][(4, 24, D, f32)]
    assert gw.counters["ingest_stage_new"] == 4
    assert gw.counters["ingest_stage_kept"] == 5
    assert [_bits(a) for a in got] == [_bits(a) for a in want]


def test_sentinel_sends_a_poisoned_row_of_a_kept_batch_to_its_policy(
        monkeypatch):
    N, c = 4, 16

    def poisoned(seed, bad):
        chunks = _chunks(N, c=c, seed=seed)
        for u in bad:
            chunks[u][3, 1] = np.nan
        return chunks

    async def serve(gw):
        gw.set_tenant_policy(1, "sanitize")
        gw.set_tenant_policy(2, "quarantine")
        outcomes = []
        for chunks in (poisoned(50, ()), poisoned(51, (1, 2, 3)),
                       poisoned(52, ())):
            futs = {u: gw.submit_ingest(u, chunk) for u, chunk in chunks.items()
                    if u not in gw.quarantined}
            await gw.tick()
            done = await asyncio.gather(*futs.values(), return_exceptions=True)
            outcomes.append({u: type(r).__name__ if isinstance(r, Exception)
                             else "ok" for u, r in zip(futs, done)})
        futs = [gw.submit_query(u) for u in (0, 1, 3)]
        await gw.tick()
        return outcomes, await asyncio.gather(*futs)

    cfg = GatewayConfig(sentinel=True, sentinel_policy="reject")
    heap = StatsGateway(_session(N), cfg)
    want_outcomes, want = run(serve(heap))

    monkeypatch.setattr(gateway, "_PIECE_BYTES", 1)
    gw = StatsGateway(_session(N), cfg)
    outcomes, got = run(serve(gw))
    assert outcomes == want_outcomes == [
        {0: "ok", 1: "ok", 2: "ok", 3: "ok"},
        {0: "ok", 1: "ok", 2: "PoisonedChunk", 3: "PoisonedChunk"},
        {0: "ok", 1: "ok", 3: "ok"},
    ]
    assert gw.quarantined == {2}
    assert gw.counters["sanitized_chunks"] == 1
    assert gw.counters["rejected_ingest_poisoned"] == 2
    # rounds 0 and 1 share a buffer; round 2 has three rows
    assert gw.counters["ingest_stage_new"] == 2
    assert gw.counters["ingest_stage_kept"] == 3
    assert [_bits(a) for a in got] == [_bits(a) for a in want]
    assert all(np.isfinite(leaf).all()
               for a in got for leaf in jax.tree.leaves(a))


def test_aliases_tells_a_device_view_of_the_buffer_from_a_copy():
    # a 64-byte aligned host buffer: the CPU device reads it in place
    raw = np.empty(3 * 16 * D * 4 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    buf = raw[off:off + 3 * 16 * D * 4].view(np.float32).reshape(3, 16, D)
    buf[...] = 1.0
    view = jax.device_put(buf)
    assert gateway._aliases(view, buf)
    assert not gateway._aliases(view + 0, buf)
    assert not gateway._aliases(view, buf[1:].copy())
