"""Checks of a `FrameSession` whose tenants are split over four devices.

Run as a script in a process that sees four devices (four virtual CPU
devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``); prints one
JSON object, check name → ``"ok"`` or the failure's traceback.
`tests/test_tenant_sharding.py` runs it and asserts on each entry.
"""
import asyncio
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import FrameSession  # noqa: E402
from repro.core.estimators.spectral import welch_psd  # noqa: E402
from repro.core.estimators.stats import (  # noqa: E402
    autocovariance,
    moment_engine,
    streaming_window_moments,
)
from repro.serving import gateway  # noqa: E402
from repro.serving.gateway import StatsGateway  # noqa: E402
from repro.serving.rolling import RollingStatsService  # noqa: E402

D, C, LAG, WINDOW, NPERSEG = 3, 32, 4, 16, 32
CHIPS = 4


def session(n, devices=CHIPS, **kw):
    s = FrameSession(d=D, num_users=n, backend="jnp", compensated=True,
                     devices=devices, **kw)
    s.autocovariance(LAG)
    s.moments(WINDOW)
    s.welch(nperseg=NPERSEG, overlap=NPERSEG // 2)
    return s


def arrivals(n, seed, rounds=4):
    """Every tenant in round 0, then random subsets in random order: the
    chips get uneven row counts, some none at all."""
    rng = np.random.default_rng(seed)
    out = []
    for r in range(rounds):
        ids = rng.permutation(n)[: n if r == 0 else rng.integers(1, n)]
        out.append((ids, rng.standard_normal((len(ids), C, D)).astype(np.float32)))
    return out


def series(n, feed):
    rows = [[] for _ in range(n)]
    for ids, chunks in feed:
        for u, chunk in zip(ids, chunks):
            rows[u].append(chunk)
    return [np.concatenate(r) for r in rows]


def fed(n, seed, devices=CHIPS):
    s = session(n, devices)
    for ids, chunks in arrivals(n, seed):
        s.ingest(ids, chunks)
    return s


def assert_same(got, want, rtol=1e-5, atol=1e-6):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol, atol=atol)


def eager(x):
    me = moment_engine(WINDOW, D, backend="jnp")
    return {
        "autocovariance": autocovariance(jnp.asarray(x), LAG, backend="jnp"),
        "moments": streaming_window_moments(me, me.from_chunk(jnp.asarray(x))),
        "welch": welch_psd(jnp.asarray(x), nperseg=NPERSEG, overlap=NPERSEG // 2,
                           backend="jnp"),
    }


# ------------------------------------------------------------------ checks
def matches_one_device(n):
    split, whole = fed(n, n), fed(n, n, devices=1)
    ids = np.arange(n)
    assert_same(split.query_batch(ids), whole.query_batch(ids))
    back = ids[::-1].copy()
    assert_same(split.query_batch(back), whole.query_batch(back))
    for u in (0, n - 1):
        assert_same(split.query(u), whole.query(u))
    # each chip holds one block of the tenant axis, nothing else
    block = -(-n // CHIPS)
    for leaf in jax.tree.leaves(split.state_template()):
        if hasattr(leaf, "sharding"):
            assert len(leaf.sharding.device_set) == CHIPS
            assert {sh.data.shape[1] for sh in leaf.addressable_shards} == {block}


def matches_eager(n):
    s = fed(n, n)
    got = s.query_batch(np.arange(n))
    for u, x in enumerate(series(n, arrivals(n, n))):
        want = eager(x)
        mine = jax.tree.map(lambda leaf: leaf[u], got)
        np.testing.assert_allclose(mine["autocovariance"], want["autocovariance"],
                                   rtol=1e-5, atol=1e-4)
        for stat in ("mean", "var", "count"):
            np.testing.assert_allclose(mine["moments"][stat], want["moments"][stat],
                                       rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(mine["welch"][0], want["welch"][0], rtol=1e-6)
        np.testing.assert_allclose(mine["welch"][1], want["welch"][1],
                                   rtol=1e-4, atol=1e-5)


def lengths():
    n = 10
    s = fed(n, 3)
    want = [len(x) for x in series(n, arrivals(n, 3))]
    got = np.asarray(s.lengths())
    assert got.shape == (n,) and got.tolist() == want, (got, want)
    assert s.audit().shape == (n,) and s.audit().all()


def state_round_trip():
    n = 6
    s = fed(n, 4)
    snap = s.export_state()
    fresh = session(n)
    fresh.import_state(snap)
    ids = np.arange(n)
    assert_same(fresh.query_batch(ids), s.query_batch(ids), rtol=0, atol=0)
    assert np.asarray(fresh.lengths()).tolist() == np.asarray(s.lengths()).tolist()
    # both keep serving from the same compiled programs
    more = arrivals(n, 5, rounds=1)[0]
    for sess in (s, fresh):
        sess.ingest(*more)
    assert_same(fresh.query_batch(ids), s.query_batch(ids), rtol=0, atol=0)
    (svc,) = fresh._services
    assert svc._scatter_update._cache_size() == 1


def import_tenant():
    n, u = 10, 7
    src, dst = fed(n, 6), fed(n, 7)
    ids = np.arange(n)
    before = dst.query_batch(ids)
    dst.import_tenant(u, src.tenant_slice(src.export_state(), u))
    after = dst.query_batch(ids)
    want = jax.tree.map(lambda leaf: leaf[u], src.query_batch(ids))
    assert_same(jax.tree.map(lambda leaf: leaf[u], after), want, rtol=0, atol=0)
    others = np.setdiff1d(ids, [u])
    assert_same(jax.tree.map(lambda leaf: leaf[others], after),
                jax.tree.map(lambda leaf: leaf[others], before), rtol=0, atol=0)
    assert_same(dst.export_tenant(u), src.export_tenant(u), rtol=0, atol=0)


def padding_rows():
    n = 6
    s, plain = session(n), session(n)
    ids = np.array([1, 0, 4, 2])
    rng = np.random.default_rng(8)
    chunks = rng.standard_normal((len(ids), C, D)).astype(np.float32)
    laid, rows = s.lay_out(ids)
    assert rows is not None and (laid == -1).sum() == len(laid) - len(ids)
    assert laid[rows].tolist() == ids.tolist()
    # padding rows carry NaN: were one absorbed, every answer it reached
    # would be NaN
    batch = np.full((len(laid), C, D), np.nan, np.float32)
    batch[rows] = chunks
    s.ingest(laid, batch)
    plain.ingest(ids, chunks)
    assert s.lay_out(laid)[1] is None
    assert_same(s.query_batch(np.arange(n)), plain.query_batch(np.arange(n)),
                rtol=0, atol=0)
    assert np.asarray(s.lengths()).tolist() == [C, C, C, 0, C, 0]
    assert s.audit().all()
    # a laid-out query answers in place; padding rows answer nothing
    got = s.query_batch(laid)
    assert_same(jax.tree.map(lambda leaf: leaf[rows], got),
                plain.query_batch(ids), rtol=0, atol=0)


def gateway_tick(pieces):
    n = 10
    s, whole = session(n), session(n, devices=1)
    if pieces:
        # the answers held tenant-minor, as a TPU holds them, and cut
        # into pieces of one tenant
        from jax.experimental.layout import Format, Layout

        s._ensure_plan()
        finalize = s._finalize_batch
        s._finalize_batch = lambda merged: jax.tree.map(
            lambda leaf: jax.device_put(leaf, Format(
                Layout(tuple(range(1, leaf.ndim)) + (0,)), leaf.sharding)),
            finalize(merged))
        gateway._PIECE_BYTES = 1
    gw, ref = StatsGateway(s), StatsGateway(whole)
    rng = np.random.default_rng(9)
    # tenant 3 twice: its second chunk waits for the next tick
    order = [3, 9, 0, 3, 5, 1, 8, 6]
    chunks = rng.standard_normal((len(order), C, D)).astype(np.float32)
    asks = [9, 0, 4, 3, 7, 2, 9]
    only = [None, "welch", None, ("moments", "autocovariance"), None, None, None]

    async def serve(g):
        futs = [g.submit_ingest(u, c) for u, c in zip(order, chunks)]
        await g.tick()
        await g.tick()
        await asyncio.gather(*futs)
        futs = [g.submit_query(u, only=o) for u, o in zip(asks, only)]
        await g.tick()
        return await asyncio.gather(*futs)

    loop = asyncio.new_event_loop()
    try:
        got = loop.run_until_complete(serve(gw))
        want = loop.run_until_complete(serve(ref))
    finally:
        loop.close()
    for g, w in zip(got, want):
        assert jax.tree.structure(g) == jax.tree.structure(w)
        assert_same(g, w)
    # blocks of 3 tenants.  Tick 1: 7 tenants → 2, 2, 2, 1 rows, padded
    # to 8; tick 2: tenant 3 alone → 4 rows.  Queries: 6 tenants → 2, 2,
    # 1, 1 rows, padded to 8.
    assert gw.counters["ingest_pad_rows"] == 1 + 3
    assert gw.counters["query_pad_rows"] == 2
    assert gw.counters["programs_ingest"] == 2
    assert ref.counters["ingest_pad_rows"] == ref.counters["query_pad_rows"] == 0
    if pieces:
        assert gateway._fetch(s.query_batch(s.lay_out(np.arange(n))[0]))[0] == "pieces"


def gateway_tick_kept():
    """Every ingest batch stacked into a kept buffer, in owner-chip order
    with padding rows, over rounds of uneven blocks: the gateway answers as
    a one-device session fed the same chunks directly."""
    n = 10
    s, whole = session(n), session(n, devices=1)
    gw = StatsGateway(s)
    feed = arrivals(n, 11, rounds=5)

    async def round_(ids, chunks):
        futs = [gw.submit_ingest(int(u), c) for u, c in zip(ids, chunks)]
        await gw.tick()
        await asyncio.gather(*futs)
        futs = [gw.submit_query(u) for u in range(n)]
        await gw.tick()
        return await asyncio.gather(*futs)

    piece_bytes, gateway._PIECE_BYTES = gateway._PIECE_BYTES, 1
    loop = asyncio.new_event_loop()
    try:
        for ids, chunks in feed:
            got = loop.run_until_complete(round_(ids, chunks))
            whole.ingest(ids, chunks)
            want = whole.query_batch(np.arange(n))
            for u, g in enumerate(got):
                assert_same(g, jax.tree.map(lambda leaf: leaf[u], want))
    finally:
        loop.close()
        gateway._PIECE_BYTES = piece_bytes
    assert gw.counters["ingest_stage_kept"] == len(feed)
    assert gw.counters["ingest_pad_rows"] > 0
    assert len(gw._kept) == 1


def window_raises():
    for make in (
        lambda: FrameSession(d=D, num_users=6, window=64, devices=CHIPS),
        lambda: RollingStatsService(
            session(6, devices=1).plan.groups[0].engine, 6, window=64,
            devices=CHIPS),
    ):
        try:
            make()
        except ValueError as e:
            assert "window" in str(e) and "devices" in str(e), e
        else:
            raise AssertionError("window= with devices=4 did not raise")


CHECKS = {
    "matches_one_device_6": lambda: matches_one_device(6),
    "matches_one_device_10": lambda: matches_one_device(10),
    "matches_eager_6": lambda: matches_eager(6),
    "matches_eager_10": lambda: matches_eager(10),
    "lengths": lengths,
    "state_round_trip": state_round_trip,
    "import_tenant": import_tenant,
    "padding_rows": padding_rows,
    "gateway_tick": lambda: gateway_tick(pieces=False),
    "gateway_tick_pieces": lambda: gateway_tick(pieces=True),
    "gateway_tick_kept": gateway_tick_kept,
    "window_raises": window_raises,
}


def main() -> int:
    if len(jax.devices()) != CHIPS:
        print(json.dumps({"error": f"{len(jax.devices())} devices, not {CHIPS}"}))
        return 1
    out = {}
    for name, check in CHECKS.items():
        try:
            check()
            out[name] = "ok"
        except Exception:   # report every check, not only the first failure
            out[name] = traceback.format_exc()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
