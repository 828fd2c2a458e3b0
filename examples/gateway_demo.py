"""Serving demo — the async gateway over a multi-tenant FrameSession.

The paper's mergeable partials make per-tenant statistics *servable*:
state is a fixed-size stacked pytree, ingest is a scatter-⊕, queries are
a gather-⊕-finalize.  `repro.serving.gateway.StatsGateway` is the
concurrency front door over that math:

    gw = StatsGateway(session, GatewayConfig(checkpoint_dir=...))
    gw.start()                            # background coalescing ticks
    await gw.ingest(tenant, chunk)        # any number of asyncio clients
    stats = await gw.query(tenant)

Every tick, all admitted ingests coalesce into ONE donated scatter
program and all queries into ONE vmapped fused finalize — device cost
per tick is flat in the number of connected clients.  The demo below
runs three acts:

  1. 64 concurrent tenant tasks ingest + query through a ticking
     gateway; the metrics show the coalescing ratio.
  2. An over-rate tenant is rejected (RateLimited backpressure) while
     everyone else keeps flowing.
  3. The process "crashes" (the gateway is abandoned), a new gateway
     restores from the periodic snapshot, and serves answers identical
     to pre-crash — zero re-ingest of history.

  PYTHONPATH=src python examples/gateway_demo.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import asyncio
import tempfile

import numpy as np

from repro.core.frame import FrameSession
from repro.serving.gateway import (
    GatewayConfig,
    RateClass,
    RateLimited,
    StatsGateway,
)

TENANTS, D, CHUNK = 64, 3, 128


def make_session() -> FrameSession:
    sess = FrameSession(d=D, num_users=TENANTS, backend="jnp")
    sess.autocovariance(4)
    sess.moments(32)
    return sess


async def tenant_task(gw: StatsGateway, tenant: int, rounds: int) -> dict:
    """One simulated client: stream chunks, then read statistics."""
    rng = np.random.RandomState(tenant)
    for _ in range(rounds):
        await gw.ingest(tenant, rng.randn(CHUNK, D).astype(np.float32))
    return await gw.query(tenant)


async def main() -> None:
    ckpt = tempfile.mkdtemp(prefix="gateway_demo_")
    cfg = GatewayConfig(
        tick_interval=0.002,
        snapshot_every=5,
        checkpoint_dir=ckpt,
        rate_classes={
            "default": RateClass(),
            "free-tier": RateClass(ingest_per_tick=1, burst=1),
        },
    )

    # -- act 1: concurrent tenants through the background tick loop -------
    gw = StatsGateway(make_session(), cfg)
    gw.start()
    answers = await asyncio.gather(
        *(tenant_task(gw, t, rounds=4) for t in range(TENANTS))
    )
    m = gw.metrics()
    served = m["ingest"]["count"] + m["query"]["count"]
    programs = m["ingest"]["programs"] + m["query"]["programs"]
    print(f"served {served} requests from {TENANTS} tenants in "
          f"{m['ticks']} ticks using {programs} device programs "
          f"({served / programs:.0f} requests/program)")
    print(f"latency p50/p99: ingest {m['ingest']['p50_us']:.0f}/"
          f"{m['ingest']['p99_us']:.0f}us, query {m['query']['p50_us']:.0f}/"
          f"{m['query']['p99_us']:.0f}us")
    mean0 = np.asarray(answers[0]["moments"]["mean"])
    print(f"tenant 0 rolling mean (first dim): {mean0[0]:.4f}")

    # -- act 2: backpressure — over-rate tenant, unharmed neighbours ------
    gw.set_tenant_class(0, "free-tier")
    chunk = np.zeros((CHUNK, D), np.float32)
    rejected = 0
    admitted = gw.submit_ingest(0, chunk)   # consumes the only token
    try:
        gw.submit_ingest(0, chunk)          # same tick: over rate
    except RateLimited:
        rejected += 1
    neighbour = gw.submit_ingest(1, chunk)  # sails through, same tick
    await asyncio.gather(admitted, neighbour)
    print(f"free-tier tenant rejected {rejected} over-rate request(s); "
          f"others unaffected (rejections total: "
          f"{gw.counters['rejected_ingest_rate']})")

    # -- act 3: crash, restart, identical answers -------------------------
    pre = await gw.query(7)
    gw._loop_rt.manager.flush()             # let the async snapshot land
    del gw                                  # the "crash": no graceful stop

    gw2 = StatsGateway(make_session(), cfg)  # same ckpt dir → restores
    gw2.start()
    post = await gw2.query(7)
    same = np.array_equal(
        np.asarray(pre["autocovariance"]), np.asarray(post["autocovariance"])
    )
    print(f"restarted from snapshot (restored="
          f"{gw2.counters['restored_from_snapshot']}, resume tick "
          f"{gw2.metrics()['tick']}); tenant 7 answers identical: {same} "
          f"with {gw2.counters['programs_ingest']} re-ingest programs")
    await gw2.stop()


if __name__ == "__main__":
    enable_compile_cache()
    asyncio.run(main())
