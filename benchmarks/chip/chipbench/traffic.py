"""The one traffic generator: a host fleet in closed-loop rounds.

A traffic file (``traffic/<mix>.json``) gives:

- ``pool_rounds``: distinct rounds of readings made from the seed in
  set-up and cycled; in round r host h sends row (h + c) mod hosts of pool
  round r mod pool_rounds, c = r div pool_rounds the cycle, so no host's
  series repeats itself (a repeating series would add the same sums again
  and again and round them alike);
- ``setup``: ``[[kind, count], ...]`` rounds run before the window (they
  fill state and warm every program shape the window uses);
- ``window``: the round kinds cycled for ``--seconds``;
- ``after``: rounds run once the window has closed, for the check;
- ``sampled_hosts``: how many hosts, drawn from the seed, are compared
  with the reference.

A round is ``ingest`` (every host submits its next chunk) or ``query``
(every host asks for its statistics).  The loop is closed, as the TSBS
loader's is: the next round starts when every request of the last one has
resolved.

The readings follow the configuration's ``series`` model, TSBS's
``ClampedRandomWalk``: each field starts uniform in its range and moves by
a normal step each reading, clamped to the range.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generators from one seed of any size or sign."""
    return np.random.default_rng([seed % (1 << 64), stream])


def make_pool(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """(pool_rounds, hosts, chunk, metrics) float32 readings; the rounds
    follow each other in time, so a cycled pool is one continuing series
    (with one seam per cycle)."""
    hosts, d, chunk = config["hosts"], config["metrics"], config["chunk"]
    model = config["series"]
    if model["model"] != "clamped_walk":
        raise ValueError(f"unknown series model {model['model']!r}")
    ranges = [f["range"] for f in model["fields"] for _ in range(f["count"])]
    if len(ranges) != d:
        raise ValueError(f"series fields give {len(ranges)} metrics, config has {d}")
    lo, hi = np.asarray(ranges, np.float32).T
    step_mean, step_std = model["step"]
    rng = rng_for(seed, 0)
    state = rng.uniform(lo, hi, (hosts, d)).astype(np.float32)
    pool = np.empty((traffic["pool_rounds"], hosts, chunk, d), np.float32)
    for r in range(traffic["pool_rounds"]):
        steps = rng.standard_normal((chunk, hosts, d), dtype=np.float32)
        steps = steps * np.float32(step_std) + np.float32(step_mean)
        for j in range(chunk):
            state = np.clip(state + steps[j], lo, hi)
            pool[r, :, j] = state
    return pool


def sampled_hosts(config: dict, traffic: dict, seed: int) -> list:
    rng = rng_for(seed, 1)
    k = min(traffic["sampled_hosts"], config["hosts"])
    return sorted(rng.choice(config["hosts"], k, replace=False).tolist())


def pool_row(pool: np.ndarray, host: int, r: int) -> int:
    """Which row of pool round r mod pool_rounds host ``host`` sends in
    global round ``r``."""
    return (host + r // pool.shape[0]) % pool.shape[1]


def host_series(pool: np.ndarray, host: int, rounds) -> np.ndarray:
    """The readings ``host`` sent in the given global rounds, in order."""
    P = pool.shape[0]
    return np.concatenate([pool[r % P, pool_row(pool, host, r)] for r in rounds])


@dataclasses.dataclass
class Round:
    kind: str
    requests: int
    failed: int
    latencies: Optional[np.ndarray] = None  # seconds, query rounds


def _annotate(name: str, **kw):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **kw)


class FleetDriver:
    """Drives a `StatsGateway` round by round from the pool.

    Keeps what the check needs: which rounds each host's ingests were
    acknowledged in, one answer per sampled host drawn from the seed among
    the rounds marked ``keep`` (reservoir sampling), and the last query
    round's answers for every host.
    """

    def __init__(self, gateway, pool: np.ndarray, sampled: list, seed: int,
                 drain):
        self.gw = gateway
        self.pool = pool
        self.hosts = pool.shape[1]
        self.sampled = sampled
        self.drain = drain          # blocks until the served state is ready
        self.rounds_sent = 0        # global round index of the next ingest
        self.missed: dict = {}      # host -> ingest rounds not acknowledged
        self.samples: dict = {}     # host -> (acknowledged rounds, answer)
        self._seen: dict = {}       # host -> kept query rounds so far
        self.last_answers: Optional[list] = None
        self._rng = rng_for(seed, 2)

    def acked_rounds(self, host: int) -> list:
        missed = self.missed.get(host, ())
        return [r for r in range(self.rounds_sent) if r not in missed]

    async def run(self, kind: str, keep: bool = False) -> Round:
        with _annotate("bench.round", kind=kind):
            if kind == "ingest":
                return await self._ingest()
            if kind == "query":
                return await self._query(keep)
        raise ValueError(f"unknown round kind {kind!r}")

    async def _ingest(self) -> Round:
        r = self.rounds_sent
        chunks = self.pool[r % self.pool.shape[0]]
        shift = r // self.pool.shape[0]
        futs, failed = [], 0
        with _annotate("bench.submit"):
            for h in range(self.hosts):
                try:
                    futs.append((h, self.gw.submit_ingest(
                        h, chunks[(h + shift) % self.hosts])))
                except Exception:   # a rejection is a failed request
                    self.missed.setdefault(h, set()).add(r)
                    failed += 1
        with _annotate("bench.tick"):
            await self.gw.tick()
        for h, fut in futs:
            if not fut.done() or fut.cancelled() or fut.exception() is not None:
                self.missed.setdefault(h, set()).add(r)
                failed += 1
        self.rounds_sent += 1
        return Round("ingest", self.hosts, failed)

    async def _query(self, keep: bool) -> Round:
        submitted = np.empty(self.hosts)
        futs, failed = [], 0
        with _annotate("bench.submit"):
            for h in range(self.hosts):
                submitted[h] = time.perf_counter()
                try:
                    futs.append(self.gw.submit_query(h))
                except Exception:
                    futs.append(None)
                    failed += 1
        with _annotate("bench.tick"):
            await self.gw.tick()
        done = time.perf_counter()
        ok = [f is not None and f.done() and not f.cancelled()
              and f.exception() is None for f in futs]
        failed += sum(1 for f, good in zip(futs, ok) if f is not None and not good)
        with _annotate("bench.answers"):
            if keep:
                self._keep(futs, ok)
            self.last_answers = [f.result() if good else None
                                 for f, good in zip(futs, ok)]
        answered = np.asarray(ok)
        return Round("query", self.hosts, failed,
                     latencies=(done - submitted)[answered])

    def _keep(self, futs, ok) -> None:
        import jax

        for h in self.sampled:
            if not ok[h]:
                continue
            seen = self._seen.get(h, 0) + 1
            self._seen[h] = seen
            if self._rng.random() * seen < 1.0:   # keep with chance 1/seen
                answer = jax.tree.map(np.array, futs[h].result())
                self.samples[h] = (self.acked_rounds(h), answer)

    async def run_phase(self, phase, keep: bool = False) -> list:
        """``[[kind, count], ...]`` in order."""
        out = []
        for kind, count in phase:
            for _ in range(count):
                out.append(await self.run(kind, keep))
        with _annotate("bench.drain"):
            self.drain()
        return out

    async def window(self, pattern: list, seconds: float):
        """Cycle ``pattern`` for ``seconds``; returns the rounds and the
        window's start and end (the end after the served state is ready)."""
        rounds = []
        t_start = time.perf_counter()
        deadline = t_start + seconds
        i = 0
        while time.perf_counter() < deadline:
            rounds.append(await self.run(pattern[i % len(pattern)], keep=True))
            i += 1
        with _annotate("bench.drain"):
            self.drain()
        return rounds, t_start, time.perf_counter()
