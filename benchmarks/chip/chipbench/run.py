"""One run of one cell: set-up, the measured window, the check, the line.

Set-up makes the readings from the seed, builds the served session and
gateway from the configuration, and runs the traffic's ``setup`` rounds,
which fill state and warm every program shape the window uses.  The
window cycles the traffic's round kinds for ``seconds``; nothing in it
compiles (the count is printed).  After the window the device peak is
read, the ``after`` rounds run, the program's state is freed, and the
sampled answers are compared with the plain reference.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import gc
import math
import sys
import time
from typing import Optional

from . import compare, device, spec, tracing
from .compile_watch import BACKEND_COMPILE, CompileWatch
from .traffic import FleetDriver, make_pool, sampled_hosts


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What the end-to-end and per-layer readers read."""

    config: dict
    traffic: dict
    chips: int              # the cell's chips, as `BENCHMARK.json` gives them
    setup_s: float
    rounds: list            # window rounds (`chipbench.traffic.Round`)
    t_start: float
    t_end: float
    summary: Optional[tracing.Summary]
    peak: Optional[dict]    # the chip's row of the peaks table

    def of_kind(self, kind: str) -> list:
        return [r for r in self.rounds if r.kind == kind]


def configure_cache() -> None:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), every program cached
    however fast it compiled."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(config: dict):
    """The served path as the configuration states it: its ``session``
    object, where it has one, gives `FrameSession` further keywords."""
    from repro import FrameSession
    from repro.serving.gateway import GatewayConfig, StatsGateway

    session = FrameSession(d=config["metrics"], num_users=config["hosts"],
                           backend=config["backend"],
                           compensated=config["compensated"],
                           **config.get("session", {}))
    names = [spec.member(m["kind"]).declare(session, m["params"])
             for m in config["plan"]]
    return session, StatsGateway(session, GatewayConfig(**config["gateway"])), names


def _finite(x):
    return x if x is None or math.isfinite(x) else None


async def _drive(driver, traffic, seconds, trace, t_process, watch, gw, chips):
    await driver.run_phase(traffic["setup"])
    setup_s = time.perf_counter() - t_process
    before = (collections.Counter(watch.by_event), watch.seconds, dict(gw.counters))
    with tracing.capture(trace, chips) as captured:
        rounds, t_start, t_end = await driver.window(traffic["window"], seconds)
    events = watch.by_event - before[0]
    log(f"compiles in window: {events[BACKEND_COMPILE]} backend compiles; "
        f"{watch.seconds - before[1]:.6f} s in compile events {dict(events)}")
    programs = {k: gw.counters[k] - before[2].get(k, 0)
                for k in ("programs_ingest", "programs_finalize")}
    log(f"window: {len(rounds)} rounds in {t_end - t_start:.6f} s; "
        f"gateway programs {programs}")
    memory = device.memory_peak_bytes(chips)
    await driver.run_phase(traffic["after"], keep=True)
    return setup_s, rounds, t_start, t_end, captured.summary, memory


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, control: bool = False,
             require_chip: bool = True) -> dict:
    """Run ``cell`` once; returns the result line's object."""
    import jax

    dev = device.require_chips(cell.chips) if require_chip else device.describe()
    configure_cache()
    watch = CompileWatch()
    config, traffic = cell.config, cell.traffic
    pool = make_pool(config, traffic, seed)
    session, gw, names = build(config)
    driver = FleetDriver(
        gw, pool, sampled_hosts(config, traffic, seed), seed,
        drain=lambda: jax.block_until_ready(jax.tree.leaves(session.state_template())),
    )
    setup_s, rounds, t_start, t_end, summary, memory = asyncio.run(
        _drive(driver, traffic, seconds, trace, t_process, watch, gw, cell.chips))
    # the program's state goes before the reference runs
    driver.gw = None
    del gw, session
    gc.collect()

    peak = device.peaks(dev["kind"]) if dev["platform"] == "tpu" else None
    run = Run(config, traffic, cell.chips, setup_s, rounds, t_start, t_end,
              summary, peak)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        group = "metrics" if trace else "endtoend"
        value = spec.reader(group, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    found = compare.readings(config, pool, driver, names)
    correct, checks = compare.verdict(found, cell.limits)
    log("program readings: " + ", ".join(f"{k} {v!r}" for k, v in sorted(found.items())))
    if control:
        found = compare.readings(config, pool, driver, names, control=True)
        correct, checks = compare.verdict(found, cell.limits)
        log("control readings: " + ", ".join(f"{k} {v!r}" for k, v in sorted(found.items())))

    dev = dict(dev, memory_peak_bytes=memory)
    if trace and summary is not None and summary.window is not None:
        dev["busy_s"] = summary.busy_seconds()
        dev["window_s"] = summary.window[1] - summary.window[0]
    out = {
        "correct": bool(correct),
        "attempted": sum(r.requests for r in rounds),
        "failed": sum(r.failed for r in rounds),
        "metrics": metrics,
        "device": dev,
    }
    if trace and summary is not None and summary.window is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.items()}
    for key, c in checks.items():
        log(f"check {key} {c['value']!r} limit {c['limit']!r}")
    return out
