"""The reduction of the program's own spans and modules (`chipbench.
program_spans`) and the per-layer readers built on it, on the CPU: a
synthetic trace in the chip's form, the benchmark's own synthetic trace
(which must read as before), and a real CPU trace of a gateway tick."""
import asyncio
import glob
import json
import os
import types

import numpy as np
import pytest

from chipbench import device, program_spans, spec, tracing
from test_chipbench_reduce import _Event, _Line, _Plane, _Profile, _trace

SPAN_METRICS = ["stack_ms.ingest", "h2d_ms.ingest", "dispatch_ms.ingest",
                "resolve_ms.ingest", "tick_self_ms.ingest", "dispatch_ms.query",
                "fetch_ms.query", "resolve_ms.query", "tick_self_ms.query"]
DEVICE_METRICS = ["result_transfer_ms.query", "gather_device_ms.query",
                  "finalize_device_ms.query"]


def _read(summary, name, config=None):
    run = types.SimpleNamespace(summary=summary, config=config, chips=summary.chips,
                                peak=device.peaks("TPU v5 lite"))
    return spec.reader("metrics", name).read(run)


def _chip_trace():
    """An ingest round (0–10 s) and a query round (10–20 s), then a drain to
    21, as the chip writes them: ``XLA Ops`` events carry no module stat,
    ``XLA Modules`` events are named ``<module>(<id>)``.  The device runs
    the scatter 5.5–9.5, the gather 12.2–13.2 and the finalize 13.2–14.7."""
    host = _Plane("/host:CPU", [_Line("python3", [
        _Event("bench.round", 0, 10, kind="ingest"),
        _Event("bench.submit", 0, 2),
        _Event("bench.tick", 2, 9),
        _Event("repro.tick", 2.5, 8.5, step_num=7, _r=1),
        _Event("repro.ingest.stack", 3, 4, rows=4000, length=64, bytes=102400000),
        _Event("repro.ingest.h2d", 4, 5, bytes=102400000),
        _Event("repro.ingest.dispatch", 5, 7, rows=4000),
        _Event("repro.ingest.resolve", 7.5, 8, n=4000),
        _Event("bench.round", 10, 20, kind="query"),
        _Event("bench.submit", 10, 11),
        _Event("bench.tick", 11, 19),
        _Event("repro.tick", 11.5, 18.5, step_num=8, _r=1),
        _Event("repro.query.dispatch", 12, 12.5, tenants=4000),
        _Event("repro.query.fetch", 12.5, 17, bytes=1600000000),
        _Event("repro.query.resolve", 17, 18, n=4000),
        _Event("bench.drain", 20, 21),
    ])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [
            _Event("jit_scatter_update(7)", 5.5, 9.5),
            _Event("jit_gather_merge(12)", 12.2, 13.2),
            _Event("jit_finalize_batch(13)", 13.2, 14.7),
        ]),
        _Line("XLA Ops", [
            _Event("fusion.43", 5.5, 9.5),
            _Event("fusion.1", 12.2, 13.2),
            _Event("copy.2", 13.2, 14.7),
        ]),
    ])
    return _Profile([host, dev, _Plane("/device:TPU:1", [_Line("XLA Ops", [])])])


def test_module_names_drop_the_chips_id_suffix():
    assert program_spans.module_name("jit_gather_merge(12)") == "jit_gather_merge"
    assert program_spans.module_name("jit_finalize_batch") == "jit_finalize_batch"


@pytest.mark.parametrize("name, want", [
    ("stack_ms.ingest", 1000.0),
    ("h2d_ms.ingest", 1000.0),
    ("dispatch_ms.ingest", 2000.0),
    ("resolve_ms.ingest", 500.0),
    ("tick_self_ms.ingest", 6000.0 - 4500.0),   # 2.5–8.5 less its children
    ("dispatch_ms.query", 500.0),
    ("fetch_ms.query", 4500.0),
    ("result_transfer_ms.query", 4500.0 - 2200.0),  # busy 12.5–14.7 inside
    ("resolve_ms.query", 1000.0),
    ("tick_self_ms.query", 7000.0 - 6000.0),
    ("gather_device_ms.query", 1000.0),
    ("finalize_device_ms.query", 1500.0),
])
def test_each_new_metric_reads_the_chip_form_trace(name, want):
    s = program_spans.reduce(_chip_trace(), chips=1)
    assert _read(s, name) == pytest.approx(want)


def test_chip_form_trace_keeps_the_existing_readings():
    s = program_spans.reduce(_chip_trace(), chips=1)
    assert [r.kind for r in s.rounds] == ["ingest", "query"]
    assert _read(s, "ingest_device_ms") == pytest.approx(4000.0)
    # the two read-path programs are the query round's device time
    assert _read(s, "query_device_ms") == pytest.approx(
        _read(s, "gather_device_ms.query") + _read(s, "finalize_device_ms.query"))
    assert _read(s, "tick_host_ms.query") == pytest.approx(8000.0)
    # op names stay as the chip gives them (no module stat)
    assert dict(map(tuple, s.breakdown()["device_ops"])) == pytest.approx(
        {"fusion.43": 4.0, "copy.2": 1.5, "fusion.1": 1.0})


def test_module_time_is_per_chip_of_the_cell():
    """Only TPU:0 runs the programs: read as a four-chip cell, each device
    time is a quarter of the one-chip reading."""
    one = program_spans.reduce(_chip_trace(), chips=1)
    four = program_spans.reduce(_chip_trace(), chips=4)
    for name in ("gather_device_ms.query", "finalize_device_ms.query",
                 "query_device_ms", "ingest_device_ms"):
        assert _read(four, name) == pytest.approx(_read(one, name) / 4), name


def test_idle_gaps_go_to_the_innermost_span():
    b = program_spans.reduce(_chip_trace(), chips=1).breakdown(top=20)
    # gaps 0–5.5, 9.5–12.2 and 14.7–21
    assert dict(b["idle_gaps"]) == pytest.approx({
        "host in bench.submit": 2 + 1,
        "host in bench.tick": 0.5 + 0.5 + 0.5,
        "host in repro.tick": 0.5 + 0.5 + 0.5,
        "host in repro.ingest.stack": 1,
        "host in repro.ingest.h2d": 1,
        "host in repro.ingest.dispatch": 0.5,
        "host in repro.query.dispatch": 0.2,
        "host in repro.query.fetch": 2.3,
        "host in repro.query.resolve": 1,
        "host in bench.drain": 1,
        "outside any span": 0.5 + 1,                   # 9.5–10, 19–20
    })
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(5.5 + 2.7 + 6.3)


def _per_layer_names():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


def test_a_trace_without_program_spans_reads_as_before():
    """The benchmark's own synthetic trace: the same rounds, breakdown and
    per-layer readings as `tracing.reduce` gives, and no new reading."""
    old = tracing.reduce(_trace(), chips=1)
    new = program_spans.reduce(_trace(), chips=1)
    assert new.rounds == old.rounds and new.window == old.window
    b_old, b_new = old.breakdown(), new.breakdown()
    assert b_new["device_ops"] == b_old["device_ops"]
    assert [n for n, _ in b_new["idle_gaps"]] == [n for n, _ in b_old["idle_gaps"]]
    assert [v for _, v in b_new["idle_gaps"]] == pytest.approx(
        [v for _, v in b_old["idle_gaps"]])
    config = spec.load("devops-ingest").config
    for name in _per_layer_names():
        assert _read(new, name, config) == _read(old, name, config), name
    for name in SPAN_METRICS + DEVICE_METRICS:
        assert _read(new, name) is None and _read(old, name) is None, name


def test_span_metrics_read_a_real_gateway_tick_on_the_cpu(tmp_path):
    """A real trace of a small gateway's ingest and query ticks inside the
    benchmark's spans: the span readers give numbers; a CPU has no TPU
    plane, so the readers joined with the device give None."""
    import jax

    from repro import FrameSession
    from repro.serving.gateway import StatsGateway

    n, d = 4, 3
    session = FrameSession(d=d, num_users=n, backend="jnp")
    session.autocovariance(2)
    gw = StatsGateway(session)
    chunk = np.ones((8, d), np.float32)

    async def rounds():
        for kind in ("ingest", "query"):
            with jax.profiler.TraceAnnotation("bench.round", kind=kind):
                futs = [gw.submit_ingest(u, chunk) if kind == "ingest"
                        else gw.submit_query(u) for u in range(n)]
                with jax.profiler.TraceAnnotation("bench.tick"):
                    await gw.tick()
                await asyncio.gather(*futs)

    asyncio.run(rounds())                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(rounds())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    s = program_spans.reduce(jax.profiler.ProfileData.from_file(path), chips=1)
    assert [r.kind for r in s.rounds] == ["ingest", "query"]
    for name in SPAN_METRICS:
        assert _read(s, name) > 0, name
    for name in DEVICE_METRICS:
        assert _read(s, name) is None, name
    # the phases lie inside the tick, and the tick inside bench.tick
    tick = _read(s, "tick_host_ms.ingest")
    phases = sum(_read(s, m) for m in SPAN_METRICS[:5])
    assert phases <= tick
