"""The benchmark's arithmetic on the CPU: the trace reduction, the work
counts against the served state's real leaves, the peaks table, and the
shape of ``BENCHMARK.json``."""
import json
import os
import re
import types

import numpy as np
import pytest

from chipbench import device, spec, tracing, work


# ------------------------------------------------------------ intervals
def test_union_covered_and_gaps():
    merged = tracing.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert merged == [(0, 3), (5, 9)]
    assert tracing.covered(merged, 2, 6) == pytest.approx(2.0)
    assert tracing.gaps(merged, -1, 11) == [(-1, 0), (3, 5), (9, 11)]
    assert tracing.covered([], 0, 1) == 0.0


# ------------------------------------------------------- a synthetic trace
class _Event:
    def __init__(self, name, start_s, end_s, **stats):
        self.name = name
        self.start_ns = start_s * 1e9
        self.duration_ns = (end_s - start_s) * 1e9
        self.stats = stats


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _trace():
    """Two ingest rounds of 10 s on the host (submit 0–4, tick 4–9), then a
    drain to 22; device work 5–8 (round 0), 12–14 and 15–21 (round 1, the
    second spilling into the drain), plus an op outside the window."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Event("bench.round", 0, 10, kind="ingest"),
        _Event("bench.submit", 0, 4),
        _Event("bench.tick", 4, 9),
        _Event("bench.round", 10, 20, kind="ingest"),
        _Event("bench.submit", 10, 13),
        _Event("bench.tick", 13, 19),
        _Event("bench.drain", 20, 22),
        _Event("PjitFunction(f)", 4, 5),
    ])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Event("jit_scatter", 5, 8)]),
        _Line("XLA Ops", [
            _Event("fusion.1", 5, 8, hlo_module="jit_scatter"),
            _Event("fusion.1", 12, 14, hlo_module="jit_scatter"),
            _Event("copy.2", 15, 21, hlo_module="jit_scatter"),
            _Event("copy.2", 30, 31, hlo_module="jit_other"),
        ]),
    ])
    idle = _Plane("/device:TPU:1", [_Line("XLA Ops", [])])
    return _Profile([host, dev, idle])


def test_reduce_rounds_busy_and_idle():
    s = tracing.reduce(_trace(), chips=1)
    assert [r.kind for r in s.rounds] == ["ingest", "ingest"]
    assert s.window == (0.0, 22.0)
    assert list(s.busy_by_device) == ["/device:TPU:0"]   # TPU:1 did nothing
    # round 0 owns 0–10 (3 s busy); round 1 owns 10–22 (2 + 6 s)
    assert s.round_busy("ingest") == pytest.approx((3 + 8) / 2)
    assert s.idle_share("ingest") == pytest.approx(1 - 11 / 22)
    assert s.span_mean("ingest", "submit") == pytest.approx(3.5)
    assert s.span_mean("ingest", "tick") == pytest.approx(5.5)
    assert s.busy_seconds() == pytest.approx(11.0)
    assert s.round_busy("query") is None and s.span_mean("query", "tick") is None


def test_breakdown_names_ops_and_what_the_host_did_in_each_gap():
    b = tracing.reduce(_trace(), chips=1).breakdown()
    assert b["device_ops"][0] == ["jit_scatter/copy.2", pytest.approx(6.0)]
    assert b["device_ops"][1] == ["jit_scatter/fusion.1", pytest.approx(5.0)]
    # gaps 0–5, 8–12, 14–15 and 21–22, split by the host span they overlap
    assert dict(b["idle_gaps"]) == {
        "host in bench.submit": pytest.approx(4 + 2),
        "host in bench.tick": pytest.approx(1 + 1 + 1),
        "host in bench.drain": pytest.approx(1),
        "outside any span": pytest.approx(1),            # 9–10
    }


# ------------------------------------------------------- a cell of N chips
def _four_chip_trace(working):
    """`_trace`'s host on a host of four chips: each chip in ``working``
    runs TPU:0's operations of `_trace`, the others none."""
    host, work_plane = _trace().planes[:2]
    return _Profile([host] + [
        _Plane(f"/device:TPU:{i}",
               work_plane.lines if i in working else [_Line("XLA Ops", [])])
        for i in range(4)])


@pytest.mark.parametrize("chips", [1, 4])
def test_a_cell_reads_busy_and_idle_per_chip(chips):
    """Only TPU:0 works on a host of four.  Read as a one-chip cell, the
    numbers of `test_reduce_rounds_busy_and_idle`; read as a four-chip
    cell, each idle chip counts as idle for the whole window."""
    s = tracing.reduce(_four_chip_trace(working=(0,)), chips=chips)
    assert list(s.busy_by_device) == ["/device:TPU:0"]
    assert s.round_busy("ingest") == pytest.approx((3 + 8) / 2 / chips)
    assert s.idle_share("ingest") == pytest.approx(1 - 11 / (22 * chips))
    assert s.busy_seconds() == pytest.approx(11.0 / chips)
    if chips == 4:
        assert s.idle_share("ingest") >= 0.75
    # TPU:0's gaps as in test_breakdown_...; an idle chip's whole window
    # splits into submit 0–4 and 10–13, tick 4–9 and 13–19, drain 20–22,
    # and 9–10 and 19–20 outside any span
    n = chips - 1
    idle = dict(s.breakdown()["idle_gaps"])
    assert idle == {
        "host in bench.submit": pytest.approx(6 + 7 * n),
        "host in bench.tick": pytest.approx(3 + 11 * n),
        "host in bench.drain": pytest.approx(1 + 2 * n),
        "outside any span": pytest.approx(1 + 2 * n),
    }
    assert sum(idle.values()) == pytest.approx(11 + 22 * n)   # idle chip-seconds


def test_chips_outside_the_cell_are_ignored():
    """A one-chip cell on a host of four reads only TPU:0, whatever the
    other chips run."""
    s = tracing.reduce(_four_chip_trace(working=(0, 2, 3)), chips=1)
    old = tracing.reduce(_trace(), chips=1)
    assert list(s.busy_by_device) == ["/device:TPU:0"]
    assert s.device_ops == old.device_ops
    assert s.round_busy("ingest") == old.round_busy("ingest")
    assert s.breakdown() == old.breakdown()


def _roofline(summary):
    run = types.SimpleNamespace(summary=summary, config=_config(), chips=summary.chips,
                                peak=device.peaks("TPU v5 lite"))
    return spec.reader("metrics", "chunk_update_roofline").read(run)


def test_roofline_share_is_taken_at_the_cells_combined_peak():
    """Four chips that each work as one chip did read a quarter of its
    share; one chip of four doing all the work reads what one chip did."""
    every = _four_chip_trace(working=range(4))
    one = _roofline(tracing.reduce(every, chips=1))
    assert _roofline(tracing.reduce(every, chips=4)) == pytest.approx(one / 4)
    only_first = _four_chip_trace(working=(0,))
    assert _roofline(tracing.reduce(only_first, chips=4)) == pytest.approx(one)


@pytest.mark.parametrize("split", [(1, 0, 0, 0), (0.25, 0.25, 0.25, 0.25),
                                   (0.7, 0.1, 0.1, 0.1), (0.4, 0.4, 0.2, 0)])
@pytest.mark.parametrize("excess", [1.0, 1.5])
def test_roofline_share_cannot_pass_100(split, excess):
    """One ingest round whose busy chip-seconds, split across four chips in
    any way, are ``excess`` times one chip's least time: the share reads
    100 / ``excess``, never above 100."""
    config = _config()
    least, _ = work.roofline_seconds(
        work.chunk_update(config, config["hosts"], config["chunk"]),
        device.peaks("TPU v5 lite"))
    host = _Plane("/host:CPU", [_Line("python", [
        _Event("bench.round", 0, 1, kind="ingest"),
        _Event("bench.drain", 1, 1.1),
    ])])
    chips = [_Plane(f"/device:TPU:{i}", [_Line("XLA Ops", [
        _Event("fusion", 0, share * excess * least)] if share else [])])
        for i, share in enumerate(split)]
    reading = _roofline(tracing.reduce(_Profile([host] + chips), chips=4))
    assert reading == pytest.approx(100 / excess)
    assert reading <= 100 + 1e-9


def test_reduce_reads_a_real_cpu_trace(tmp_path):
    """The capture path end to end on the CPU: host spans come back from the
    profiler; a CPU has no TPU plane, so no device time is read."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with tracing.capture(True, chips=1) as cap:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.round", kind="query"):
                with jax.profiler.TraceAnnotation("bench.tick"):
                    f(jnp.ones(8)).block_until_ready()
    s = cap.summary
    assert [r.kind for r in s.rounds] == ["query", "query"]
    assert s.span_mean("query", "tick") > 0
    assert s.busy_by_device == {} and s.round_busy("query") is None


# -------------------------------------------------------------- work counts
def _config(name="tsbs-devops-4000"):
    return spec.load("devops-ingest").config if name == "tsbs-devops-4000" \
        else spec.load("cpuonly-ingest").config


@pytest.mark.parametrize("name", ["tsbs-devops-4000", "tsbs-cpuonly-4000"])
def test_state_bytes_match_the_served_state(name):
    """The byte count from shapes equals the leaves of the live session's
    state at the configuration's widths, on a tiny fleet."""
    import jax

    from chipbench.run import build

    config = dict(_config(name), hosts=3)
    session, _gw, _names = build(config)
    session.ingest(np.arange(3), np.zeros((3, config["chunk"], config["metrics"]),
                                          np.float32))
    lanes = [g["lanes"] for g in session.state_template().values()]
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(lanes))
    assert total == 3 * work.state_bytes(config)


def test_chunk_update_work_at_devops_widths():
    config = _config()
    w = work.chunk_update(config, 4000, 64)
    d, H = 100, 8
    lag = 2.0 * 4000 * 64 * d * d * (H + 1)
    assert lag <= w["flops"] <= 1.05 * lag        # lag products dominate
    state = work.state_bytes(config)
    # lag 9·100², moments 2·100+1, PSD 33·100+1, each with its error
    # companion; head and tail 2·63·100, Σx 100, length and start
    stats = 9 * d * d + 2 * d + 1 + 33 * d + 1
    assert config["compensated"]
    assert state == 4 * (2 * stats + 2 * 63 * d + d) + 8
    assert w["bytes"] == 4000 * (64 * d * 4 + 2 * state)
    t, bound = work.roofline_seconds(w, device.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(w["bytes"] / 819e9)


def test_peaks_table_knows_v5e_and_refuses_others():
    p = device.peaks("TPU v5 lite")
    assert (p["flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


# ------------------------------------------------------- BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers_seen = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "metrics", m["name"] + ".py"))
        layers_seen.setdefault(m["layer"], m["layer"])
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "endtoend", m["name"] + ".py"))
        assert 0 < m["bound"] <= 0.25
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = spec.load(w["name"])
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        for kind in (m["kind"] for m in cell.config["plan"]):
            spec.member(kind)
    cells = len(bench["workloads"])
    assert 1 <= bench["run_seconds"] <= 51
    # a full check of 24 cells fits: 2 + 14·24 runs, 24·180 s to compile
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert cells <= 24
