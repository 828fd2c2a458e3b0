"""Profiler capture and the reduction from a trace to per-layer numbers.

The benchmark's own host spans (``bench.round`` around each round, with
``bench.submit``, ``bench.tick``, ``bench.answers`` and ``bench.drain``
inside) are `jax.profiler.TraceAnnotation`s, so they land in the same trace
as the device's operations and on the same clock.

A cell of N chips reads the planes ``/device:TPU:0`` … ``N−1``; planes of
other chips on the host are ignored.  A chip's busy time is the union of
the intervals in which an operation ran on it (its ``XLA Ops`` line, or
``XLA Modules`` where the plane has no op line).  Busy and idle time are
per chip of the cell: the sum over its N chips over N, a chip with no
events counting as idle for the whole window.  Device time is given to
rounds by where it lies, not by program name: round i owns the device
time between its start and the next round's start (the last round up to
the end of the drain), since the gateway dispatches without waiting.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "bench."


def chip_planes(chips: int) -> tuple:
    """The device planes of a cell of ``chips`` chips."""
    return tuple(f"{DEVICE_PLANE_PREFIX}{i}" for i in range(chips))


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[tuple]) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: list, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by disjoint sorted intervals."""
    total = 0.0
    # the first interval that can reach lo: ends are sorted like starts
    for s, e in merged[max(bisect.bisect_right(merged, (lo, lo)) - 1, 0):]:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def gaps(merged: list, lo: float, hi: float) -> list:
    """The uncovered parts of [lo, hi) as (start, end)."""
    out, cur = [], lo
    for s, e in merged:
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# ---------------------------------------------------------------- the trace
@dataclasses.dataclass
class Span:
    name: str
    start: float   # seconds on the trace clock
    end: float
    args: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceRound:
    kind: str
    start: float
    end: float          # start of the next round, or the drain's end
    spans: dict         # inner span name -> seconds (summed)


class Summary:
    """What the per-layer readers read: rounds, host spans, device busy
    per chip of a cell of ``chips`` chips."""

    HOST_PREFIXES = (SPAN_PREFIX,)   # spans an idle stretch is given to

    def __init__(self, spans: list, device_ops: dict, busy_by_device: dict,
                 chips: int):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.device_ops = device_ops              # (module, op) -> seconds
        self.busy_by_device = busy_by_device      # device -> merged intervals
        self.chips = chips
        rounds = [s for s in self.spans if s.name == SPAN_PREFIX + "round"]
        drains = [s for s in self.spans if s.name == SPAN_PREFIX + "drain"]
        self._inner = [s for s in self.spans if s.name != SPAN_PREFIX + "round"]
        self._inner_starts = [s.start for s in self._inner]
        self._longest = max((s.seconds for s in self._inner), default=0.0)
        self.rounds: list = []
        for i, r in enumerate(rounds):
            if i + 1 < len(rounds):
                end = rounds[i + 1].start
            else:
                after = [d.end for d in drains if d.start >= r.end]
                end = min(after) if after else r.end
            inner: dict = {}
            first = bisect.bisect_left(self._inner_starts, r.start)
            last = bisect.bisect_right(self._inner_starts, r.end)
            for s in self._inner[first:last]:
                if s.end <= r.end:
                    inner[s.name] = inner.get(s.name, 0.0) + s.seconds
            self.rounds.append(TraceRound(r.args.get("kind", ""), r.start, end, inner))
        self.window = ((self.rounds[0].start, self.rounds[-1].end)
                       if self.rounds else None)

    def busy(self, lo: float, hi: float) -> float:
        """Device busy seconds in [lo, hi) per chip of the cell."""
        return sum(covered(m, lo, hi) for m in self.busy_by_device.values()) \
            / self.chips
    def of_kind(self, kind: str) -> list:
        return [r for r in self.rounds if r.kind == kind]

    def round_busy(self, kind: str) -> Optional[float]:
        """Mean device busy seconds of a round of ``kind``."""
        rs = self.of_kind(kind)
        if not rs or not self.busy_by_device:
            return None
        return sum(self.busy(r.start, r.end) for r in rs) / len(rs)

    def idle_share(self, kind: str) -> Optional[float]:
        rs = self.of_kind(kind)
        if not rs or not self.busy_by_device:
            return None
        length = sum(r.end - r.start for r in rs)
        return 1.0 - sum(self.busy(r.start, r.end) for r in rs) / length

    def span_mean(self, kind: str, name: str) -> Optional[float]:
        """Mean seconds per round of ``kind`` spent in span ``name``."""
        rs = self.of_kind(kind)
        if not rs:
            return None
        return sum(r.spans.get(SPAN_PREFIX + name, 0.0) for r in rs) / len(rs)

    def busy_seconds(self) -> float:
        lo, hi = self.window
        return self.busy(lo, hi)

    def split_gap(self, g0: float, g1: float, idle: dict) -> None:
        """Add the idle stretch [g0, g1) to ``idle`` by the host span it
        overlaps (the rest is "outside any span")."""
        rest = g1 - g0
        # spans that can overlap the gap start within the longest before it
        first = bisect.bisect_left(self._inner_starts, g0 - self._longest)
        last = bisect.bisect_left(self._inner_starts, g1)
        for s in self._inner[first:last]:
            overlap = min(s.end, g1) - max(s.start, g0)
            if overlap > 0:
                idle[s.name] = idle.get(s.name, 0.0) + overlap
                rest -= overlap
        if rest > 0:
            idle["outside any span"] = idle.get("outside any span", 0.0) + rest

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the idle
        chip-seconds of the window, summed over the cell's chips and split
        by `split_gap`."""
        ops = sorted(self.device_ops.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.window
        idle: dict = {}
        for plane in chip_planes(self.chips):   # a chip with no events: all idle
            for g0, g1 in gaps(self.busy_by_device.get(plane, []), lo, hi):
                self.split_gap(g0, g1, idle)
        gap_list = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[f"{m}/{o}" if m else o, s] for (m, o), s in ops],
            "idle_gaps": [
                [f"host in {n}" if n.startswith(self.HOST_PREFIXES) else n, s]
                for n, s in gap_list],
        }


def reduce(profile, chips: int) -> Summary:
    """Reduce a `jax.profiler.ProfileData` (or anything with its
    ``planes`` → ``lines`` → ``events`` shape) to the `Summary` of a cell
    of ``chips`` chips."""
    spans, device_ops, busy = [], {}, {}
    cell = chip_planes(chips)
    for plane in profile.planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if plane.name not in cell:
                continue
            by_name = {line.name: line for line in lines}
            line = next((by_name[n] for n in OP_LINES if n in by_name), None)
            if line is None:
                continue
            intervals = []
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                intervals.append((s, e))
                stats = dict(ev.stats)
                key = (str(stats.get("hlo_module", "")), ev.name)
                device_ops[key] = device_ops.get(key, 0.0) + (e - s)
            if intervals:   # a chip the run never used has no events
                busy[plane.name] = union(intervals)
        else:
            for line in lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append(Span(ev.name, s, s + ev.duration_ns * 1e-9,
                                          dict(ev.stats)))
    return Summary(spans, device_ops, busy, chips)


@contextlib.contextmanager
def capture(enabled: bool, chips: int):
    """Profile the body when ``enabled``; yields a holder whose ``summary``
    (of a cell of ``chips`` chips) is set once the body has run.  The trace
    goes to a temporary directory that is removed after it has been read."""
    holder = type("Captured", (), {"summary": None})()
    if not enabled:
        yield holder
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if files:
            holder.summary = reduce(jax.profiler.ProfileData.from_file(max(files)),
                                    chips)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
