"""The program's own spans and device programs, read from the same trace.

The gateway opens ``repro.*`` spans inside its tick: ``repro.tick``
(a `jax.profiler.StepTraceAnnotation`) around the whole tick, and one
`jax.profiler.TraceAnnotation` for each phase inside it
(``repro.ingest.stack``, ``.h2d``, ``.dispatch``, ``.resolve``;
``repro.query.dispatch``, ``.fetch``, ``.resolve``).  They share the
profiler's clock with the benchmark's ``bench.*`` spans and the device.
Its device programs have stable names (``jit_scatter_update``,
``jit_gather_merge``, ``jit_finalize_batch``), which the chip writes on
each device plane's ``XLA Modules`` line, with a ``(<id>)`` suffix.

`reduce` reads everything `chipbench.tracing.reduce` reads, unchanged, and
keeps besides the ``repro.*`` host events and, for each chip of the cell,
the intervals of each ``XLA Modules`` event keyed by its module name.  A
`ProgramSummary` answers every question a `tracing.Summary` answers, adds
readers of the program's spans and modules, and gives each idle stretch
of the device to the innermost host span that covers it.  On a trace with
no ``repro.*`` span or no ``XLA Modules`` line (the program before it
had spans, or a CPU) those readers return ``None``.
"""
from __future__ import annotations

import bisect
import re
from typing import Optional

from . import tracing

PROGRAM_PREFIX = "repro."
MODULE_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_gather_merge(123)`` → ``jit_gather_merge``."""
    return _MODULE_ID.sub("", event_name)


class ProgramSummary(tracing.Summary):
    """A `tracing.Summary` with the program's spans and modules."""

    HOST_PREFIXES = (tracing.SPAN_PREFIX, PROGRAM_PREFIX)

    def __init__(self, spans: list, device_ops: dict, busy_by_device: dict,
                 chips: int, program_spans: list, modules: dict):
        super().__init__(spans, device_ops, busy_by_device, chips)
        self.program_spans = sorted(program_spans, key=lambda s: s.start)
        self._program_starts = [s.start for s in self.program_spans]
        self.modules = modules     # device -> module -> merged intervals
        self._all = sorted(self._inner + self.program_spans, key=lambda s: s.start)
        self._all_starts = [s.start for s in self._all]
        self._all_longest = max((s.seconds for s in self._all), default=0.0)

    # ---------------------------------------------------------- the rounds
    def _program_in(self, r, name: Optional[str] = None) -> list:
        """Program spans that start in round ``r`` (named ``name``)."""
        lo = bisect.bisect_left(self._program_starts, r.start)
        hi = bisect.bisect_left(self._program_starts, r.end)
        return [s for s in self.program_spans[lo:hi]
                if name is None or s.name == name]

    def _per_round(self, kind: str, name: str, value) -> Optional[float]:
        """Mean over the rounds of ``kind`` of the sum of ``value(span)``
        over the round's ``repro.<name>`` spans; ``None`` where no round
        of ``kind`` holds one."""
        rs = self.of_kind(kind)
        per_round = [[value(s) for s in self._program_in(r, PROGRAM_PREFIX + name)]
                     for r in rs]
        if not any(per_round):
            return None
        return sum(sum(v) for v in per_round) / len(rs)

    def program_span_mean(self, kind: str, name: str) -> Optional[float]:
        """Mean seconds per round of ``kind`` spent in ``repro.<name>``."""
        return self._per_round(kind, name, lambda s: s.seconds)

    def program_span_idle(self, kind: str, name: str) -> Optional[float]:
        """Mean seconds per round of ``kind`` spent in ``repro.<name>``
        while no operation runs on the device."""
        if not self.busy_by_device:
            return None
        return self._per_round(kind, name,
                               lambda s: s.seconds - self.busy(s.start, s.end))

    def tick_self(self, kind: str) -> Optional[float]:
        """Mean seconds per round of ``kind`` of ``repro.tick`` that no
        child span covers: the tick's length minus the union of the
        program spans inside it."""
        def own(tick):
            children = [(s.start, s.end) for s in self._program_in(tick)
                        if s is not tick]
            return tick.seconds - tracing.covered(tracing.union(children),
                                                  tick.start, tick.end)
        return self._per_round(kind, "tick", own)

    def module_busy(self, kind: str, module: str) -> Optional[float]:
        """Mean device busy seconds per round of ``kind`` in program
        ``module`` (its ``XLA Modules`` events), per chip of the cell;
        ``None`` where no chip ran it."""
        rs = self.of_kind(kind)
        runs = [m[module] for m in self.modules.values() if module in m]
        if not rs or not runs:
            return None
        return sum(tracing.covered(iv, r.start, r.end) for iv in runs for r in rs) \
            / (len(rs) * self.chips)

    # ------------------------------------------------------------ breakdown
    def split_gap(self, g0: float, g1: float, idle: dict) -> None:
        """Give each part of the idle stretch [g0, g1) to the innermost host
        span that covers it (the one that started last), so a gap inside
        ``bench.tick`` reads ``host in repro.query.fetch``.  The
        ``bench.*`` spans never nest within each other, so without program
        spans this is `tracing.Summary.split_gap`."""
        first = bisect.bisect_left(self._all_starts, g0 - self._all_longest)
        last = bisect.bisect_left(self._all_starts, g1)
        near = [s for s in self._all[first:last] if s.end > g0]
        cuts = sorted({g0, g1} | {t for s in near for t in (s.start, s.end)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            inner = None
            for s in near:   # sorted by start: the last cover is innermost
                if s.start <= a and s.end >= b:
                    inner = s
            name = inner.name if inner else "outside any span"
            idle[name] = idle.get(name, 0.0) + (b - a)


def reduce(profile, chips: int) -> ProgramSummary:
    """`tracing.reduce`, keeping besides the ``repro.*`` host events and the
    ``XLA Modules`` intervals of each chip of the cell."""
    base = tracing.reduce(profile, chips)
    program, modules = [], {}
    cell = tracing.chip_planes(chips)
    for plane in profile.planes:
        if plane.name.startswith(tracing.DEVICE_PLANE_PREFIX):
            if plane.name not in cell:
                continue
            by_module: dict = {}
            for line in plane.lines:
                if line.name != MODULE_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    by_module.setdefault(module_name(ev.name), []).append(
                        (s, s + ev.duration_ns * 1e-9))
            if by_module:
                modules[plane.name] = {m: tracing.union(iv)
                                       for m, iv in by_module.items()}
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        s = ev.start_ns * 1e-9
                        program.append(tracing.Span(
                            ev.name, s, s + ev.duration_ns * 1e-9, dict(ev.stats)))
    return ProgramSummary(base.spans, base.device_ops, base.busy_by_device,
                          chips, program, modules)
