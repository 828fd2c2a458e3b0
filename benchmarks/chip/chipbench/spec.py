"""Find everything of a cell by its name.

``BENCHMARK.json`` (at the root of the checkout) names the cells.  Each
configuration, traffic mix, limit file and metric reader is a file of its
own under ``benchmarks/chip``:

- ``configs/<config>.json``: the deployment (sizes, plan, guarantees);
  its optional ``session`` object holds further keyword arguments of
  `FrameSession` (such as ``num_shards``), passed beside ``d``,
  ``num_users``, ``backend`` and ``compensated`` (`chipbench.run.build`);
- ``traffic/<traffic>.json``: the mix, read by `chipbench.traffic`;
- ``limits/<workload>.json``: the limit of each number compared;
- ``endtoend/<metric>.py`` and ``metrics/<metric>.py``: one reader each,
  ``read(run) -> float | None``;
- ``chipbench/members/<kind>.py``: one statistic of a plan (how to declare
  it, its plain reference, its control, its numbers and its work).

A later cell or metric is added by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def member(kind: str) -> ModuleType:
    """The module of one plan statistic, ``chipbench/members/<kind>.py``."""
    return _load_module(
        os.path.join(BENCH_DIR, "chipbench", "members", f"{kind}.py"),
        f"chipbench_member_{kind}",
    )


def reader(group: str, name: str) -> ModuleType:
    """``endtoend/<name>.py`` or ``metrics/<name>.py``."""
    return _load_module(os.path.join(BENCH_DIR, group, f"{name}.py"),
                        f"chipbench_{group}_{name.replace('.', '_')}")


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, workload: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; BENCHMARK.json has "
                f"{sorted(cells)}"
            )
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _load_json(
            os.path.join(ROOT, configs[self.workload["config"]]["file"]))
        self.traffic = _load_json(
            os.path.join(BENCH_DIR, "traffic", f"{self.workload['traffic']}.json"))
        self.limits = _load_json(
            os.path.join(BENCH_DIR, "limits", f"{workload}.json"))["limits"]
        self.end_to_end = [m for m in bench["end_to_end"] if self._reports(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def load(workload: str, bench_path: str | None = None) -> Cell:
    bench = _load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    return Cell(bench, workload)
