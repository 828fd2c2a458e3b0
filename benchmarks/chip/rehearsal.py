"""Tiny cells for the CPU tests: the configuration's plan and traffic mix,
at a handful of hosts and channels, driven through the whole run without
the chip check and without the persistent compile cache."""
import time

from chipbench import run, spec

TINY_HOSTS, TINY_METRICS = 6, 4


def tiny_cell(workload: str, **config) -> spec.Cell:
    cell = spec.load(workload)
    series = cell.config["series"]
    series = dict(series, fields=[dict(series["fields"][0], count=TINY_METRICS)])
    cell.config = dict(cell.config, hosts=TINY_HOSTS, metrics=TINY_METRICS,
                       series=series, **config)
    cell.traffic = dict(cell.traffic, pool_rounds=3, sampled_hosts=3)
    return cell


def run_tiny(monkeypatch, workload: str, seed: int = 2**31 + 77,
             seconds: float = 0.3, control: bool = False, **config) -> dict:
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    return run.run_cell(tiny_cell(workload, **config), seed, seconds, False,
                        time.perf_counter(), control=control, require_chip=False)
