"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmark contract).

Two kinds of modules run here:

* **Trajectory benches** — emit a ``BENCH_<name>.json`` at the repo root
  so the perf trajectory populates per commit, and
  ``python -m benchmarks.check_regression`` diffs them against the
  committed baselines (fails on >1.5× slowdowns; re-bless with
  ``--update-baselines`` after an intentional trade-off):
  ``bench_backends`` (kernel-backend shootout), ``bench_spectral``
  (spectral primitive + fused Welch), ``bench_fused`` (N-statistic
  plans), ``bench_megakernel`` (persistent fused-plan kernel),
  ``bench_frame`` (SeriesFrame session API), ``bench_streaming``
  (streaming monoid ingest), ``bench_chaos`` (fault-injection overhead +
  breaker recovery), ``bench_forecast``
  (served forecasts/sec + accuracy-vs-horizon), and ``bench_integrity``
  (compensated-accumulation drift + ingest-sentinel tick overhead).

* **Standalone paper-figure benches** — CSV rows only, NO JSON: they
  reproduce a specific paper table/figure or answer a one-off design
  question, and their numbers are workload narratives rather than
  regression surfaces (several sweep sizes/shapes, so a single
  us_per_call baseline would be meaningless): ``bench_autocov``
  (Fig. 2 / Fig. 9), ``bench_overlap_scaling`` (Fig. 4), ``bench_mle``
  (§5 / §7.2 Z-estimators), ``bench_spatial`` (§6 banded high-d),
  ``bench_graph`` (§11 / Fig. 8), ``bench_accuracy`` (§2 1/√N
  convergence — a statistical check, not a timing), ``bench_halo``
  (beyond-paper halo exchange vs replication study), and ``bench_lm``
  (framework micro-benchmarks).
"""
from __future__ import annotations

import sys
import traceback

MODULES = [
    "bench_autocov",        # paper Fig. 2 (+ Fig. 9 kernel check)
    "bench_backends",       # compute-registry shootout → BENCH_backends.json
    "bench_spectral",       # spectral primitive + fused Welch → BENCH_spectral.json
    "bench_fused",          # fused N-statistic plans → BENCH_fused.json
    "bench_megakernel",     # fused-plan megakernel → BENCH_megakernel.json
    "bench_frame",          # SeriesFrame session API → BENCH_frame.json
    "bench_streaming",      # streaming monoid → BENCH_streaming.json
    "bench_chaos",          # fault-injection overhead + breaker recovery → BENCH_chaos.json
    "bench_forecast",       # served forecasts + anomaly scoring → BENCH_forecast.json
    "bench_integrity",      # compensated drift + ingest sentinel → BENCH_integrity.json
    "bench_overlap_scaling",  # paper Fig. 4
    "bench_mle",            # paper §5 / §7.2 Z-estimators
    "bench_spatial",        # paper §6 banded high-d
    "bench_graph",          # paper §11 / Fig. 8 graphs
    "bench_accuracy",       # paper §2 1/√N convergence
    "bench_halo",           # beyond-paper halo exchange vs replication
    "bench_lm",             # framework micro-benchmarks
]


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod in MODULES:
        try:
            m = __import__(f"benchmarks.{mod}", fromlist=["run"])
            m.run()
        except Exception:
            failures.append(mod)
            print(f"{mod},0.0,ERROR")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
