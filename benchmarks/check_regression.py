"""Perf-regression gate over the committed BENCH_*.json trajectories.

Diffs the working-tree benchmark JSONs (the ones `benchmarks.run` just
wrote) against the **baseline**: the blessed snapshot in
``benchmarks/baselines/<file>`` when one exists, else the version committed
at HEAD (``git show HEAD:<file>``).  FAILS — nonzero exit — when any named
entry slowed down by more than ``THRESHOLD`` (1.5×).  Speedups and new
entries pass; an entry present in the baseline but missing from the fresh
run fails (a silently dropped benchmark is how perf coverage rots).

Usage:
    PYTHONPATH=src python -m benchmarks.check_regression [--threshold 1.5]
    PYTHONPATH=src python -m benchmarks.check_regression --update-baselines

``--update-baselines`` blesses the current working-tree JSONs: they are
copied into ``benchmarks/baselines/`` (shown against the old baseline
first, never gated), and committing that directory pins them as the
reference for every later run.  Use it after an intentional perf trade-off
or a hardware change, not to silence a regression you have not read.

Meant to run right after ``python -m benchmarks.run`` in CI: the blessed
JSONs are the trajectory, the fresh ones are the candidate, and the gate
keeps a PR from landing a >1.5× slowdown on any tracked hot path.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

THRESHOLD = 1.5
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DIR = os.path.join(REPO_ROOT, "benchmarks", "baselines")

# Every tracked trajectory file; entries are matched by (name, backend).
BENCH_FILES = [
    "BENCH_backends.json",
    "BENCH_spectral.json",
    "BENCH_fused.json",
    "BENCH_megakernel.json",
    "BENCH_frame.json",
    "BENCH_streaming.json",
    "BENCH_chaos.json",
    "BENCH_forecast.json",
    "BENCH_integrity.json",
]


def discover_files() -> list:
    """The default ``--files`` set: the tracked list UNIONED with every
    ``BENCH_*.json`` found in the repo root or the baselines directory.

    The union is what lets a brand-new benchmark participate before anyone
    remembers to add it to ``BENCH_FILES``: a fresh working-tree JSON is
    picked up (and blessed by ``--update-baselines``), and a blessed file
    whose working-tree copy was not regenerated still gates."""
    found = set(BENCH_FILES)
    for path in glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json")):
        found.add(os.path.basename(path))
    if os.path.isdir(BASELINE_DIR):
        for fname in os.listdir(BASELINE_DIR):
            if fname.startswith("BENCH_") and fname.endswith(".json"):
                found.add(fname)
    return sorted(found)
# Timing rows with us_per_call below this are jitter, not signal — a 1.5×
# blowup of a 50µs dispatch round-trip is noise on shared CI hardware.
MIN_US = 1_000.0


def _entry_key(entry: dict) -> tuple:
    return (entry["name"], entry.get("backend", ""))


def _load_entries(payload: dict) -> dict:
    return {
        _entry_key(e): float(e["us_per_call"])
        for e in payload.get("results", [])
        if float(e.get("us_per_call", 0.0)) > 0.0
    }


def _committed(fname: str):
    try:
        blob = subprocess.run(
            ["git", "show", f"HEAD:{fname}"],
            cwd=REPO_ROOT,
            capture_output=True,
            check=True,
        ).stdout
    except (subprocess.CalledProcessError, FileNotFoundError):
        return None  # not committed yet — nothing to regress against
    try:
        return json.loads(blob)
    except ValueError:
        print(f"{fname}: HEAD-committed copy is not valid JSON — "
              "treating as no baseline", file=sys.stderr)
        return None


def _baseline(fname: str):
    """Baseline payload: the blessed benchmarks/baselines snapshot when one
    exists (and parses), the HEAD-committed file otherwise.  A torn or
    hand-mangled blessed file degrades to the committed copy with a warning
    rather than crashing the whole gate."""
    blessed = os.path.join(BASELINE_DIR, fname)
    if os.path.exists(blessed):
        try:
            with open(blessed) as f:
                return json.load(f)
        except (OSError, ValueError) as exc:
            print(f"{fname}: blessed baseline unreadable ({exc}) — "
                  "falling back to HEAD", file=sys.stderr)
    return _committed(fname)


def update_baselines(files) -> int:
    """Copy the working-tree BENCH files into benchmarks/baselines/."""
    os.makedirs(BASELINE_DIR, exist_ok=True)
    missing = []
    for fname in files:
        src = os.path.join(REPO_ROOT, fname)
        if not os.path.exists(src):
            missing.append(fname)
            continue
        shutil.copyfile(src, os.path.join(BASELINE_DIR, fname))
        print(f"blessed {fname} -> benchmarks/baselines/{fname}")
    if missing:
        print(f"not blessed (missing from working tree): {missing}",
              file=sys.stderr)
    return 0


def check_file(fname: str, threshold: float) -> list:
    """Returns a list of human-readable failure strings for one file."""
    path = os.path.join(REPO_ROOT, fname)
    base_payload = _baseline(fname)
    if not os.path.exists(path):
        if base_payload is None:
            # A bench that exists in neither place (e.g. freshly added to
            # BENCH_FILES before its first run) is a to-do, not a failure.
            print(f"{fname}: no working-tree run and no baseline — skipping "
                  "(run benchmarks, then --update-baselines to bless it)")
            return []
        return [f"{fname}: missing from working tree (benchmarks not run?)"]
    if base_payload is None:
        print(f"{fname}: no blessed or committed baseline — skipping "
              "(use --update-baselines to bless this run)")
        return []
    try:
        with open(path) as f:
            fresh_payload = json.load(f)
    except (OSError, ValueError) as exc:
        return [f"{fname}: working-tree copy unreadable ({exc})"]
    if fresh_payload.get("platform") != base_payload.get("platform"):
        # A TPU run vs a committed CPU baseline (or vice versa) is a
        # platform change, not a regression — only like-for-like gates.
        print(
            f"{fname}: platform changed "
            f"({base_payload.get('platform')} -> {fresh_payload.get('platform')})"
            " — skipping"
        )
        return []
    fresh = _load_entries(fresh_payload)
    base = _load_entries(base_payload)

    failures = []
    for key, base_us in sorted(base.items()):
        name = ":".join(k for k in key if k)
        if key not in fresh:
            failures.append(f"{fname}: entry {name!r} disappeared from the run")
            continue
        if base_us < MIN_US:
            continue
        ratio = fresh[key] / base_us
        status = "OK" if ratio <= threshold else "REGRESSION"
        print(
            f"{fname}: {name:<40s} {base_us:>12.1f}us -> {fresh[key]:>12.1f}us "
            f"({ratio:.2f}x) {status}"
        )
        if ratio > threshold:
            failures.append(
                f"{fname}: {name!r} slowed {ratio:.2f}x "
                f"({base_us:.0f}us -> {fresh[key]:.0f}us, limit {threshold}x)"
            )
    return failures


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--threshold", type=float, default=THRESHOLD)
    parser.add_argument(
        "--files", nargs="*", default=None,
        help="BENCH json filenames (repo-root relative) to check; default "
             "is the tracked list plus every BENCH_*.json discovered in "
             "the repo root or benchmarks/baselines/",
    )
    parser.add_argument(
        "--update-baselines", action="store_true",
        help="bless the working-tree JSONs as the new baseline "
             "(benchmarks/baselines/); shows diffs, never fails",
    )
    args = parser.parse_args(argv)
    if args.files is None:
        args.files = discover_files()

    if args.update_baselines:
        # Show the diff being blessed — including disappeared entries: a
        # benchmark silently baked out of the baseline is exactly the
        # coverage rot the gate exists to prevent.  Blessing proceeds (the
        # flag is for intentional changes) but never silently.
        warnings = []
        for fname in args.files:
            warnings.extend(check_file(fname, args.threshold))
        if warnings:
            print("\nBLESSING OVER THESE DIFFERENCES:", file=sys.stderr)
            for w in warnings:
                print(f"  {w}", file=sys.stderr)
        return update_baselines(args.files)

    failures = []
    for fname in args.files:
        failures.extend(check_file(fname, args.threshold))
    if failures:
        print("\nPERF REGRESSIONS:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("\nno perf regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
