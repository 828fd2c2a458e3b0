"""The chip a run measures: which one, its published peaks, its memory.

The measurement path needs a TPU: `require_chips` exits (no result line)
when JAX finds another platform or fewer chips than the cell asks for.
Peaks come from ``peaks.json``, keyed by ``device_kind``; a kind missing
from the table is an error, never a default.
"""
from __future__ import annotations

import json
import os

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(kind: str) -> dict:
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(
            f"device kind {kind!r} is not in the peaks table {PEAKS_PATH} "
            f"(known: {sorted(table)})"
        )
    return table[kind]


def require_chips(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the TPU this run uses; exits
    with an error when JAX finds no TPU or fewer than ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: JAX found no TPU (devices[0] is {devices[0].platform!r}); "
            "the benchmark measures on the chip only"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    peaks(devices[0].device_kind)  # an unknown kind fails before any work
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def describe() -> dict:
    """Whatever JAX runs on, for runs that skip the chip check (tests)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int | None:
    """The peak bytes in use on the fullest of the cell's chips, where the
    backend reports it."""
    import jax

    peaks_seen = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_seen) if peaks_seen else None
