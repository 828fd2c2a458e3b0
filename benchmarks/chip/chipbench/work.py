"""The least work of a plan's chunk update, counted from the shapes.

A tenant's state is the fused plan's `PartialState`: every member's
statistic slot, plus what the shared streaming engine carries — the sample
sum (d), the first and last ``carry = W − 1`` samples (head and tail, each
carry × d, W the widest member window), a length and a start index.  A
compensated configuration carries a Neumaier error companion of every
statistic slot beside it.

One ingest round updates ``hosts`` tenants with a ``rows``-row chunk each.
Its least traffic to device memory is the chunks read once and each
touched tenant's state read once and written once; its least operations
are the members' own (`chipbench.members`).  The roofline time is the
larger of operations over peak FLOP/s and bytes over peak bytes/s.
"""
from __future__ import annotations

from . import spec

FLOAT_BYTES = 4
INT_BYTES = 4


def carry(config: dict) -> int:
    return max(spec.member(m["kind"]).window(m["params"]) for m in config["plan"]) - 1


def state_bytes(config: dict) -> int:
    """Bytes of one tenant's served state."""
    d = config["metrics"]
    stats = sum(spec.member(m["kind"]).stat_floats(m["params"], d)
                for m in config["plan"])
    floats = d + 2 * carry(config) * d + stats * (2 if config["compensated"] else 1)
    return floats * FLOAT_BYTES + 2 * INT_BYTES  # + length, t0


def chunk_update(config: dict, hosts: int, rows: int) -> dict:
    """FLOPs and bytes of one round in which ``hosts`` tenants each absorb
    a ``rows``-row chunk."""
    d = config["metrics"]
    flops = hosts * sum(spec.member(m["kind"]).flops(m["params"], d, rows)
                        for m in config["plan"])
    moved = hosts * (rows * d * FLOAT_BYTES + 2 * state_bytes(config))
    return {"flops": float(flops), "bytes": float(moved)}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time for ``work`` on a chip with ``peak``, and which
    bound sets it (``"flops"`` or ``"bytes"``)."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
