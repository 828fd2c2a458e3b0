"""Tenant sharding: how far apart the cell's chips finish an ingest round,
in ms.  In each ingest round every chip's busy time ends with the last
operation it ran in the round; the stagger is the latest of those ends
less the earliest, and the reading is its mean over the rounds in which
every chip of the cell was busy.  Near 0 when one program a tick drives
all chips at once; the time between the host's feeds of the chips when it
feeds them one after another."""
import bisect

from chipbench import tracing


def _last_end(merged, lo, hi):
    """End of the last busy interval in [lo, hi) of disjoint sorted
    intervals, cut at hi."""
    i = bisect.bisect_left(merged, (hi, hi)) - 1
    return min(merged[i][1], hi)


def read(run):
    s = run.summary
    if s is None or run.chips < 2:
        return None
    planes = [s.busy_by_device.get(p) for p in tracing.chip_planes(run.chips)]
    staggers = []
    for r in s.of_kind("ingest"):
        if not all(m and tracing.covered(m, r.start, r.end) > 0 for m in planes):
            continue
        ends = [_last_end(m, r.start, r.end) for m in planes]
        staggers.append(max(ends) - min(ends))
    return 1e3 * sum(staggers) / len(staggers) if staggers else None
