"""Kernels: the least time of an ingest round's chunk update (every host's
chunk absorbed into its state, counted from the shapes by
`chipbench.work`) over the device time of the round, in %.  The least
time is the work at the peak of all the cell's N chips together, and the
device time is the round's busy time per chip, so the share is the work
at one chip's peak over the busy chip-seconds: whatever implements the
update, and however it splits the hosts across chips, it cannot pass 100."""
import sys

from chipbench import work


def read(run):
    if run.summary is None or run.peak is None:
        return None
    busy = run.summary.round_busy("ingest")
    if not busy:
        return None
    cfg = run.config
    one_chip, bound = work.roofline_seconds(
        work.chunk_update(cfg, cfg["hosts"], cfg["chunk"]), run.peak)
    least = one_chip / run.chips
    print(f"chunk_update_roofline: bound by {bound}, least {least * 1e3:.6f} ms "
          f"a round on {run.chips} chip(s), device {busy * 1e3:.6f} ms a chip",
          file=sys.stderr)
    return 100.0 * least / busy
