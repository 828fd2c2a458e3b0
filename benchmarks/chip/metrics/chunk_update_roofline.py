"""Kernels: the least time of an ingest round's chunk update (every host's
chunk absorbed into its state, counted from the shapes by
`chipbench.work`) over the device time of the round, in %.  Whatever
implements the update, the share cannot pass 100."""
import sys

from chipbench import work


def read(run):
    if run.summary is None or run.peak is None:
        return None
    busy = run.summary.round_busy("ingest")
    if not busy:
        return None
    cfg = run.config
    least, bound = work.roofline_seconds(
        work.chunk_update(cfg, cfg["hosts"], cfg["chunk"]), run.peak)
    print(f"chunk_update_roofline: bound by {bound}, least {least * 1e3:.6f} ms "
          f"a round, device {busy * 1e3:.6f} ms", file=sys.stderr)
    return 100.0 * least / busy
