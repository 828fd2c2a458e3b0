"""Gateway tick: host ms of an ingest round's ``repro.tick`` that none of
its phase spans covers (admission bookkeeping, health, snapshots)."""


def read(run):
    reader = getattr(run.summary, "tick_self", None)
    if reader is None:
        return None
    s = reader("ingest")
    return None if s is None else s * 1e3
