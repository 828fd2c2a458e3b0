"""Gateway tick: host ms of a query round's ``repro.tick`` that none of its
phase spans covers (grouping the waiters, health, snapshots)."""


def read(run):
    reader = getattr(run.summary, "tick_self", None)
    if reader is None:
        return None
    s = reader("query")
    return None if s is None else s * 1e3
