"""Read path: ms of a query round's ``repro.query.fetch`` during which no
operation runs on the device: the device→host copy of the answers and the
host's work on it, not the wait for the programs."""


def read(run):
    reader = getattr(run.summary, "program_span_idle", None)
    if reader is None:
        return None
    s = reader("query", "query.fetch")
    return None if s is None else s * 1e3
