"""Gateway tick: host ms a query round spends slicing each waiter's answer
and resolving its future (span ``repro.query.resolve``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("query", "query.resolve")
    return None if s is None else s * 1e3
