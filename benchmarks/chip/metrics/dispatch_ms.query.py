"""Read path: host ms a query round spends dispatching the gather, ⊕-fold
and batched finalize (span ``repro.query.dispatch``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("query", "query.dispatch")
    return None if s is None else s * 1e3
