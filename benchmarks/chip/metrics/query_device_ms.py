"""Read path (gather, ⊕-fold, batched finalize): device busy ms a query
round per chip of the cell."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.round_busy("query")
    return None if not s else s * 1e3
