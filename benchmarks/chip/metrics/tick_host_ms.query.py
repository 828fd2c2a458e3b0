"""Gateway tick: host wall ms of ``StatsGateway.tick`` in a query round
(span ``bench.tick``), the device→host copy of the answers included."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.span_mean("query", "tick")
    return None if s is None else s * 1e3
