"""Gateway tick: host wall ms of ``StatsGateway.tick`` in an ingest round
(span ``bench.tick``)."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.span_mean("ingest", "tick")
    return None if s is None else s * 1e3
