"""Gateway tick: host ms an ingest round spends grouping the backlog and
stacking each group into one batch (span ``repro.ingest.stack``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("ingest", "ingest.stack")
    return None if s is None else s * 1e3
