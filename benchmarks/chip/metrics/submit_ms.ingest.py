"""Front door: host ms an ingest round spends in the client's
``submit_ingest`` calls (span ``bench.submit``)."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.span_mean("ingest", "submit")
    return None if s is None else s * 1e3
