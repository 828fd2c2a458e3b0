"""Tenant state and plan update: host ms an ingest round spends dispatching
the scatter update, any wait on the donated state included (span
``repro.ingest.dispatch``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("ingest", "ingest.dispatch")
    return None if s is None else s * 1e3
