"""Tenant state and plan update: host ms an ingest round spends handing the
stacked batch and its ids to the device (span ``repro.ingest.h2d``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("ingest", "ingest.h2d")
    return None if s is None else s * 1e3
