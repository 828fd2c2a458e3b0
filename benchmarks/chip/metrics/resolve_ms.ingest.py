"""Gateway tick: host ms an ingest round spends resolving the acknowledged
futures (span ``repro.ingest.resolve``)."""


def read(run):
    reader = getattr(run.summary, "program_span_mean", None)
    if reader is None:
        return None
    s = reader("ingest", "ingest.resolve")
    return None if s is None else s * 1e3
