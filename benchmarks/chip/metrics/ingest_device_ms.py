"""Tenant state and plan update: device busy ms an ingest round per chip
of the cell (the device time that lies in the round's share of the
trace)."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.round_busy("ingest")
    return None if not s else s * 1e3
