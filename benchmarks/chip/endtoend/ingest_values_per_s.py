"""Readings × metrics absorbed in the window, over the time from the
window's start until the served state is ready after its last round."""


def read(run):
    ingests = run.of_kind("ingest")
    if not ingests:
        return None
    acked = sum(r.requests - r.failed for r in ingests)
    values = acked * run.config["chunk"] * run.config["metrics"]
    return values / (run.t_end - run.t_start)
