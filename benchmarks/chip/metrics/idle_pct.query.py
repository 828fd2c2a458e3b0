"""Device: idle share of the query rounds, 100 · (1 − busy / length)."""


def read(run):
    if run.summary is None:
        return None
    s = run.summary.idle_share("query")
    return None if s is None else 100.0 * s
