"""The 95th percentile, over every query answered in the window, of the
time from the client's submit until its future has resolved."""
import numpy as np


def read(run):
    lat = [r.latencies for r in run.of_kind("query") if r.latencies is not None]
    if not lat or not sum(len(x) for x in lat):
        return None
    return float(np.percentile(np.concatenate(lat), 95)) * 1e3
