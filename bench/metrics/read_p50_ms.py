"""Median latency, in ms, of every read due in the window, timed on the
client from the request's due time to its answer's arrival."""
import numpy as np


def read(run):
    lat = (run.read_done - run.read_due)[run.read_ok]
    return float(np.percentile(lat, 50) * 1e3) if len(lat) else None
