"""How late the load generator sent: 99th percentile, in ms, of send time
minus due time over every read of the window."""
import numpy as np


def read(run):
    lag = run.read_sent - run.read_due
    lag = lag[~np.isnan(lag)]
    return float(np.percentile(lag, 99) * 1e3) if len(lag) else None
