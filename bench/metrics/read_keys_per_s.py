"""Keys answered per second, summed over tables: the keys of every read due
in the window that was answered, over the time from the window's start to
the last of those answers. Offered above capacity, the backlog keeps the
server busy from the window's start to that answer, so this is the rate the
cell sustains; below capacity it would only read back the offered load."""
import numpy as np


def read(run):
    if not run.read_ok.any():
        return None
    return float(run.read_keys[run.read_ok].sum()
                 / (np.nanmax(run.read_done) - run.t0))
