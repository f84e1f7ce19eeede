"""Mean host time, in ms, of one ``client.update`` call that publishes a
delta (the engine's ``publish_delta``: index copy, device upload,
hybrid-store clone and upsert)."""
import numpy as np


def read(run):
    d = run.pub_end - run.pub_start
    d = d[~np.isnan(d)]
    return float(d.mean() * 1e3) if len(d) else None
