"""99th percentile, in ms, over every update record due in the window, of
the time from its due time until the ``client.update`` call that publishes
it returns, after which a ``min_version`` read sees it."""
import numpy as np


def read(run):
    lag = run.update_visible - run.update_due
    lag = lag[~np.isnan(lag)]
    return float(np.percentile(lag, 99) * 1e3) if len(lag) else None
