"""Mean time, in ms, per micro-batch of the hybrid store's gather of hot-tier
rows: the program's ``store.hot_gather`` spans (``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "store.hot_gather")
