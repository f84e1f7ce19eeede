"""Mean time, in ms, per micro-batch of the hybrid store's admission of cold
rows to the hot tier: the program's ``store.admit`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "store.admit")
