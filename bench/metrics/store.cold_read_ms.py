"""Mean time, in ms, per micro-batch of the hybrid store's reads of cold-file
rows: the program's ``store.cold_read`` spans (``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "store.cold_read")
