"""Mean time, in ms, per micro-batch of the hybrid store's index probe
(``lookup_host_batch``) and tier split: the program's ``store.probe`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "store.probe")
