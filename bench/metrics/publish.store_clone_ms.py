"""Mean time, in ms, per delta publish of the hybrid store's copy-on-write
clone: the program's ``publish.store_clone`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_publish_ms(run, "publish.store_clone")
