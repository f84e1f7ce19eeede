"""Mean time, in ms, per delta publish of the hybrid store's upserts and
deletes of the delta's rows: the program's ``publish.store_upsert`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_publish_ms(run, "publish.store_upsert")
