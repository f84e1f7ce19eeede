"""Mean time, in ms, per delta publish of the touched shards' device puts,
waited on while traced: the program's ``publish.upload`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_publish_ms(run, "publish.upload")
