"""Mean time, in ms, per delta publish of the host index update
(``nh.apply_delta``), every shard's summed: the program's ``publish.index``
spans (``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_publish_ms(run, "publish.index")
