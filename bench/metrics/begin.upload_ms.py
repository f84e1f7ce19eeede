"""Mean time, in ms, per micro-batch of the query tiles' padding and their
host-to-device copies: the program's ``begin.upload`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "begin.upload")
