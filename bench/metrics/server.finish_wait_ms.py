"""Mean time, in ms, per micro-batch of the wait from the return of ``begin``
until a finish worker takes the batch: the program's ``finish_wait`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "finish_wait")
