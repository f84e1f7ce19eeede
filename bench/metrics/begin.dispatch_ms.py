"""Mean time, in ms, per micro-batch of the call of the fused probe program,
until it returns: the program's ``begin.dispatch`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "begin.dispatch")
