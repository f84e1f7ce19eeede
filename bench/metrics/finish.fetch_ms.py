"""Mean time, in ms, per micro-batch of the device-to-host copies of the
launch's outputs: the program's ``finish.fetch`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "finish.fetch")
