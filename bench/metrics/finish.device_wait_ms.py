"""Mean time, in ms, per micro-batch of the wait until the fused launch's
outputs are ready on the device: the program's ``finish.device_wait`` spans
(``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "finish.device_wait")
