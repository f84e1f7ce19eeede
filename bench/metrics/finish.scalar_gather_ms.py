"""Mean time, in ms, per micro-batch of the scalar tables' payload assembly
and inverse gather to request order: the program's ``finish.scalar_gather``
spans (``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "finish.scalar_gather")
