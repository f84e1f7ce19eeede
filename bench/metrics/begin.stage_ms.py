"""Mean time, in ms, per micro-batch of the engine's stage step (version pin,
per-table np.unique, shard routing, key split): the program's
``begin.stage`` spans (``bench/span_stats.py``)."""
import span_stats


def read(run):
    return span_stats.per_batch_ms(run, "begin.stage")
