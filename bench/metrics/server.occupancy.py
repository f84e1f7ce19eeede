"""Requests per micro-batch over the window: the change in the server's
completed requests over the change in its batches (``StatsSnapshot``)."""


def read(run):
    if run.server0 is None:
        return None
    batches = run.server1.batches - run.server0.batches
    done = run.server1.completed - run.server0.completed
    return done / batches if batches else None
