"""Mean host time, in ms, of one micro-batch's engine stage and launch (key
dedup, shard routing, the async device dispatch): the server's ``begin``
spans, once per batch."""


def read(run):
    d = [t1 - t0 for name, t0, t1 in set(run.spans or ()) if name == "begin"]
    return sum(d) / len(d) * 1e3 if d else None
