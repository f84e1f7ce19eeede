"""Mean host time, in ms, of one micro-batch's engine finish (the wait for
the device, the inverse gather, the value-tier gather): the server's
``finish`` spans, once per batch."""


def read(run):
    d = [t1 - t0 for name, t0, t1 in set(run.spans or ()) if name == "finish"]
    return sum(d) / len(d) * 1e3 if d else None
