"""Mean time, in ms, a request waited in its QueryServer lane before its
micro-batch formed: the server's ``lane_wait`` spans, every request
traced."""


def read(run):
    d = [t1 - t0 for name, t0, t1 in run.spans or () if name == "lane_wait"]
    return sum(d) / len(d) * 1e3 if d else None
