"""Share, in %, of requested keys that per-batch dedup removed before the
probe and the value gather, over the window: 1 - keys_deviceside /
keys_requested, from the server's counters of the engine's batches."""


def read(run):
    if run.server0 is None:
        return None
    asked = run.server1.keys_requested - run.server0.keys_requested
    kept = run.server1.keys_deviceside - run.server0.keys_deviceside
    return 100.0 * (1.0 - kept / asked) if asked else None
