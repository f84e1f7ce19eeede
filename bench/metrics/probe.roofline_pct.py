"""The device probe's share, in %, of the HBM roofline in the traced part of
the window: keys probed on the device times 32 B (one 16-B bucket read, an
8-B key in, an 8-B payload out) over the device's busy time, over the
chip's HBM bandwidth from ``bench/peaks.json``. Meant for cells in which the
probe is the only device work."""


def read(run):
    if not run.trace or not run.trace["busy_s"] or run.peaks is None:
        return None
    moved = run.trace["keys_deviceside"] * 32
    return 100.0 * moved / run.trace["busy_s"] / run.peaks["hbm_bytes_per_s"]
