"""Set-up: from process start to the first timed request. It holds the data
made from the seed, the index build, the device upload and the warm-up of
the cell's own shapes (the run prints each part on its ``setup:`` line)."""


def read(run):
    return run.setup_s
