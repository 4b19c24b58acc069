"""Set-up: from process start to the first timed call (imports, device
start-up, building the cell's specs, the warm-up call at the cell's
width with its compile or cache load)."""


def read(run):
    return run.setup_s
