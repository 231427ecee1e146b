"""Process start to window start: imports, weights, compile or cache load,
warm-up of the cell's shapes, replica spin-up."""


def read(run):
    return run.setup_s
