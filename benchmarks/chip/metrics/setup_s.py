"""Process start to the first timed request: weights made on the device,
documents committed, the cell's shapes warmed (and compiled, or loaded
from the persistent cache)."""


def read(run):
    return run.setup_s
