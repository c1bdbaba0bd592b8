"""Process start to the first timed request: imports, jax, data from the
seed, node start, canary, the cell's own executables and warm-up."""

NAME = "setup_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run: dict):
    return run["setup_s"]
