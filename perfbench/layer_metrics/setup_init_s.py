"""Seconds of `init_layers(seed)` to `block_until_ready(params)`: the
init program's build or load, and the draws on the device."""

NAME = "setup_init_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "init"
MOVES = "setup_s"
CELLS = "*"


def read(run):
    return run.timings.get("init_s")
