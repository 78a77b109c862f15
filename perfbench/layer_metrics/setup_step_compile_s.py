"""Seconds the warm-up `fit` spent on anything but its two epochs of
steps (its wall time minus twice its second epoch): staging the data set,
building or loading the step program, the first steps' re-layouts."""

NAME = "setup_step_compile_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "graph_compile"
MOVES = "setup_s"
CELLS = "*"


def read(run):
    return run.timings.get("step_compile_s")
