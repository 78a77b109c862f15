"""Seconds from the first line of the harness's build to the end of
`FFModel.compile()`: graph build, strategy, sharding resolution."""

NAME = "setup_build_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "graph_compile"
MOVES = "setup_s"
CELLS = "*"


def read(run):
    return run.timings.get("build_s")
