"""Device program launches in the traced slice over its steps (a count):
1 when a step is one executable, below 1 under fused supersteps, above 1
when metric folds or re-layouts launch programs of their own."""

NAME = "programs_per_step"
UNIT = "count"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "training_loop"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    return None if t is None else t["programs"] / t["steps"]
