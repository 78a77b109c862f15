"""Device time a step of every op that is neither a Mosaic custom call
nor a collective, in ms: all that XLA compiled from `ops/*.py`, pooled
until the program names its ops in the trace."""

NAME = "xla_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    return None if t is None else 1e3 * t["xla_s"] / t["steps"]
