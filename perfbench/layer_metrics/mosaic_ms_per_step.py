"""Device time a step of the Mosaic custom calls (the Pallas kernels of
`ops/pallas/`), in ms. 0 where a gate sent the step to XLA's own ops."""

NAME = "mosaic_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    return None if t is None else 1e3 * t["mosaic_s"] / t["steps"]
