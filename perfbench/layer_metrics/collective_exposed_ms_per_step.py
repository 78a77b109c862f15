"""The part of `collective_ms_per_step` during which no other op runs on
that chip, in ms: the exchange that nothing hides."""

NAME = "collective_exposed_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "exchange"
MOVES = "samples_per_s_per_chip"
CELLS = "*_x4"


def read(run):
    t = run.trace
    if t is None:
        return None
    return 1e3 * t["collective_exposed_s"] / t["steps"]
