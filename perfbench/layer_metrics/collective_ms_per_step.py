"""Time a step during which a collective is in flight on a chip, in ms,
by HLO collective op names (an asynchronous one from its -start to the end
of its -done), averaged over the chips."""

NAME = "collective_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "exchange"
MOVES = "samples_per_s_per_chip"
CELLS = "*_x4"


def read(run):
    t = run.trace
    return None if t is None else 1e3 * t["collective_s"] / t["steps"]
