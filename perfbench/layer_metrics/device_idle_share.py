"""Share of the traced slice in which no op ran on a chip, in %: 1 minus
the union of the device-op intervals over the slice, averaged over chips."""

NAME = "device_idle_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    return None if t is None else 100.0 * t["idle_share"]
