"""(Wall time of the traced slice minus device busy time) over its steps,
in ms: what a step waits for the host loop, dispatch and readbacks."""

NAME = "host_gap_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "training_loop"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    if t is None:
        return None
    return 1e3 * (t["window_s"] - t["busy_s"]) / t["steps"]
