"""Peak bytes in use on the fullest chip right after `init_layers`, in
GiB: what initialization needs above the parameters it leaves behind."""

NAME = "init_peak_hbm_gib"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "init"
MOVES = "peak_hbm_gib"
CELLS = "*"


def read(run):
    peak = run.memory.get("init_peak_bytes")
    return None if peak is None else peak / 2**30
