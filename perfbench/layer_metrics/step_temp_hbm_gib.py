"""The temporaries of the run's largest step program (the one
`step_hbm_gib` reads), in GiB: `temp` of `obs.trace.program_memory`, the
bytes XLA's schedule keeps live at once beside arguments and outputs. What
recomputation (`Op.recompute`), residuals kept for the backward pass and
the compiler's schedule decide, and what the runtime's peak does not
show."""

from perfbench.layer_metrics.step_hbm_gib import GIB, largest

NAME = "step_temp_hbm_gib"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "graph_compile"
MOVES = "peak_hbm_gib"
CELLS = "*"


def read(run):
    memory = largest()
    return None if memory is None else memory["temp"] / GIB
