"""Seconds the program itself spent lowering and compiling (or loading)
its step programs: `lower_s + compile_s` summed over the `train` /
`superstep` records of this process, by `FFModel._cached_compile`'s own
clock reads. The inside of what `setup_step_compile_s` times from outside
(the warm-up fit minus its steps, which also holds the staging and the
first steps' re-layouts). `note(run)` prints the two phases and how many
programs the model's own CompileCache loaded."""

from perfbench.layer_metrics.step_hbm_gib import step_programs

NAME = "step_program_compile_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "graph_compile"
MOVES = "setup_s"
CELLS = "*"


def read(run):
    recs = step_programs()
    return sum(r.lower_s + r.compile_s for r in recs) if recs else None


def note(run) -> str:
    recs = step_programs()
    return (f"{len(recs)} step program(s): lower "
            f"{sum(r.lower_s for r in recs):.3f} s, compile "
            f"{sum(r.compile_s for r in recs):.3f} s, "
            f"{sum(r.loaded for r in recs)} loaded by the CompileCache; "
            f"from outside, setup_step_compile_s "
            f"{run.timings.get('step_compile_s')}")
