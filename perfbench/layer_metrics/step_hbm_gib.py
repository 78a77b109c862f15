"""Bytes of HBM the compiler counts for the run's largest step program, in
GiB: `counted` = argument + output - alias + temp of
`obs.trace.program_memory(executable)`, over the `train` / `superstep`
records `FFModel._cached_compile` left in this process. What has to be free
on the chip for a step to run; the runtime's `peak_bytes_in_use`
(`peak_hbm_gib`) leaves a step's temporaries out and counts what else the
process holds (the staged data set). A program without the record or the
analysis reports nothing. `note(run)` prints the parts beside that peak."""

NAME = "step_hbm_gib"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "graph_compile"
MOVES = "peak_hbm_gib"
CELLS = "*"

GIB = 2**30


def step_programs():
    """The run's step-program records, oldest first; none where the
    program keeps no such record."""
    from dlrm_flexflow_tpu.obs import trace
    programs = getattr(trace, "programs", None)
    if programs is None:
        return []
    return [r for r in programs() if r.kind in trace.STEP_KINDS]


def largest():
    """`program_memory` of the step program with the largest `counted`,
    or None."""
    from dlrm_flexflow_tpu.obs import trace
    found = [m for m in (trace.program_memory(r.executable)
                         for r in step_programs()) if m is not None]
    return max(found, key=lambda m: m["counted"], default=None)


def read(run):
    memory = largest()
    return None if memory is None else memory["counted"] / GIB


def note(run) -> str:
    memory = largest()
    if memory is None:
        return "no step program left a record with a memory analysis"
    peak = run.memory.get("peak_bytes")
    return (", ".join(f"{part} {n:,}" for part, n in memory.items())
            + " bytes by the compiler's count (counted = argument + output "
            "- alias + temp); the runtime's peak_bytes_in_use, which leaves "
            "temporaries out and holds what else the process staged, "
            + ("not kept by this backend" if peak is None else f"{peak:,}"))
