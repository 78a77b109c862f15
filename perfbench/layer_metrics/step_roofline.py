"""The least time one chip could take for one step over the device time
it took, in %: the larger of FLOPs / peak bf16 FLOP/s and bytes / peak HBM
bytes/s (both from the family's shape arithmetic and peaks.json), over
device busy time a step. `note(run)` says which of the two bounds it."""

NAME = "step_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "samples_per_s_per_chip"
CELLS = "*"


def read(run):
    t = run.trace
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * max(floors(run).values()) / (t["busy_s"] / t["steps"])


def floors(run) -> dict:
    """Seconds one step needs at the least, by each roof."""
    fam, b = run.family, run.batch_per_chip
    return {"compute": fam.flops_per_sample(run.config) * b
            / run.peaks["bf16_flops_per_s"],
            "memory": fam.bytes_per_step(run.config, b)
            / run.peaks["hbm_bytes_per_s"]}


def note(run) -> str:
    f = floors(run)
    return (f"bound by {max(f, key=f.get)} (a step needs at least "
            f"{1e3 * f['compute']:.4f} ms of compute, "
            f"{1e3 * f['memory']:.4f} ms of HBM traffic)")
