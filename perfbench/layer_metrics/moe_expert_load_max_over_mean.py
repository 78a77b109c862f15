"""The busiest held expert's pairs over the mean held expert's, since
init, the worst of the expert layers: 1 is an even router. Read from the
expert op's cumulative counters through the family (`run.family`), which
keeps the handle `build` made; a family without them reports nothing."""

NAME = "moe_expert_load_max_over_mean"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "qwen3_next_80b_a3b.*"


def read(run):
    counters = getattr(run.family, "expert_counters", lambda: {})()
    loads = [c["pairs"] for c in counters.values() if c["pairs"].sum() > 0]
    if not loads:
        return None
    return float(max(p.max() / p.mean() for p in loads))
