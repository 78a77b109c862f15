"""The (token, expert) pairs that fell on the experts held here over all
the pairs the router made, since init, all expert layers together, in %:
the rows the walk must compute. held / scored (12.5% for 8 of 64) when the
router is even. Read from the expert op's cumulative `pairs` and `load`
through the family (`run.family`); a program or a family without them
reports nothing."""

NAME = "held_pair_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "glm_4_7_flash.*"


def read(run):
    counters = [c for c in getattr(run.family, "expert_counters",
                                   lambda: {})().values() if "load" in c]
    made = sum(int(c["load"].sum()) for c in counters)
    if made <= 0:
        return None
    return 100.0 * sum(int(c["pairs"].sum()) for c in counters) / made
