"""The (token, expert) pairs that fell on the experts held here over all
the pairs the router made, since init, all expert layers together, in %:
the rows the walk must compute, and the seed's part of the rate. held /
scored (6.25% for 8 of 128) when the router is even. Read from the expert
op's cumulative `pairs` and `load` through the family (`run.family`); a
program or a family without them reports nothing. (`held_pair_share` is the
same quantity for the GLM-4.7 cells: PERF.md 7.6.)"""

NAME = "nemotron_h_held_pair_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "nemotron_3_nano_30b_a3b.*"


def read(run):
    counters = [c for c in getattr(run.family, "expert_counters",
                                   lambda: {})().values() if "load" in c]
    made = sum(int(c["load"].sum()) for c in counters)
    if made <= 0:
        return None
    return 100.0 * sum(int(c["pairs"].sum()) for c in counters) / made
