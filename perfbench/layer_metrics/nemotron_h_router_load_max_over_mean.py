"""The busiest expert's pairs over the mean expert's, counted over ALL
128 experts the router scores (held here or not), since init, the worst of
the expert layers: 1 is an even router. It is what the balance bias
steers. Read from the expert op's cumulative `load` through the family
(`run.family`), which keeps the handle `build` made; a program or a family
without that counter reports nothing. (`router_load_max_over_mean` is the
same quantity for the GLM-4.7 cells, whose reader is pinned to them:
PERF.md 7.6 owes ONE reader for the three families.)"""

NAME = "nemotron_h_router_load_max_over_mean"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "nemotron_3_nano_30b_a3b.*"


def read(run):
    counters = getattr(run.family, "expert_counters", lambda: {})()
    loads = [c["load"] for c in counters.values()
             if "load" in c and c["load"].sum() > 0]
    if not loads:
        return None
    return float(max(p.max() / p.mean() for p in loads))
