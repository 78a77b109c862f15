"""Rows the expert walk computed that carried no (token, expert) pair, as
a share of all the rows it computed since init, in %. Read from the expert
op's cumulative `rows` and `pairs` through the family (`run.family`); a
family without them reports nothing. (`moe_padded_row_share` is the same
quantity for the Qwen3-Next cells, whose reader is pinned to them: PERF.md
7.6 owes one reader for both.)"""

NAME = "expert_padded_row_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "glm_4_7_flash.*"


def read(run):
    counters = getattr(run.family, "expert_counters", lambda: {})()
    rows = sum(int(c["rows"]) for c in counters.values())
    pairs = sum(int(c["pairs"].sum()) for c in counters.values())
    if rows <= 0:
        return None
    return 100.0 * (rows - pairs) / rows
