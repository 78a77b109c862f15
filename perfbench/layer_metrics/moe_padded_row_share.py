"""Rows the expert op's grouped products were given that carried no
(token, expert) pair, as a share of all the rows they were given since
init, in %. Read from the expert op's cumulative counters through the
family (`run.family`); a family without them reports nothing."""

NAME = "moe_padded_row_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "qwen3_next_80b_a3b.*"


def read(run):
    counters = getattr(run.family, "expert_counters", lambda: {})()
    rows = sum(int(c["rows"]) for c in counters.values())
    pairs = sum(int(c["pairs"].sum()) for c in counters.values())
    if rows <= 0:
        return None
    return 100.0 * (rows - pairs) / rows
