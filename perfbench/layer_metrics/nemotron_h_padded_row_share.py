"""Rows the expert walk computed that carried no (token, expert) pair, as
a share of all the rows it computed since init, in %: what `CHUNK_ROWS`
(512) costs at ~384 pairs a held expert. Read from the expert op's
cumulative `rows` and `pairs` through the family (`run.family`); a family
without them reports nothing. (`moe_padded_row_share` and
`expert_padded_row_share` are the same quantity for the Qwen3-Next and the
GLM-4.7 cells: PERF.md 7.6.)"""

NAME = "nemotron_h_padded_row_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops"
MOVES = "samples_per_s_per_chip"
CELLS = "nemotron_3_nano_30b_a3b.*"


def read(run):
    counters = getattr(run.family, "expert_counters", lambda: {})()
    rows = sum(int(c["rows"]) for c in counters.values())
    pairs = sum(int(c["pairs"].sum()) for c in counters.values())
    if rows <= 0:
        return None
    return 100.0 * (rows - pairs) / rows
