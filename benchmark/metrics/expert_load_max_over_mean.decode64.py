"""The busiest expert's token-expert pairs over the mean expert's, over the window and summed over
the expert layers, from the program's own counts (`scheduler_stats()["experts"]["window"]`:
`max_load`, `mean_load`). 1 is even routing; with seeded random weights and a zero selection bias
the spread is what falls."""
from lib import expert_counts

NAME, UNIT, LAYER, MOVES, SOURCE = "expert_load_max_over_mean.decode64", "ratio", "model block", "serve_out_tok_s", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    w = expert_counts.window(record)
    return w["max_load"] / w["mean_load"] if w.get("mean_load") else None
