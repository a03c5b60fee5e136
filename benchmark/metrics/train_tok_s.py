"""Tokens trained per second: every step of the window, over the whole window, with the
state threaded through the steps and one sync at the end."""
NAME, UNIT, LAYER, MOVES, SOURCE = "train_tok_s", "tokens/s", "end to end", None, "host_clock"
DRIVERS = ("train_steps",)


def read(record):
    return record["steps"] * record["tokens_per_step"] / record["window_s"] if record["steps"] else None
