"""Output tokens produced per second of the window, over every request that produced
any. A request that straddles an edge of the window counts for the part of its decoding
that lies inside: its tokens come at an even pace between its first and its last."""
from lib import rows

NAME, UNIT, LAYER, MOVES, SOURCE = "serve_out_tok_s", "tokens/s", "end to end", None, "host_clock"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    lo, hi = record["window"]
    total = 0.0
    for r, inside, whole in rows.decode_seconds(record):
        if whole > 0:
            total += r["n_out"] * inside / whole
        elif lo <= r["sent"] + r["ttft_s"] < hi:  # a single token
            total += r["n_out"]
    return total / (hi - lo) if total else None
