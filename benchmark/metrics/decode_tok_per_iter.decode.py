"""Decode tokens the scheduler planned per iteration (counter deltas over the window).
With 12 slots, 12 means single steps and up to 96 means 8-token multi-step dispatches:
it shows how often prefills and arrivals break the multi-step path."""
NAME, UNIT, LAYER, MOVES, SOURCE = "decode_tok_per_iter.decode", "tokens", "scheduler", "serve_out_tok_s", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    c = record["counters"]
    return c["decode_tokens"] / c["iterations"] if c["iterations"] else None
