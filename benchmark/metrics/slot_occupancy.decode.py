"""Share of the window's slot-seconds in which a slot held a request that was past its
first token. Taken from the requests' own first and last tokens on the host clock,
not from the scheduler's `decode_tokens / iterations`: one multi-step dispatch is one
iteration that plans up to 8 tokens a slot, so that ratio passes 100%."""
from lib import rows

NAME, UNIT, LAYER, MOVES, SOURCE = "slot_occupancy.decode", "%", "scheduler", "serve_out_tok_s", "host_clock"
DRIVERS = ("serve_closed",)


def read(record):
    busy = sum(inside for _, inside, _ in rows.decode_seconds(record))
    return 100.0 * busy / (record["window_s"] * record["slots"])
