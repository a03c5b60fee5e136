"""How often an admission held a plan's decode phase to a single step: the share of the traced
window's `rt.engine.iter` spans with `decode_slots > 0` whose `limit` is `chunk` (a prefill chunk
in the plan), `prefilling` (a request admitted whose chunks are not in it) or `queue` (a request
waiting for a slot)."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "plan_held_by_prefill_share.decode", "%", "scheduler", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.limit_share(events, lt.HELD_BY_PREFILL)
