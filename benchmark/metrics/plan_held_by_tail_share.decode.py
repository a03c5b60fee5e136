"""How often one slot's last tokens held every slot's decode phase under `multi_step`: the share of
the traced window's `rt.engine.iter` spans with `decode_slots > 0` whose `limit` is `tail` (nothing
else in the plan, and the least `max_tokens - generated` over the decoding slots under `multi_step`,
rounded down to a power of two)."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "plan_held_by_tail_share.decode", "%", "scheduler", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.limit_share(events, ("tail",))
