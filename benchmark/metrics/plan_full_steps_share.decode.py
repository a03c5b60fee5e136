"""How often a plan with a slot decoding ran every step `multi_step` allows: the share of the
traced window's `rt.engine.iter` spans with `decode_slots > 0` whose `limit` is `none`
(`Plan.limit`, `ray_tpu/llm/scheduler/scheduler.py`). Beside it `plan_held_by_prefill_share.decode`
and `plan_held_by_tail_share.decode`; the three need not sum to 100 (`sampling`, `spec`, `off`
are the rest)."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "plan_full_steps_share.decode", "%", "scheduler", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.limit_share(events, ("none",))
