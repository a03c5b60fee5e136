"""How many decode steps a plan with a slot decoding ran, in the mean: `steps` over the traced
window's `rt.engine.iter` spans with `decode_slots > 0` (`Plan.multi_step`; `lib/loop_trace.py`'s
table by `limit` sums the same). 1.00 while every plan of sampled traffic was held to one step
under `sampling`; up to `steps_max` (8) once the multi-step program draws at a temperature
(PERF.md §6, PR 44). What is left under it are the plans an arrival's chunk or a slot's last
tokens hold."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "decode_steps_per_round.chat", "steps", "scheduler", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_open",)


def read(record):
    events = lt.for_record(record)
    if events is None:
        return None
    steps = [int(attrs["steps"]) for attrs in lt.decode_iters(events)]
    return sum(steps) / len(steps) if steps else None
