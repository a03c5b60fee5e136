"""Experts of a layer that took at least one token-expert pair in a decode step, mean over the
window's decode steps and the expert layers, from the program's own counts
(`scheduler_stats()["experts"]`: `decode_experts_hit` over `decode_layer_steps`). Each is read
once a step whatever it is given to do: at 64 slots even routing hits 63 of 64."""
from lib import expert_counts

NAME, UNIT, LAYER, MOVES, SOURCE = "experts_hit_per_step.decode64", "count", "model block", "tpot_ms_p90", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    return expert_counts.hit_per_decode_step(record)
