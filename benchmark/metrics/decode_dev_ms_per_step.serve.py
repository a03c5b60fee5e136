"""Device time of one decode step: the seconds of the decode programs' executions in the
traced window (`jit_rt_decode`, `jit_rt_decode_multi_n<k>`: `XLA Modules` events) over the
steps they computed (1, or k)."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "decode_dev_ms_per_step.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = pt.for_record(record)
    return None if events is None else pt.decode_ms_per_step(events)
