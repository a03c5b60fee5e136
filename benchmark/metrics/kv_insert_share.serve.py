"""Share of the traced window the stepper spent in `rt.engine.kv_insert`: slicing an admitted
prompt's KV rows off the device into the prefix cache, while every decoding slot waits."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "kv_insert_share.serve", "%", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = pt.for_record(record)
    if events is None or not pt.stepper_spans(events):
        return None  # a program without spans: not 0
    lo, hi = pt.window_of(events)
    inside = sum(min(e[1] + e[2], hi) - max(e[1], lo) for e in pt.spans_named(events, "rt.engine.kv_insert", whole=False))
    return 100.0 * inside / (hi - lo)
