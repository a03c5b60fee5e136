"""Host time to call a decode program: the median `rt.engine.dispatch.call` span of the traced
window's decode rounds (the program's lookup, the jitted call over the parameters' and the caches'
leaves, which returns at enqueue, and `_note_stats`)."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch_call_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.round_ms_p50(events, "rt.engine.dispatch.call")
