"""Host time to hand a round's tokens on: the median `rt.engine.sample.emit` span of the traced
window's decode rounds (the slots' bookkeeping and `_emit`: the stop test, the flight record's token
stamp, the callback into the event loop, retirement). What is left of `rt.engine.sample` when
nothing is drawn on the host, as in a multi-step round."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "emit_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.round_ms_p50(events, "rt.engine.sample.emit")
