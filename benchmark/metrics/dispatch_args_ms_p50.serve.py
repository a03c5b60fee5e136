"""Host time to build a decode program's arguments: the median `rt.engine.dispatch.args` span of
the traced window's decode rounds (the write gate, `_lora_tables()`, the host mirrors of the
adapter ids, last tokens and lengths to device arrays)."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch_args_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.round_ms_p50(events, "rt.engine.dispatch.args")
