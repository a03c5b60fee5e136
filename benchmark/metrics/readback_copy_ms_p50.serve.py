"""The copy of a finished result to the host: the median `rt.engine.readback.copy` span
(`np.asarray(x)` after the wait) of the traced window's decode rounds: `[B, V]` float32 logits in
a single-step round, `[n, B]` int32 tokens in a multi-step one."""
from lib import loop_trace as lt

NAME, UNIT, LAYER, MOVES, SOURCE = "readback_copy_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = lt.for_record(record)
    return None if events is None else lt.round_ms_p50(events, "rt.engine.readback.copy")
