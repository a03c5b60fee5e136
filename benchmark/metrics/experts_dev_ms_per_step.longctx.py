"""Device self time a decode step under the scope `experts` (the sort of the token-expert pairs,
the loop of gated experts over the tiles that hold one, the weighted sum back), in the decode
programs wholly inside the traced window (`lib/scope_trace.py`). The router and the shared
expert have scopes of their own."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "experts_dev_ms_per_step.longctx", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else st.scope_ms_per_decode_step(events, "experts")
