"""Device self time a decode step under the scope `indexer` (the indexer's queries, its scores
over every cached key of the two full layers, `top_k`), in the decode programs wholly inside the
traced window (`lib/scope_trace.py`)."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "indexer_dev_ms_per_step.longctx", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else st.scope_ms_per_decode_step(events, "indexer")
