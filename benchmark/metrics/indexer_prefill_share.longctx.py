"""Share of the prefill programs' device time under the scope `indexer`: the part of a chunk
that is quadratic in the context whatever the selection keeps (`lib/scope_trace.py`)."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "indexer_prefill_share.longctx", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    ns, total = st.scope_ns(events, st.PREFILL, "indexer"), st.program_ns(events, st.PREFILL)
    return 100.0 * ns / total if ns > 0 and total else None
