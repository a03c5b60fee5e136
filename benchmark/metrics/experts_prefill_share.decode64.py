"""Share of the prefill programs' device time under the scope `experts`: a 512-token chunk routes
2048 pairs and reads every expert of every layer, the bytes a decode step reads, for a few
milliseconds of arithmetic (`lib/scope_trace.py`)."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "experts_prefill_share.decode64", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    ns, total = st.scope_ns(events, st.PREFILL, "experts"), st.program_ns(events, st.PREFILL)
    return 100.0 * ns / total if ns > 0 and total else None
