"""Share of the prefill programs' device time under the scopes `conv` and `ssm`: the chunked scan
and the convolution of an admitted prompt's chunks, beside the matrix products that take the rest
(`lib/scope_trace_state.py`)."""
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "ssm_prefill_share.sessions", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    ns, total = sts.scope_ns(events, st.PREFILL, ("conv", "ssm")), st.program_ns(events, st.PREFILL)
    return 100.0 * ns / total if ns > 0 and total else None
