"""Share of the prefill programs' device time under the scope `latent` of a block whose latent
attention is dense: a chunk's expansion of keys and values from the latent rows and its blocked
loop over every row before the chunk's last, the part of a chunk that grows with the context
(`lib/scope_trace.py`)."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_prefill_share.mla", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    ns, total = st.scope_ns(events, st.PREFILL, "latent"), st.program_ns(events, st.PREFILL)
    return 100.0 * ns / total if ns > 0 and total else None
