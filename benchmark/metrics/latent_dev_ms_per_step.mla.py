"""Device self time a decode step under the scope `latent` of a block whose latent attention is
dense (`models/pangu_moe.py`: W_kvb folded into the query, the kernel `latent_attn` over every
slot's live rows, W_kvb's other half over its output, in every layer), in the decode programs
wholly inside the traced window (`lib/scope_trace.py`). The row's write is outside the scope."""
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_dev_ms_per_step.mla", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else st.scope_ms_per_decode_step(events, "latent")
