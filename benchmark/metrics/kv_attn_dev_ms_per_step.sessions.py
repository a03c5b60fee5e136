"""Device self time a decode step under the scope `kv_attn`: the attention layers' write into
the K and V slabs and the two products against them, the code this block shares with the dense
one (`models/llama.py:_attn_cached`), in the decode programs wholly inside the traced window."""
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "kv_attn_dev_ms_per_step.sessions", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sts.ms_per_decode_step(events, ("kv_attn",))
