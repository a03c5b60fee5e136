"""Device self time a decode step under the scopes `in_proj`, `conv` and `out_proj` of the conv
layers (the two projections, the gate, the three taps over every slot's cached inputs and the
window's shift), in the decode programs wholly inside the traced window
(`lib/scope_trace_state.py`). `in_proj` and `out_proj` are a mamba layer's names too: the entry lists
this block's cell alone."""
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "conv_dev_ms_per_step.decode64", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sts.ms_per_decode_step(events, ("in_proj", "conv", "out_proj"))
