"""Device self time a decode step under the scopes `conv` and `ssm` of the mamba layers (the
convolution's window and the recurrence's one-token update over every slot's state), in the
decode programs wholly inside the traced window (`lib/scope_trace_state.py`)."""
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "ssm_dev_ms_per_step.sessions", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sts.ms_per_decode_step(events, ("conv", "ssm"))
