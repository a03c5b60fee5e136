"""Device operations a decode step under the scope `hc` (`lib/scope_trace_hc.py`): what a layout of the
hyper-connection is meant to bring down. Written as the paper writes it, 20 Sinkhorn steps are 40
reductions and 40 divisions a sub-layer, each an operation of a few microseconds on 48 tokens; with the
steps in one kernel (`ops/hyper_connection.py:hc_map`) a sub-layer is some seven operations."""
from lib import scope_trace as st
from lib import scope_trace_hc as sth

NAME, UNIT, LAYER, MOVES, SOURCE = "hc_ops_per_step.mhc", "count", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sth.ops_per_decode_step(events)
