"""Share of the prefill programs' device time under the scope `hc` (`lib/scope_trace_hc.py`): in a chunk
the hyper-connection's cost is bytes, a 1024-token chunk's four streams (29 MB) read for the statistic,
for the projection, for the mixture and for the write-back and written once, in each of 12 sub-layers."""
from lib import scope_trace as st
from lib import scope_trace_hc as sth

NAME, UNIT, LAYER, MOVES, SOURCE = "hc_prefill_share.mhc", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sth.prefill_share(events)
