"""Device self time a decode step under the scope `hc` of a block whose residual is several streams
mixed by hyper-connections (`models/xing4.py`: in each of a layer's two sub-layers the projection of
the streams and their statistic, the coefficients with their Sinkhorn steps, the mixture the sub-layer
reads and the write-back), in the decode programs wholly inside the traced window
(`lib/scope_trace_hc.py`). Latency, not bytes: 48 tokens' streams are 1.4 MB."""
from lib import scope_trace as st
from lib import scope_trace_hc as sth

NAME, UNIT, LAYER, MOVES, SOURCE = "hc_dev_ms_per_step.mhc", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    return None if events is None else sth.ms_per_decode_step(events)
