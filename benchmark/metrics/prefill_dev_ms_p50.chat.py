"""Device time of one prefill chunk: the median duration of a prefill program's execution
(`jit_rt_prefill_b<bucket>`, any bucket) in the traced window. Every decoding slot waits
for it."""
from lib import program_trace as pt, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "prefill_dev_ms_p50.chat", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_open",)


def read(record):
    events = pt.for_record(record)
    if events is None:
        return None
    return stats.pctl([m[2] / 1e6 for m in pt.executions(events, "jit_rt_prefill_b")], 0.5)
