"""Host time of one round's sampling: the median `rt.engine.sample` span of the decode and
verify rounds of the traced window (mask, `_sample_host` row by row, bookkeeping, `_emit`).
The one-row sample that ends a prefill lies inside `rt.engine.prefill` and is left out."""
from lib import program_trace as pt, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "sample_ms_p50.chat", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_open",)


def read(record):
    events = pt.for_record(record)
    if events is None:
        return None
    rounds = pt.outside(pt.spans_named(events, "rt.engine.sample"), pt.spans_named(events, "rt.engine.prefill", whole=False))
    return stats.pctl([e[2] / 1e6 for e in rounds], 0.5)
