"""Host time of an engine iteration that the device did not hide: per `rt.engine.iter` span
of the traced window, its duration less the time an operation ran on the device inside
it; the median."""
from lib import program_trace as pt, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "iter_host_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = pt.for_record(record)
    if events is None:
        return None
    iters = [[e[1], e[1] + e[2]] for e in pt.spans_named(events, pt.ITER_SPAN)]
    idle = pt.idle_intervals(events, *pt.window_of(events))
    return stats.pctl([ns / 1e6 for ns in pt.overlap_each(iters, idle)], 0.5)
