"""How much of the device's idle time the program can name: the share of the traced window's
device-idle seconds that lie inside an `rt.*` span of the stepper thread other than
`rt.engine.iter` itself (plan, idle, prefill, attach, kv_insert, dispatch, readback, sample).
What is left is time between spans, or spans cut by the window's edges."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "idle_named_share.serve", "%", "scheduler", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = pt.for_record(record)
    if events is None or not pt.stepper_spans(events):
        return None
    by_span = pt.idle_by_span(events)
    total = sum(by_span.values())
    named = sum(s for name, s in by_span.items() if name not in (pt.NO_SPAN, pt.ITER_SPAN))
    return 100.0 * named / total if total > 0 else None
