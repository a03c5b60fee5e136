"""How long after its last operation the device waited for the host's wait to return: per
`rt.engine.readback.wait` span of the traced window's decode rounds (`x.block_until_ready()`), the
time inside it in which no operation ran on the device; the median. The wake-up of the stepper
thread, whatever the pull's bytes: the copy is `readback_copy_ms_p50.serve`."""
from lib import loop_trace as lt, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "readback_wake_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = lt.for_record(record)
    return None if events is None else stats.pctl(lt.wake_ms_each(events), 0.5)
