"""Host time of a request's prefill chunks, summed, as the engine's flight recorder
spans them (`timing["phases"]["prefill-chunk"]`): median over the window."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "prefill_ms_p50.chat", "ms", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_open",)


def read(record):
    return stats.pctl([r["prefill_s"] * 1e3 for r in rows.in_window(record) if r["prefill_s"] is not None], 0.5)
