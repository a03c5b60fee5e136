"""Time a request waited in the scheduler's queue before its first prefill chunk, as
the engine's flight recorder has it (`timing["queue_s"]`): median over the window."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "queue_ms_p50.chat", "ms", "scheduler", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_open",)


def read(record):
    return stats.pctl([r["queue_s"] * 1e3 for r in rows.in_window(record) if r["queue_s"] is not None], 0.5)
