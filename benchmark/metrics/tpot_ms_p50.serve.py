"""Median over requests of the mean gap between output tokens: the time a decode step
takes as a request sees it, prefills of other requests included."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "tpot_ms_p50.serve", "ms", "engine", "tpot_ms_p90", "host_clock"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    return stats.pctl(rows.tpot_values_ms(record), 0.5)
