"""A request's mean gap between output tokens, (latency - ttft) / (tokens - 1), 90th
percentile over every request of the window that produced two tokens or more."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "tpot_ms_p90", "ms", "end to end", None, "host_clock"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    return stats.pctl(rows.tpot_values_ms(record), 0.9)
