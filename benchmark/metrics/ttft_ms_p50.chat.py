"""The median beside the 90th percentile: it says whether the tail or the body moved."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "ttft_ms_p50.chat", "ms", "scheduler", "tpot_ms_p90", "host_clock"
DRIVERS = ("serve_open",)


def read(record):
    return stats.pctl(rows.ttft_values_ms(record), 0.5)
