"""How late after its due time the generator sent each request: 90th percentile. A
starved generator would otherwise read as a fast server."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "gen_late_ms_p90.chat", "ms", "load generator", "tpot_ms_p90", "host_clock"
DRIVERS = ("serve_open",)


def read(record):
    return stats.pctl([(r["sent"] - r["due"]) * 1e3 for r in rows.in_window(record)], 0.9)
