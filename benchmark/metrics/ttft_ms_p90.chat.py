"""Time from when a request was due to its first token, 90th percentile over every
request due in the window. A rejected or failed request counts as still waiting.

Per layer and not end to end: with 12 slots and 32 requests in a window, the cycle's
burst fills every slot, and the requests that then wait for a slot wait for the end of a
request that has run for 20 s; its end moves by 0.4 s from run to run, so this tail read
612 to 793 ms over 16 runs of one code (PERF.md, PR 23)."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "ttft_ms_p90.chat", "ms", "scheduler", "tpot_ms_p90", "host_clock"
DRIVERS = ("serve_open",)


def read(record):
    return stats.pctl(rows.ttft_values_ms(record), 0.9)
