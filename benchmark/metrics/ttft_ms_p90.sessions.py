"""Time from when a closed-loop client sent a request to its first token, 90th percentile over
the requests sent in the window (a request still waiting at the window's end counts as waiting
until then): the wait for a free iteration and the prompt's chunks, each beside a decode step of
the other 47 slots. Per layer, never end to end."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "ttft_ms_p90.sessions", "ms", "scheduler", "serve_out_tok_s", "host_clock"
DRIVERS = ("serve_closed",)


def read(record):
    return stats.pctl(rows.ttft_values_ms(record), 0.9)
