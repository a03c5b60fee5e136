"""Time from when a closed-loop client sent a request to its first token, median over the
requests sent in the window: the wait for the prompts ahead of it in the queue and its own
6 to 24 chunks of 1024 tokens. What these users feel first; seconds here, so a per-layer
metric beside `prefill_dev_ms_per_ktok.longctx`, which is the device's part of it."""
from lib import rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "ttft_ms_p50.longctx", "ms", "scheduler", "serve_out_tok_s", "host_clock"
DRIVERS = ("serve_closed",)


def read(record):
    return stats.pctl(rows.ttft_values_ms(record), 0.5)
