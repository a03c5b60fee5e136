"""Bytes a decode step needs (the block's costs module, `lib/blocks.py`: every matmul weight
once in bf16, plus the K and V rows live in the slots, averaged over the window) over the
chip's published bandwidth, over the time a decode step took as requests saw it (median
TPOT). An end-to-end utilisation, not a kernel's roofline share. One chip only: how a
server over several chips divides the bytes (tensor-parallel shards, or replicas that
each read them all) is not in the record, so there is no peak to take it against."""
from lib import blocks, rows, stats

NAME, UNIT, LAYER, MOVES, SOURCE = "decode_hbm_util.serve", "%", "engine", "tpot_ms_p90", "host_clock"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    step_ms = stats.pctl(rows.tpot_values_ms(record), 0.5)
    if not step_ms or record["chips"] != 1:
        return None
    # a request holds its prompt's rows and, on average over its decoding, half its output's
    live_rows = sum(inside * (r["prompt_len"] + r["n_out"] / 2.0)
                    for r, inside, _ in rows.decode_seconds(record)) / record["window_s"]
    need_s = blocks.costs(record).decode_step_bytes(record["model"], live_rows) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / (step_ms / 1e3)
