"""Reductions over a serve run's request rows that several metric readers share.

A row's times are seconds on one monotonic clock; `record["window"]` is the measured
window on it. A request belongs to the window by when it was due (open loop) or sent
(closed loop).
"""

from __future__ import annotations


def anchor(row: dict) -> float:
    return row["due"] if row["due"] is not None else row["sent"]


def in_window(record: dict) -> list:
    lo, hi = record["window"]
    return [r for r in record["rows"] if lo <= anchor(r) < hi]


def ttft_from_due_s(row: dict, window_end: float):
    """Seconds from when the request was due to its first token. A request that was
    rejected, failed or had no token by the time the run ended counts as having waited
    until then: it is never left out."""
    if row["ttft_s"] is None:
        return max(0.0, window_end - anchor(row)) + 1.0
    return (row["sent"] - anchor(row)) + row["ttft_s"]


def tpot_s(row: dict):
    """A request's mean gap between output tokens, over what it produced."""
    if row["ttft_s"] is None or row["n_out"] < 2:
        return None
    return (row["latency_s"] - row["ttft_s"]) / (row["n_out"] - 1)


def decode_interval(row: dict):
    """[first token, last token] of a request on the rows' clock, or None."""
    if row["ttft_s"] is None:
        return None
    return row["sent"] + row["ttft_s"], row["sent"] + row["latency_s"]


def overlap(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def decode_seconds(record: dict):
    """(row, seconds of its decoding inside the window, seconds of its decoding in all)
    for every request that produced a token."""
    lo, hi = record["window"]
    for r in record["rows"]:
        span = decode_interval(r)
        if span is not None:
            yield r, overlap(span[0], span[1], lo, hi), span[1] - span[0]


def ttft_values_ms(record: dict) -> list:
    hi = record["window"][1]
    return [ttft_from_due_s(r, hi) * 1e3 for r in in_window(record)]


def tpot_values_ms(record: dict) -> list:
    vals = (tpot_s(r) for r in in_window(record))
    return [v * 1e3 for v in vals if v is not None]
