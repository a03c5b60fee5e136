"""Device self time, and the count of device operations, under the scope `hc` that a block with a
hyper-connected residual writes round every sub-layer (`models/xing4.py`: `hc/map` the coefficients and
their Sinkhorn steps, `hc/pre` the mixture a sub-layer reads, `hc/post` the write-back to the streams;
PERF.md §3). `hc` is a sibling of `attn` and `mlp` and never inside one of `lib/scope_trace.py`'s fixed
`INNER`, which has no such name, so that file's readers cannot see it: this is its sum over one more
name, on the same events, program executions and self times; what needs no list (`DECODE`, `PREFILL`,
`decode_steps`, `program_ns`, `for_record`) is taken from it. Where the program wrote no such scope, as
one without the block has not, the sums are empty and the readers return nothing."""
from __future__ import annotations

import bisect
import re

from lib import program_trace as pt
from lib import scope_trace as st

SCOPE = "hc"
PARTS = ("map", "pre", "post")
_CACHE = {}


def part_of(path: str):
    """`map`, `pre` or `post` (or "" where `hc` is the last scope) for an operation under `hc`, else None."""
    parts = pt.scope_parts(path)
    if SCOPE not in parts:
        return None
    after = parts[parts.index(SCOPE) + 1:]
    return after[0] if after and after[0] in PARTS else ""


def by_program_and_part(events) -> dict:
    """{(program, part of `hc` or None): [self ns, operations]} over the program executions wholly
    inside the traced window, each operation's time less the operations nested in it."""
    key = id(events)
    if key in _CACHE:
        return _CACHE[key]
    out, w = {}, pt.window_of(events)
    runs = sorted((m for m in events["modules"] if w and pt._inside(m[1], m[2], events, True)), key=lambda m: m[1])
    starts = [m[1] for m in runs]
    stack, rows = [], []  # as `scope_trace.by_program_and_scope`
    inside = [(path, s, s + d) for _, path, s, d in events["ops"] if w and s >= w[0] and s + d <= w[1]]
    for path, a, b in sorted(inside, key=lambda e: (e[1], e[1] - e[2])):
        while stack and stack[-1][0] < b:
            stack.pop()
        if stack:
            rows[stack[-1][1]][2] -= b - a
        rows.append([path, a, b - a])
        stack.append([b, len(rows) - 1])
    for path, a, ns in rows:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < runs[i][1] + runs[i][2]:
            cell = out.setdefault((runs[i][0], part_of(path)), [0.0, 0])
            cell[0] += ns
            cell[1] += 1
    _CACHE.clear()
    _CACHE[key] = out
    return out


def hc_totals(events, programs: str) -> tuple:
    """(self ns, operations) under `hc` in the programs that match."""
    found = [v for (p, part), v in by_program_and_part(events).items() if part is not None and re.fullmatch(programs, p)]
    return sum(v[0] for v in found), sum(v[1] for v in found)


def ms_per_decode_step(events):
    """Device self milliseconds a decode step under `hc`; None where no operation of the decode programs carries it."""
    (ns, _), steps = hc_totals(events, st.DECODE), st.decode_steps(events)
    return ns / 1e6 / steps if ns > 0 and steps else None


def ops_per_decode_step(events):
    """Device operations a decode step under `hc` (fusions, products, kernels, copies: every event of the
    operations line that carries the scope); None where none does."""
    (_, ops), steps = hc_totals(events, st.DECODE), st.decode_steps(events)
    return ops / steps if ops and steps else None


def prefill_share(events):
    """Share (%) of the prefill programs' device time under `hc`; None where none is."""
    (ns, _), total = hc_totals(events, st.PREFILL), st.program_ns(events, st.PREFILL)
    return 100.0 * ns / total if ns > 0 and total else None
