"""Device self time by program and by a scope inside the model's layers (`indexer`, `select`,
`latent`, `window`, `router`, `experts`, `shared_expert`: the names a block writes inside
`attn` and `mlp`, PERF.md §3), from the events `lib/program_trace.py` loads. An operation
belongs to the program execution it starts in. Where the program wrote no such scope, as one
without the block has not, the sums are empty and the readers return nothing."""
from __future__ import annotations

import bisect
import os
import re
import sys

if not __package__:  # run as a script: `lib` is this file's directory
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import program_trace as pt  # noqa: E402

INNER = ("indexer", "select", "latent", "window", "router", "experts", "shared_expert")
DECODE = r"jit_rt_decode(_multi_n\d+)?"
PREFILL = r"jit_rt_prefill_b\d+"
_CACHE = {}


def inner_scope(path: str):
    """The innermost of INNER in an operation's scope path, or None."""
    found = None
    for part in pt.scope_parts(path):
        if part in INNER:
            found = part
    return found


def by_program_and_scope(events) -> dict:
    """{(program, inner scope or None): self ns} over the program executions wholly inside the
    traced window, each operation's time less the operations nested in it."""
    key = id(events)
    if key in _CACHE:
        return _CACHE[key]
    out, w = {}, pt.window_of(events)
    runs = sorted((m for m in events["modules"] if w and pt._inside(m[1], m[2], events, True)), key=lambda m: m[1])
    starts = [m[1] for m in runs]
    stack, rows = [], []  # as `program_trace.self_times`, keeping each event's start
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
            k = (runs[i][0], inner_scope(path))
            out[k] = out.get(k, 0.0) + ns
    _CACHE.clear()
    _CACHE[key] = out
    return out


def scope_ns(events, programs: str, scope: str) -> float:
    return sum(ns for (p, s), ns in by_program_and_scope(events).items() if s == scope and re.fullmatch(programs, p))


def program_ns(events, programs: str) -> float:
    return sum(ns for (p, _), ns in by_program_and_scope(events).items() if re.fullmatch(programs, p))


def for_record(record):
    """The traced window's events for a scope reader (`program_trace.for_record`), or None. The readers
    ask here: they read a block's inner scopes and find none in another block's trace."""
    return pt.for_record(record)


def decode_steps(events) -> int:
    return sum(pt.steps_of(m[0]) for m in pt.executions(events, "jit_rt_decode") if re.fullmatch(DECODE, m[0]))


def scope_ms_per_decode_step(events, scope: str):
    """Device self milliseconds a decode step under `scope`; None where no operation of the
    decode programs carries it."""
    ns, steps = scope_ns(events, DECODE, scope), decode_steps(events)
    return ns / 1e6 / steps if ns > 0 and steps else None


def main(argv) -> int:
    """`python benchmark/lib/scope_trace.py <trace dir>`: device self ms an execution by program
    and inner scope, over the executions wholly inside the traced window."""
    events = pt.load_events(pt.find_xplane(argv[1]))
    if not pt.window_of(events):
        print("no traced window", file=sys.stderr)
        return 1
    table = by_program_and_scope(events)
    for program in sorted({p for p, _ in table}):
        runs = [m for m in pt.executions(events, program) if m[0] == program]
        total = sum(ns for (p, _), ns in table.items() if p == program)
        print(f"{program}: {len(runs)} executions, {total / 1e6 / max(len(runs), 1):.3f} ms each")
        for (p, scope), ns in sorted(table.items(), key=lambda kv: -kv[1]):
            if p == program:
                print(f"    {ns / 1e6 / max(len(runs), 1):9.3f} ms  {100 * ns / total:5.1f}%  {scope or '(outside the inner scopes)'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
