"""Device self time by program and by the scopes a `granite_hybrid` layer writes inside `attn`
(`in_proj`, `conv`, `ssm`, `gate_norm`, `out_proj` in a mamba layer; `kv_attn` round the cached
products of an attention layer: PERF.md §3). `lib/scope_trace.py` reads a fixed list of inner
scopes (`INNER`, another block's), so this is its sum over another list, on the same events,
program executions and self times; what needs no list (`DECODE`, `PREFILL`, `decode_steps`,
`program_ns`, `for_record`) is taken from it. Where the program wrote no such scope, as one
without the block has not, the sums are empty and the readers return nothing."""
from __future__ import annotations

import bisect
import re

from lib import program_trace as pt
from lib import scope_trace as st

INNER = ("in_proj", "conv", "ssm", "gate_norm", "out_proj", "kv_attn")
_CACHE = {}


_LAYER = re.compile(r"layer_(\d+)")


def by_program_layer_and_scope(events) -> dict:
    """{(program, layer index or None, innermost of INNER or None): self ns} over the program
    executions wholly inside the traced window, each operation's time less the operations nested
    in it. An operation the compiler made itself (a layout copy) has no layer and no scope."""
    key = id(events)
    if key in _CACHE:
        return _CACHE[key]
    out, w = {}, pt.window_of(events)
    runs = sorted((m for m in events["modules"] if w and pt._inside(m[1], m[2], events, True)), key=lambda m: m[1])
    starts = [m[1] for m in runs]
    stack, rows = [], []
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
            found = layer = None
            for part in pt.scope_parts(path):
                if part in INNER:
                    found = part
                elif layer is None and _LAYER.fullmatch(part):
                    layer = int(part[6:])
            k = (runs[i][0], layer, found)
            out[k] = out.get(k, 0.0) + ns
    _CACHE.clear()
    _CACHE[key] = out
    return out


def by_program_and_scope(events) -> dict:
    """The same summed over the layers: {(program, innermost of INNER or None): self ns}."""
    out = {}
    for (program, _, scope), ns in by_program_layer_and_scope(events).items():
        out[program, scope] = out.get((program, scope), 0.0) + ns
    return out


def scope_ns(events, programs: str, scopes: tuple) -> float:
    return sum(ns for (p, s), ns in by_program_and_scope(events).items() if s in scopes and re.fullmatch(programs, p))


def layers_ms_per_decode_step(events, layers):
    """Device self milliseconds a decode step in everything the layers `layers` run (their norms,
    mixers and MLPs); None where the decode programs name no such layer."""
    ns = sum(t for (p, layer, _), t in by_program_layer_and_scope(events).items()
             if layer in layers and re.fullmatch(st.DECODE, p))
    steps = st.decode_steps(events)
    return ns / 1e6 / steps if ns > 0 and steps else None


def ms_per_decode_step(events, scopes: tuple):
    """Device self milliseconds a decode step under `scopes`; None where no operation of the
    decode programs carries one of them."""
    ns, steps = scope_ns(events, st.DECODE, scopes), st.decode_steps(events)
    return ns / 1e6 / steps if ns > 0 and steps else None
