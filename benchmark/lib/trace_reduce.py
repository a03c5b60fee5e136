"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports: the
seconds in which an operation ran on the device, the traced window, the device
operations that took most time, and the longest idle gaps, each named by what the
host was doing.

Two stages, so that the second can be checked on a small recorded trace
(`benchmark/tests/trace_events.json`):

- `load_events(path)` reads the file with `jax.profiler.ProfileData` and keeps, per
  device plane (`/device:TPU:<n>`), the events of its `XLA Ops` line (one per operation
  executed, under the name the compiler gave it) and of its `XLA Modules` line (one per
  program executed), and from the host plane the benchmark's own
  `jax.profiler.TraceAnnotation` spans (`bench.*`) and the profiler's Python-function
  events (`$file.py:line name`) of 0.1 ms and longer. The program has no spans of its
  own yet. All on one clock, in nanoseconds.
- `reduce_events(events, chips)` takes the union of the operation intervals of each
  device (operations nest and overlap, so a sum would count time twice), averages the
  busy seconds over the chips used, and names each idle gap of the first device by what
  the host was doing: the `bench.*` span that covers the gap, or else the innermost
  Python function that does (in a serve cell the engine's own thread does the work, and
  the function's name is all there is until the program has spans). The window is the driver's
  `bench.window` span: the profiler's own start and stop take tenths of a second in
  which the device waits for nothing the program does. Operations are ranked by self
  time (a `while` that holds the scanned layers would otherwise head the list with its
  children's time).

An operation's name in the trace is its whole HLO text; what is kept is the part before
` = `, without the `%` (`fusion.221`, `attn._flash_bhsd.32`, `while.11`), behind the name
of the program it ran in (`jit_step:fusion.221`): two programs both have a `fusion.1`.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
PY_PREFIX = "$"
PY_MIN_NS = 100_000
COVERS = 0.5  # share of a gap a host event has to cover to name it


def short_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")[:120]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    t_min, t_max = None, None

    def see(start, dur):
        nonlocal t_min, t_max
        t_min = start if t_min is None else min(t_min, start)
        t_max = start + dur if t_max is None else max(t_max, start + dur)

    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                key = "ops" if line.name == OPS_LINE else "modules"
                for e in line.events:
                    dev[key].append([short_name(e.name), int(e.start_ns), int(e.duration_ns)])
                    see(e.start_ns, e.duration_ns)
        elif plane.name == HOST_PLANE:
            # Python functions are kept from the busiest Python thread alone: the one that
            # drives the device. Another thread asleep in `poll` covers every gap too.
            busiest = []
            for line in plane.lines:
                funcs = []
                for e in line.events:
                    see(e.start_ns, e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
                    elif e.name.startswith(PY_PREFIX):
                        funcs.append(e)
                if len(funcs) > len(busiest):
                    busiest = funcs
            host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in busiest if e.duration_ns >= PY_MIN_NS)
    return {"devices": devices, "host": host, "window": [int(t_min or 0), int(t_max or 0)],
            "planes": [plane.name for plane in data.planes]}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(ops: list) -> dict:
    """Nanoseconds per name, each event less the events nested inside it."""
    totals, stack = {}, []  # stack of [end, name]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            totals[stack[-1][1]] -= min(dur, stack[-1][0] - start)
        totals[name] = totals.get(name, 0) + dur
        stack.append([start + dur, name])
    return totals


def _name_gap(a: int, b: int, host: list) -> str:
    """The `bench.*` span that covers most of the gap, if that is half of it or more;
    failing that the innermost (shortest) Python function that covers half of it. (A gap
    between two engine steps straddles two calls of the step function, and the loop that
    calls them was entered before the trace began, so nothing covers it whole.)"""
    span, span_cover, func = None, 0, None
    for name, start, dur in host:
        cover = min(b, start + dur) - max(a, start)
        if name == WINDOW_SPAN or cover <= 0:
            continue
        if name.startswith(SPAN_PREFIX):
            if cover > span_cover:
                span, span_cover = name, cover
        elif cover >= COVERS * (b - a) and (func is None or dur < func[0]):
            func = (dur, name)
    if span is not None and span_cover >= COVERS * (b - a):
        return span
    if func is not None:
        return func[1].lstrip(PY_PREFIX)
    return span or "no host span"


def _with_module(ops: list, modules: list) -> list:
    """Each operation named `<program>:<op>` by the program event it ran inside."""
    import bisect

    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for op, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < mods[i][1] + mods[i][2]
        out.append([(mods[i][0].split("(")[0] + ":" + op) if inside else op, s, d])
    return out


def reduce_events(events: dict, chips: int = 1) -> dict:
    lo, hi = events["window"]
    for name, start, dur in events["host"]:
        if name == WINDOW_SPAN:
            lo, hi = start, start + dur
    names = sorted(events["devices"])[:chips]
    if not names:
        raise ValueError("the trace holds no device plane: nothing ran on a TPU under the profiler; planes: "
                         + ", ".join(events.get("planes", [])))
    busy_ns, totals, merged_first = 0, {}, None
    for n, name in enumerate(names):
        dev = events["devices"][name]
        ops = [[op, max(s, lo), min(s + d, hi) - max(s, lo)]
               for op, s, d in _with_module(dev["ops"], dev["modules"]) if s < hi and s + d > lo]
        merged = _union([[s, s + d] for _, s, d in ops])
        busy_ns += sum(b - a for a, b in merged)
        if n == 0:
            merged_first = merged
            totals = _self_times(ops)
    gaps = []
    edges = [[lo, lo]] + merged_first + [[hi, hi]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    module_totals = {}
    for mod, s, d in events["devices"][names[0]]["modules"]:
        if s < hi and s + d > lo:
            module_totals[mod] = module_totals.get(mod, 0) + min(s + d, hi) - max(s, lo)
    return {
        "busy_s": busy_ns / len(names) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[op, ns / 1e9] for op, ns in
                       sorted(totals.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_name_gap(a, b, events["host"]), ns / 1e9] for ns, a, b in gaps[:10]],
        "modules": [[m, ns / 1e9] for m, ns in
                    sorted(module_totals.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), chips)
