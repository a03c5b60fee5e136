"""From a profiler trace (`.xplane.pb`) to the program's own names: the `rt.*` host spans
with their attributes, the programs the device ran (`XLA Modules`) and its operations
(`XLA Ops`) with the scope path each carries, all on one clock, and the reductions the
per-layer metrics of `metrics/` read. `lib/trace_reduce.py` stays what it is (busy
seconds, the ledger's `breakdown`); this file reads what the program names itself.

    python benchmark/lib/program_trace.py <trace dir or .xplane.pb>

prints the device-idle seconds of the window by innermost `rt.*` span and the device
seconds by program, by scope and by collective operation (PERF.md §5 is written from it).

One device's lines are read: the first (`/device:TPU:0`). In a cell over several chips
that is right for what these reductions give, which is per chip: an SPMD program runs
the same operations on every chip, a scope's or a kernel's time is that chip's, and a
flash call's shapes in its HLO text are already the chip's share of the batch and the
heads. Busy seconds over all the chips are `lib/trace_reduce.py`'s.

Where things sit in the file (looked at by hand, PERF.md §6, PR 24):

- a host span made by `jax.profiler.TraceAnnotation(name, **attrs)` is an event of a
  thread's line in the `/host:CPU` plane; its attributes are the event's own stats;
- a program execution is an event of the `XLA Modules` line of `/device:TPU:<n>`, named
  `jit_<function>(<fingerprint>)`;
- an operation is an event of the `XLA Ops` line; its name is its whole HLO text
  (`%fusion.221 = ... fusion(...)`, a Pallas kernel `%flash_bwd.12 = ... custom-call(...)`).
  The scope path is not on the event: it is the `tf_op` stat of the event's *metadata*
  (`jit(step)/transpose(jvp(Transformer))/lm_head/dot_general:`), which
  `jax.profiler.ProfileData` does not hand out. So the file is read here from its wire
  format (`tsl/profiler/protobuf/xplane.proto`), with nothing imported. A fusion has one
  `tf_op`, that of the instruction the compiler named it after: a fusion that spans two
  scopes (the head's weight gradient fused with its optimizer update) counts for one.
  A transformation wraps the outermost scope of a path (`jvp(loss)`,
  `transpose(jvp(Transformer))`), a recomputed copy has `checkpoint` or
  `rematted_computation` in its path: `scope_of` looks through both, so forward,
  recomputed and backward operations of a scope count together.

Two stages, as in `trace_reduce`, so that the second is checked on a small recorded
trace (`benchmark/tests/program_trace_events.json`): `load_events(path)` gives plain
lists, the functions below reduce them. Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import os
import re
import struct
import sys

if not __package__:  # run as a script: `lib` is this file's directory
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib.trace_reduce import (DEVICE_PREFIX, HOST_PLANE, MODULES_LINE, OPS_LINE, WINDOW_SPAN,  # noqa: E402
                              _union, find_xplane, short_name)

SPAN_PREFIX = "rt."
ITER_SPAN = "rt.engine.iter"
NO_SPAN = "(no rt span)"
UNSCOPED = "(no scope)"
# One list for both models (PERF.md §3): the flax modules' names, the engine's own
# model under the same names, and what `parallel/spmd.py` and the engine add.
SCOPES = ("embedding", "attn_norm", "attn", "mlp_norm", "mlp", "final_norm", "lm_head",
          "loss", "optimizer", "sample")
KERNEL_PREFIXES = ("flash_fwd", "flash_bwd")  # operations whose HLO text is kept, for their shapes
# Operations that move data between chips. A v5e's compiled step holds them in four forms
# (looked at in the four-chip cell's trace and its HLO, PERF.md §6, PR 27): under their own
# opcode and synchronous (`%all-reduce.66 = ... all-reduce(`, `%all-gather.327`,
# `%all-to-all.1`); as a fusion the compiler made round one (`%fusion.447 = ... fusion(...),
# kind=kCustom, calls=%all-reduce-scatter.6`: a reduce-scatter is an all-reduce fused with the
# slice of the chip's own part); as the two halves of an asynchronous one under its opcode
# (`%collective-permute-start.1`, `-done.1`); and as the two halves of an asynchronous one the
# compiler fused, whose name alone says what it is (`%async-collective-start.11`,
# `%async-collective-done.11`: here the all-gathers of the scanned layers' weights). A
# synchronous one is on the `XLA Ops` line for as long as it takes: the core waits. Of an
# asynchronous one that line has only the halves, some microseconds each: the issue, and at
# the `-done` the wait for whatever has not landed yet. The transfer itself is a span from
# start to done on another line of the first chip's plane, `Async XLA Ops`, beside the
# compiler's own `copy-start.N` prefetches; it overlaps the operations line, is not device
# time, and is not read here. A matmul fusion that carries a gather along with it
# (`calls=%async_collective_fusion.449`) is computation, and is not counted.
ASYNC_FUSED = "async-collective"
COLLECTIVES = ("reduce-scatter", "all-gather", "all-reduce", "collective-permute", "all-to-all", ASYNC_FUSED)
_COLLECTIVE_OPCODE = re.compile(r" (" + "|".join(COLLECTIVES[:5]) + r")(?:-start|-done)?\(")
_COLLECTIVE_FUSION = re.compile(r" fusion\(.*calls=%?(all-reduce-scatter|" + "|".join(COLLECTIVES[:5]) + ")")


# -- the wire format ----------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited value is a
    `memoryview` of its bytes."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """An XStat as (name, value); a `ref_value` names another stat's metadata."""
    name, value = None, None
    for num, _, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _plane(buf):
    """{"name", "lines": [bytes], "metadata": [map entry bytes], "stat_names": {id:
    name}}: lines and event metadata stay undecoded until a caller wants them."""
    name, lines, metadata, stat_names = "", [], [], {}
    for num, _, val in _fields(buf):
        if num == 2:
            name = bytes(val).decode()
        elif num == 3:
            lines.append(val)
        elif num == 4:
            metadata.append(val)
        elif num == 5:
            key, md = _map_entry(val)
            for n, _, v in _fields(md):
                if n == 2:
                    stat_names[key] = bytes(v).decode()
    return {"name": name, "lines": lines, "metadata": metadata, "stat_names": stat_names}


def _event_metadata(plane, keep_stats=("tf_op",)):
    out = {}
    for entry in plane["metadata"]:
        key, md = _map_entry(entry)
        name, stats = "", {}
        for num, _, val in _fields(md):
            if num == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif num == 5 and keep_stats:
                k, v = _stat(val, plane["stat_names"])
                if k in keep_stats:
                    stats[k] = v
        out[key] = (name, stats)
    return out


def _line(buf):
    """(name#id, timestamp ns, [event bytes]): two Python threads are both `python`."""
    name, t0_ns, events = "", 0, []
    for num, _, val in _fields(buf):
        if num == 1:
            name += f"#{val}"
        elif num == 2:
            name = bytes(val).decode() + name
        elif num == 3:
            t0_ns = _signed(val)
        elif num == 4:
            events.append(val)
    return name, t0_ns, events


def _event(buf, t0_ns, stat_names=None):
    """(metadata id, start ns, duration ns, {stat: value} if `stat_names` is given)."""
    mid = offset_ps = dur_ps = 0
    stats = {}
    for num, _, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            offset_ps = _signed(val)
        elif num == 3:
            dur_ps = _signed(val)
        elif num == 4 and stat_names is not None:
            k, v = _stat(val, stat_names)
            stats[k] = v
    return mid, t0_ns + offset_ps / 1e3, dur_ps / 1e3, stats


def collective_kind(hlo: str):
    """Which of COLLECTIVES an operation's HLO text is, or None."""
    head = hlo.split(", metadata=", 1)[0]
    if short_name(head).startswith(ASYNC_FUSED + "-"):
        return ASYNC_FUSED
    m = _COLLECTIVE_OPCODE.search(head) or _COLLECTIVE_FUSION.search(head)
    if m is None:
        return None
    return "reduce-scatter" if m.group(1) == "all-reduce-scatter" else m.group(1)


def program_name(module: str) -> str:
    """`jit_rt_decode(682187994369778556)` -> `jit_rt_decode`."""
    return module.split("(", 1)[0]


def load_events(path: str) -> dict:
    """{"window": [lo, hi] or None, "spans": [[name, start, dur, attrs, thread]],
    "modules": [[program, start, dur]], "ops": [[op, scope path, start, dur]],
    "hlo": {op: HLO text} for the kernels, "collectives": {op: kind} for the operations
    that move data between chips}: the first device's, and the host's `rt.*` spans and
    `bench.window`."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [_plane(val) for num, _, val in _fields(space) if num == 1]
    out = {"window": None, "spans": [], "modules": [], "ops": [], "hlo": {}, "collectives": {}}
    devices = sorted(p["name"] for p in planes if p["name"].startswith(DEVICE_PREFIX))
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            names = {k: name for k, (name, _) in _event_metadata(plane, ()).items()}
            wanted = {k for k, name in names.items() if name.startswith(SPAN_PREFIX) or name == WINDOW_SPAN}
            for raw in plane["lines"]:
                thread, t0_ns, events = _line(raw)
                for ev in events:
                    if len(ev) and ev[0] == 0x08 and _varint(ev, 1)[0] not in wanted:
                        continue  # the Python tracer's events, by the hundred thousand
                    mid, start, dur, attrs = _event(ev, t0_ns, plane["stat_names"])
                    if mid not in wanted:
                        continue
                    if names[mid] == WINDOW_SPAN:
                        out["window"] = [start, start + dur]
                    else:
                        out["spans"].append([names[mid], start, dur, attrs, thread])
        elif devices and plane["name"] == devices[0]:
            metadata = _event_metadata(plane)
            for raw in plane["lines"]:
                line, t0_ns, events = _line(raw)
                line = line.split("#", 1)[0]
                if line == MODULES_LINE:
                    for ev in events:
                        mid, start, dur, _ = _event(ev, t0_ns)
                        out["modules"].append([program_name(metadata[mid][0]), start, dur])
                elif line == OPS_LINE:
                    for ev in events:
                        mid, start, dur, _ = _event(ev, t0_ns)
                        hlo, stats = metadata[mid]
                        op = short_name(hlo)
                        out["ops"].append([op, stats.get("tf_op", ""), start, dur])
                        if op.startswith(KERNEL_PREFIXES):
                            out["hlo"][op] = hlo
                        elif op not in out["collectives"]:
                            kind = collective_kind(hlo)
                            if kind is not None:
                                out["collectives"][op] = kind
    out["spans"].sort(key=lambda e: e[1])
    out["modules"].sort(key=lambda e: e[1])
    out["ops"].sort(key=lambda e: e[2])
    return out


# -- reductions -----------------------------------------------------------------------

def scope_parts(path: str) -> list:
    """The scopes of an operation's path, each without what a transformation wrapped
    round it: `jit(step)/transpose(jvp(loss))/mul:` -> [step, loss, mul]."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", part) for part in path.rstrip(":").split("/")]


def scope_of(path: str) -> str:
    """The outermost scope of `SCOPES` in the path, or UNSCOPED."""
    for part in scope_parts(path):
        if part in SCOPES:
            return part
    return UNSCOPED


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def overlap_each(intervals, others):
    """For each of the sorted disjoint `intervals`, its overlap with the sorted disjoint
    `others`."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(others) and others[j][1] <= a:
            j += 1
        total, k = 0.0, j
        while k < len(others) and others[k][0] < b:
            total += min(b, others[k][1]) - max(a, others[k][0])
            k += 1
        out.append(total)
    return out


def _inside(start, dur, events, whole):
    """Whether [start, start + dur] lies in the window (wholly, or touching it)."""
    w = window_of(events)
    if w is None:
        return False
    return (w[0] <= start and start + dur <= w[1]) if whole else (start < w[1] and start + dur > w[0])


def window_of(events):
    """[lo, hi] of the `bench.window` span, or None: without it there is no traced
    window to take a share of."""
    w = events.get("window")
    return w if w and w[1] > w[0] else None


def busy_intervals(events, lo, hi):
    return _union([list(_clip(s, s + d, lo, hi)) for _, _, s, d in events["ops"] if s < hi and s + d > lo])


def idle_intervals(events, lo, hi):
    edges = [[lo, lo]] + busy_intervals(events, lo, hi) + [[hi, hi]]
    return [[a, b] for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]


def stepper_spans(events):
    """The `rt.*` spans of the thread that made most of them (the engine's stepper):
    spans of one thread nest, those of two need not."""
    count = {}
    for _, _, _, _, thread in events["spans"]:
        count[thread] = count.get(thread, 0) + 1
    if not count:
        return []
    thread = max(count, key=count.get)
    return [e for e in events["spans"] if e[4] == thread]


def innermost_segments(spans):
    """{name: sorted disjoint [start, end]}: every instant of a thread's spans given to
    the innermost span that covers it."""
    out, stack = {}, []  # stack of [name, end]
    edges = sorted(spans, key=lambda e: (e[1], -e[2]))
    cursor = None

    def give(upto):
        nonlocal cursor
        if stack and upto > cursor:
            out.setdefault(stack[-1][0], []).append([cursor, upto])
        cursor = upto

    for name, start, dur, _, _ in edges:
        while stack and stack[-1][1] <= start:
            give(stack[-1][1])
            stack.pop()
        if cursor is None or not stack:
            cursor = start
        give(start)
        stack.append([name, start + dur])
    while stack:
        give(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(events):
    """Seconds the device stood idle inside the window, by the innermost `rt.*` span of
    the stepper thread at the time; NO_SPAN for the rest. None without a window."""
    w = window_of(events)
    if w is None:
        return None
    idle = idle_intervals(events, *w)
    out, named = {}, 0.0
    for name, segments in innermost_segments(stepper_spans(events)).items():
        ns = sum(overlap_each(segments, idle))
        if ns > 0:
            out[name] = ns / 1e9
            named += ns
    rest = sum(b - a for a, b in idle) - named
    if rest > 0.5:  # half a nanosecond: rounding
        out[NO_SPAN] = rest / 1e9
    return out


def device_seconds_by_program(events):
    w = window_of(events)
    if w is None:
        return None
    out = {}
    for program, start, dur in events["modules"]:
        a, b = _clip(start, start + dur, *w)
        if b > a:
            out[program] = out.get(program, 0.0) + (b - a) / 1e9
    return out


def self_times(ops, lo, hi):
    """[(op, scope path, self ns)] of the operations inside [lo, hi]: each event less
    the events nested inside it (a `while` holds its body's operations)."""
    out, stack = [], []  # stack of [end, index into out]
    inside = [(op, path, *_clip(s, s + d, lo, hi)) for op, path, s, d in ops if s < hi and s + d > lo]
    for op, path, a, b in sorted(inside, key=lambda e: (e[2], e[2] - e[3])):
        while stack and stack[-1][0] < b:  # over, or overlapping by a rounding: not this event's parent
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= b - a
        out.append([op, path, b - a])
        stack.append([b, len(out) - 1])
    return out


def device_seconds_by_scope(events):
    """Device self seconds in the window by scope (forward, recomputed and backward
    copies together), UNSCOPED for operations under none. None without a window."""
    w = window_of(events)
    if w is None:
        return None
    out = {}
    for _, path, ns in self_times(events["ops"], *w):
        scope = scope_of(path)
        out[scope] = out.get(scope, 0.0) + ns / 1e9
    return out


def executions(events, prefix: str, whole: bool = True):
    """[[program, start, dur]] of the programs whose name starts with `prefix`, inside
    the window (wholly, or `whole=False` touching it)."""
    return [m for m in events["modules"] if m[0].startswith(prefix) and _inside(m[1], m[2], events, whole)]


def step_program(events):
    """The program that took most of the window's device time (a train cell's step)."""
    by_program = device_seconds_by_program(events)
    return max(by_program, key=by_program.get) if by_program else None


def kernel_calls(events, kernel: str):
    """[[op, start, dur]] of a named kernel's executions inside the window."""
    return [[op, s, d] for op, _, s, d in events["ops"]
            if re.fullmatch(re.escape(kernel) + r"(\.\d+)?", op) and _inside(s, d, events, True)]


def steps_of(program: str) -> int:
    """Decode steps one execution computes: `jit_rt_decode` 1, `jit_rt_decode_multi_n8` 8."""
    m = re.fullmatch(r"jit_rt_decode_multi_n(\d+)", program)
    return int(m.group(1)) if m else 1


def spans_named(events, name: str, whole: bool = True):
    """The stepper's spans of that name inside the window."""
    return [e for e in stepper_spans(events) if e[0] == name and _inside(e[1], e[2], events, whole)]


def outside(spans, parents):
    """Those of `spans` that lie in none of `parents`."""
    return [e for e in spans if not any(p[1] <= e[1] and e[1] + e[2] <= p[1] + p[2] for p in parents)]


def decode_ms_per_step(events):
    """Device milliseconds of the decode programs (`jit_rt_decode`, `jit_rt_decode_multi_n<k>`)
    wholly inside the window, over the decode steps they computed. None where none ran."""
    runs = [m for m in executions(events, "jit_rt_decode") if re.fullmatch(r"jit_rt_decode(_multi_n\d+)?", m[0])]
    steps = sum(steps_of(m[0]) for m in runs)
    return sum(m[2] for m in runs) / 1e6 / steps if steps else None


def device_seconds_by_collective(events, with_scope: bool = False):
    """Device self seconds in the window by kind of collective operation (COLLECTIVES), or
    by kind and the scope it serves (`all-reduce in loss`): the time the core spends in
    them or waiting for them, which is the part of the communication that no computation
    hides. {} where the program has none (one chip), None without a window."""
    w = window_of(events)
    if w is None:
        return None
    kinds, out = events.get("collectives") or {}, {}
    for op, path, ns in self_times(events["ops"], *w):
        if op in kinds:
            key = f"{kinds[op]} in {scope_of(path)}" if with_scope else kinds[op]
            out[key] = out.get(key, 0.0) + ns / 1e9
    return out


def ms_per_step(events, seconds: float):
    """`seconds` of the window as milliseconds a step: over the executions of the step
    program in the window, those cut by its edges counting for their part. None where no
    whole step lies inside."""
    program, w = step_program(events), window_of(events)
    if program is None:
        return None
    steps = sum(min(s + d, w[1]) - max(s, w[0]) for p, s, d in executions(events, program, whole=False) if p == program)
    whole = [d for p, _, d in executions(events, program) if p == program]
    if not whole:
        return None
    return 1e3 * seconds / (steps / (sum(whole) / len(whole)))


def scope_ms_per_step(events, scopes):
    """Device self milliseconds a step under the given scopes: the window's total over
    the executions of the step program in it. None where no such operation ran."""
    by_scope = device_seconds_by_scope(events)
    total = sum(by_scope.get(scope, 0.0) for scope in scopes) if by_scope else 0.0
    return ms_per_step(events, total) if total > 0 else None


def kernel_ms_per_step(events, kernel: str):
    """Device milliseconds a step in a named kernel, or None where it never ran."""
    calls, program = kernel_calls(events, kernel), step_program(events)
    runs = [m for m in executions(events, program or "") if m[0] == program]
    return sum(d for _, _, d in calls) / 1e6 / len(runs) if calls and runs else None


# -- what a metric reader calls ---------------------------------------------------------

_LOADED = {}


def trace_dir(record) -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".bench_trace", record["cell"])


def for_record(record):
    """The events of this run's trace, read once per process; None where the run has
    no trace (an untraced run, or a profiler that wrote nothing) or no traced window."""
    if "trace" not in record:
        return None
    try:
        path = find_xplane(trace_dir(record))
    except FileNotFoundError:
        return None
    if path not in _LOADED:
        _LOADED[path] = load_events(path)
    events = _LOADED[path]
    return events if window_of(events) else None


def main(argv) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    events = load_events(path)
    if window_of(events) is None:  # a capture no benchmark driver made: the device's whole extent
        times = [(s, s + d) for _, _, s, d in events["ops"]] + [(s, s + d) for _, s, d in events["modules"]]
        if not times:
            print(f"{path}: no bench.window span and no device plane")
            return 1
        events["window"] = [min(a for a, _ in times), max(b for _, b in times)]
        print("no bench.window span: the window is the device's first to last operation")
    w = window_of(events)
    window_s = (w[1] - w[0]) / 1e9
    busy_s = sum(b - a for a, b in busy_intervals(events, *w)) / 1e9
    print(f"{path}\nwindow {window_s:.6f} s, device busy {busy_s:.6f} s, idle {window_s - busy_s:.6f} s "
          f"({100 * (1 - busy_s / window_s):.1f}%)")

    def table(title, rows, total):
        print(f"\n{title}")
        for name, s in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"  {s:10.6f} s  {100 * s / total if total else 0:5.1f}%  {name}")

    table("device idle by innermost rt.* span (share of idle)", idle_by_span(events), window_s - busy_s)
    counts = {}
    for name, _, dur, _, _ in stepper_spans(events):
        c = counts.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += dur / 1e9
    print("\nrt.* spans of the stepper thread: count, seconds, mean ms")
    for name, (n, s) in sorted(counts.items()):
        print(f"  {n:6d}  {s:10.6f} s  {1e3 * s / n:9.3f} ms  {name}")
    by_program = device_seconds_by_program(events)
    table("device seconds by program (share of window)", by_program, window_s)
    runs = {}
    for program, _, dur in executions(events, ""):
        runs.setdefault(program, []).append(dur / 1e6)
    print("\nexecutions wholly inside the window: count, median ms")
    for program, durs in sorted(runs.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {len(durs):6d}  {sorted(durs)[len(durs) // 2]:9.3f} ms  {program}")
    table("device self seconds by scope (share of busy)", device_seconds_by_scope(events), busy_s)
    table("device self seconds by collective operation and the scope it serves (share of busy)",
          device_seconds_by_collective(events, with_scope=True), busy_s)
    for kernel in KERNEL_PREFIXES:
        calls = kernel_calls(events, kernel)
        if calls:
            durs = sorted(d / 1e6 for _, _, d in calls)
            print(f"\n{kernel}: {len(calls)} calls, median {durs[len(durs) // 2]:.3f} ms, total {sum(durs) / 1e3:.6f} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
