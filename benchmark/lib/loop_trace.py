"""What the engine's loop says of itself in a profiler trace (PERF.md §3; PR 36): why a
plan ran fewer decode steps than `multi_step` (`limit` on `rt.engine.iter`), and the parts
of the three coarse spans of a decode round (`rt.engine.readback.wait` / `.copy`,
`rt.engine.dispatch.args` / `.call`, `rt.engine.sample.draw` / `.emit`). Reductions over
`lib/program_trace.py`'s events for the readers of `metrics/`; each gives None on a trace
whose program wrote no such name (a parent of PR 36), and raises nothing.

    python benchmark/lib/loop_trace.py <trace dir or .xplane.pb>

prints the decode iterations by `limit`, the device's idle time inside a readback's wait by
the pull's bytes, the medians of the six parts, and the requests' instants with the waits
they carry (PERF.md §5 is written from it).

A decode round's spans are those inside an `rt.engine.iter` and outside an
`rt.engine.prefill`, as `sample_ms_p50.chat` takes them: the one-row pull and sample that
end a prompt's last chunk lie inside its `rt.engine.prefill` and are left out.
"""

from __future__ import annotations

import os
import sys

if not __package__:  # run as a script: `lib` is this file's directory
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import program_trace as pt, stats  # noqa: E402

HELD_BY_PREFILL = ("chunk", "prefilling", "queue")  # an admission in the plan, or waiting for one
WAIT_SPAN = "rt.engine.readback.wait"


def for_record(record):
    """The traced run's events, or None for an untraced one. The readers of `metrics/` come here and
    import no `pt` of their own: `tests/test_program_trace.py` takes every reader that has one for a
    reader of PR 24's recorded traces, which hold none of these names."""
    return pt.for_record(record)


def decode_iters(events):
    """The attributes of the window's `rt.engine.iter` spans whose plan had a slot decoding,
    where the program says what held the plan (`limit`); [] on a trace that does not."""
    return [e[3] for e in pt.spans_named(events, pt.ITER_SPAN)
            if "limit" in e[3] and int(e[3].get("decode_slots", 0)) > 0]


def limit_share(events, limits):
    """Share (%) of the decode iterations whose `limit` is one of `limits`; None where the
    trace names no limit."""
    iters = decode_iters(events)
    if not iters:
        return None
    return 100.0 * sum(1 for attrs in iters if attrs["limit"] in limits) / len(iters)


def round_spans(events, name):
    """The stepper's spans of that name in the window's decode rounds."""
    return pt.outside(pt.spans_named(events, name), pt.spans_named(events, "rt.engine.prefill", whole=False))


def round_ms_p50(events, name):
    """Median duration, ms, of that span over the decode rounds; None where there is none."""
    return stats.pctl([e[2] / 1e6 for e in round_spans(events, name)], 0.5)


def wake_by_bytes(events):
    """{bytes of the pull: [ms the device stood idle inside its `rt.engine.readback.wait`]} over
    the decode rounds' readbacks: from the device's last operation to the host's return from
    the wait. The wake-up of the stepper thread, not the bytes (the copy is the next span):
    one that does not grow with the bytes is not the copy's."""
    waits = sorted([e[1], e[1] + e[2]] for e in round_spans(events, WAIT_SPAN))
    if not waits:
        return {}
    idle = pt.idle_intervals(events, *pt.window_of(events))
    pulls = round_spans(events, "rt.engine.readback")
    out = {}
    for (a, b), ns in zip(waits, pt.overlap_each(waits, idle)):
        nbytes = next((int(p[3].get("bytes", 0)) for p in pulls if p[1] <= a and b <= p[1] + p[2]), 0)
        out.setdefault(nbytes, []).append(ns / 1e6)
    return out


def wake_ms_each(events):
    """The same idle milliseconds, one a decode round's wait, whatever the bytes."""
    return [ms for each in wake_by_bytes(events).values() for ms in each]


def table(events):
    """The decode iterations by `limit`: {limit: {"iterations", "mean_steps", "tokens",
    "tokens_possible"}} (PERF.md §5's table); {} on a trace that names no limit."""
    out = {}
    for attrs in decode_iters(events):
        row = out.setdefault(attrs["limit"], {"iterations": 0, "steps": 0, "tokens": 0, "tokens_possible": 0})
        slots, steps = int(attrs["decode_slots"]), int(attrs["steps"])
        row["iterations"] += 1
        row["steps"] += steps
        row["tokens"] += slots * steps
        row["tokens_possible"] += slots * int(attrs["steps_max"])
    for row in out.values():
        row["mean_steps"] = row.pop("steps") / row["iterations"]
    return out


def main(argv) -> int:
    path = argv[1]
    events = pt.load_events(pt.find_xplane(path) if os.path.isdir(path) else path)
    if pt.window_of(events) is None:
        print(f"{path}: no bench.window span")
        return 1
    print("decode iterations by limit: count, mean steps, decode tokens planned of decode_slots x steps_max")
    for limit, row in sorted(table(events).items(), key=lambda kv: -kv[1]["iterations"]):
        print(f"  {row['iterations']:5d}  {row['mean_steps']:5.2f}  {row['tokens']:7d} of {row['tokens_possible']:7d}  {limit}")
    print("device idle inside rt.engine.readback.wait by the pull's bytes: count, median ms, mean ms, total s")
    for nbytes, ms in sorted(wake_by_bytes(events).items()):
        print(f"  {len(ms):5d}  {stats.pctl(ms, 0.5):7.3f}  {sum(ms) / len(ms):7.3f}  {sum(ms) / 1e3:8.4f}  {nbytes} bytes")
    print("decode rounds' spans: count, median ms, mean ms, total s")
    for name in ("rt.engine.dispatch", "rt.engine.dispatch.args", "rt.engine.dispatch.call", "rt.engine.readback",
                 WAIT_SPAN, "rt.engine.readback.copy", "rt.engine.sample", "rt.engine.sample.draw", "rt.engine.sample.emit"):
        ms = [e[2] / 1e6 for e in round_spans(events, name)]
        if ms:
            print(f"  {len(ms):5d}  {stats.pctl(ms, 0.5):7.3f}  {sum(ms) / len(ms):7.3f}  {sum(ms) / 1e3:8.4f}  {name}")
    print("requests' instants in the window: count, median ms of each duration they carry")
    for name, keys in (("rt.sched.admit", ("queue_us",)), ("rt.engine.first_token", ("prefill_wait_us", "ttft_us")), ("rt.engine.finish", ())):
        rows = [e[3] for e in pt.spans_named(events, name)]
        medians = "  ".join(f"{key[:-3]} {stats.pctl([int(r[key]) / 1e3 for r in rows if key in r], 0.5)}" for key in keys)
        print(f"  {len(rows):5d}  {name}  {medians}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
