"""`lib/loop_trace.py` and its eight readers on hand-made events: three iterations of a closed-loop
cell (a chunk beside a single step, a full multi-step run, a run held by a slot's tail), the parts
of their rounds' spans, and a parent's trace that has none of the new names."""
import importlib.util
import os

import pytest

from lib import loop_trace as lt, program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns
NEW = ("plan_full_steps_share.decode", "plan_held_by_prefill_share.decode", "plan_held_by_tail_share.decode",
       "readback_wake_ms_p50.serve", "readback_copy_ms_p50.serve", "dispatch_args_ms_p50.serve",
       "dispatch_call_ms_p50.serve", "emit_ms_p50.serve")


def span(name, start_ms, dur_ms, **attrs):
    return [name, start_ms * MS, dur_ms * MS, attrs, "python#1"]


def a_round(t, step_ms, wake_ms, copy_ms, nbytes, parts=True, draw_ms=0.0, emit_ms=1.0, args_ms=1.0, call_ms=2.0):
    """The spans of one decode round that starts at `t` ms, and its program on the device:
    it runs from the call's end for `step_ms`, and the host's wait returns `wake_ms` after it."""
    dispatch = args_ms + call_ms
    dev0, dev1 = t + dispatch, t + dispatch + step_ms
    wait1 = dev1 + wake_ms
    sample0 = wait1 + copy_ms
    spans = [span("rt.engine.dispatch", t, dispatch, steps=1, slots=12, rows=4000),
             span("rt.engine.readback", dev0, wait1 + copy_ms - dev0, bytes=nbytes),
             span("rt.engine.sample", sample0, draw_ms + emit_ms, slots=12)]
    if parts:
        spans += [span("rt.engine.dispatch.args", t, args_ms), span("rt.engine.dispatch.call", t + args_ms, call_ms),
                  span("rt.engine.readback.wait", dev0, wait1 - dev0), span("rt.engine.readback.copy", wait1, copy_ms),
                  span("rt.engine.sample.emit", sample0 + draw_ms, emit_ms)]
        if draw_ms:
            spans.append(span("rt.engine.sample.draw", sample0, draw_ms))
    return spans, ["fusion.1", "jit(rt_decode)/layer_0/mlp/dot_general:", dev0 * MS, step_ms * MS], sample0 + draw_ms + emit_ms


def events_of(parts=True):
    """Iteration 1 (0 to 40 ms): a chunk whose last token is pulled inside `rt.engine.prefill`,
    then a single step (wake 3 ms, copy 1.5 ms of 4.4 MB, draw 2 ms); iteration 2: 8 steps
    (wake 2 ms, copy 0.1 ms of 384 bytes); iteration 3: 4 steps under a slot's tail (wake 4 ms)."""
    iter_attrs = lambda **kw: kw if parts else {k: kw[k] for k in ("chunks", "decode_slots", "steps")}  # noqa: E731
    spans, ops = [], []
    # the chunk: its own readback and sample lie inside the prefill span and are not a round's
    spans += [span("rt.engine.prefill", 1, 14, rid="a", tokens=100, bucket=128, last=1),
              span("rt.engine.readback", 3, 10, bytes=370_000), span("rt.engine.sample", 13, 1, slots=1)]
    if parts:
        spans += [span("rt.engine.readback.wait", 3, 9), span("rt.engine.readback.copy", 12, 1)]
    ops.append(["fusion.9", "jit(rt_prefill_b128)/layer_0/mlp/dot_general:", 2 * MS, 5 * MS])
    r1, op1, end1 = a_round(15, 10, 3, 1.5, 4_400_000, parts, draw_ms=2.0)
    spans += r1 + [span(pt.ITER_SPAN, 0, end1 + 0.5, **iter_attrs(chunks=1, decode_slots=11, steps=1, steps_max=8, limit="chunk",
                                                                waiting=0, prefilling=1, unix_us=1))]
    r2, op2, end2 = a_round(40, 60, 2, 0.1, 384, parts)
    spans += r2 + [span(pt.ITER_SPAN, 39.5, end2 + 0.5 - 39.5, **iter_attrs(chunks=0, decode_slots=12, steps=8, steps_max=8, limit="none",
                                                                             waiting=0, prefilling=0, unix_us=2))]
    r3, op3, end3 = a_round(110, 30, 4, 0.1, 192, parts)
    spans += r3 + [span(pt.ITER_SPAN, 109.5, end3 + 0.5 - 109.5, **iter_attrs(chunks=0, decode_slots=12, steps=4, steps_max=8, limit="tail",
                                                                               waiting=0, prefilling=0, unix_us=3))]
    # an iteration that only ran a chunk says `no_decode` and is no decode iteration
    spans.append(span(pt.ITER_SPAN, 150, 5, **iter_attrs(chunks=1, decode_slots=0, steps=1, steps_max=8, limit="no_decode",
                                                          waiting=0, prefilling=1, unix_us=4)))
    ops += [op1, op2, op3]
    return {"window": [0, 160 * MS], "spans": sorted(spans, key=lambda e: e[1]), "modules": [], "ops": sorted(ops, key=lambda e: e[2]),
            "hlo": {}, "collectives": {}}


def test_the_limits_table_and_the_shares():
    events = events_of()
    assert lt.table(events) == {
        "chunk": {"iterations": 1, "mean_steps": 1.0, "tokens": 11, "tokens_possible": 88},
        "none": {"iterations": 1, "mean_steps": 8.0, "tokens": 96, "tokens_possible": 96},
        "tail": {"iterations": 1, "mean_steps": 4.0, "tokens": 48, "tokens_possible": 96}}
    assert lt.limit_share(events, ("none",)) == pytest.approx(100 / 3)
    assert lt.limit_share(events, lt.HELD_BY_PREFILL) == pytest.approx(100 / 3)
    assert lt.limit_share(events, ("tail",)) == pytest.approx(100 / 3)


def test_a_rounds_parts_leave_out_the_pull_that_ends_a_chunk():
    events = events_of()
    assert len(lt.round_spans(events, "rt.engine.readback")) == 3 and len(pt.spans_named(events, "rt.engine.readback")) == 4
    assert lt.wake_by_bytes(events) == {4_400_000: [pytest.approx(3.0)], 384: [pytest.approx(2.0)], 192: [pytest.approx(4.0)]}
    assert sorted(lt.wake_ms_each(events)) == [pytest.approx(2.0), pytest.approx(3.0), pytest.approx(4.0)]
    assert lt.round_ms_p50(events, "rt.engine.readback.copy") == pytest.approx(0.1)
    assert lt.round_ms_p50(events, "rt.engine.sample.draw") == pytest.approx(2.0)  # one round drew


def _reader(name):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), os.path.join(os.path.dirname(HERE), "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("trace", ["this_pr", "parent", "untraced"])
def test_the_eight_readers(monkeypatch, trace):
    """Each reads its number off the new names; on a parent's trace (the older spans and
    attributes alone) and on an untraced run each reads nothing and raises nothing."""
    monkeypatch.setattr(pt, "for_record", lambda record: None if trace == "untraced" else events_of(parts=trace == "this_pr"))
    got = {name: _reader(name).read({"cell": "c", "trace": {}}) for name in NEW}
    if trace != "this_pr":
        assert got == dict.fromkeys(NEW)
        return
    assert got == {"plan_full_steps_share.decode": pytest.approx(100 / 3), "plan_held_by_prefill_share.decode": pytest.approx(100 / 3),
                   "plan_held_by_tail_share.decode": pytest.approx(100 / 3), "readback_wake_ms_p50.serve": pytest.approx(3.0),
                   "readback_copy_ms_p50.serve": pytest.approx(0.1), "dispatch_args_ms_p50.serve": pytest.approx(1.0),
                   "dispatch_call_ms_p50.serve": pytest.approx(2.0), "emit_ms_p50.serve": pytest.approx(1.0)}
    for name in NEW:
        mod = _reader(name)
        assert (mod.NAME, mod.SOURCE) == (name, "program_span")
        assert mod.DRIVERS == (("serve_closed",) if name.startswith("plan_") else ("serve_closed", "serve_open"))


def test_benchmark_json_lists_the_eight_with_their_cells():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    serve = [w["name"] for w in bench["workloads"] if ".serve-" in w["name"]]
    for name in NEW:
        mod, entry = _reader(name), entries[name]
        assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
        assert entry["workloads"] == ([w for w in serve if "chat" not in w] if name.startswith("plan_") else serve)


def test_the_cli_prints_the_three_tables(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(pt, "load_events", lambda path: events_of())
    assert lt.main(["loop_trace.py", str(tmp_path / "t.xplane.pb")]) == 0
    out = capsys.readouterr().out
    assert "     1   4.00       48 of      96  tail" in out and "4400000 bytes" in out and "rt.engine.sample.emit" in out
