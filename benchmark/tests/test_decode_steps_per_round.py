"""`metrics/decode_steps_per_round.chat.py` on `test_loop_trace.py`'s hand-made events: the mean of
`steps` over the iterations with a slot decoding, nothing on a trace that names no `limit` or on an
untraced run, and its entry in `BENCHMARK.json`."""
import json
import os

import pytest

from lib import program_trace as pt
from test_loop_trace import HERE, _reader, events_of  # this directory: pytest puts a test file's own on the path

NAME = "decode_steps_per_round.chat"


@pytest.mark.parametrize("trace, want", [("this_pr", pytest.approx((1 + 8 + 4) / 3)), ("before_limits", None), ("untraced", None)])
def test_the_reader_takes_the_mean_of_the_decode_iterations_steps(monkeypatch, trace, want):
    monkeypatch.setattr(pt, "for_record", lambda record: None if trace == "untraced" else events_of(parts=trace == "this_pr"))
    assert _reader(NAME).read({"cell": "c", "trace": {}}) == want


def test_a_window_of_single_steps_reads_one(monkeypatch):
    """What the parent of PR 44 reads in the chat cell: every plan one step, whatever held it."""
    events = events_of()
    for e in pt.spans_named(events, pt.ITER_SPAN):
        e[3].update(steps=1, limit="sampling")
    monkeypatch.setattr(pt, "for_record", lambda record: events)
    assert _reader(NAME).read({"cell": "c", "trace": {}}) == 1.0


def test_benchmark_json_lists_it_for_the_chat_cell():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    mod, entry = _reader(NAME), next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
    assert entry["workloads"] == ["internlm2-1.8b.serve-chat"] and entry["better"] == "higher" and mod.DRIVERS == ("serve_open",)
