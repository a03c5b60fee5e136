"""`lib/program_trace.py`: the wire-format reader on a trace captured here on the CPU and on
a device plane written by hand, the reductions' arithmetic on small hand-made events, and
the readers of `metrics/` on a recorded trace cut to size (`program_trace_events.json`: one
train step; a slice of a chat and of a decode window with the device's operations merged
into its busy intervals)."""
import glob
import json
import os
import struct

import pytest

from lib import costs_kernels, program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def span(name, start_ms, dur_ms, thread="python#1", **attrs):
    return [name, start_ms * MS, dur_ms * MS, attrs, thread]


# -- the wire format ------------------------------------------------------------------

def _vi(n):
    out = bytearray()
    n &= (1 << 64) - 1
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, payload):
    """One field: an int as a varint, bytes or str as length-delimited."""
    if isinstance(payload, int):
        return _vi(num << 3) + _vi(payload)
    payload = payload.encode() if isinstance(payload, str) else payload
    return _vi(num << 3 | 2) + _vi(len(payload)) + payload


def _device_xspace():
    """One device plane as `xplane.proto` lays it out: two stat names, three event metadata
    (a program, a fusion with a `tf_op`, a kernel without), a modules line and an ops line."""
    stat = lambda mid, **kw: _f(1, mid) + (_f(5, kw["s"]) if "s" in kw else _f(4, kw["i"]))  # noqa: E731
    meta = lambda mid, name, *stats: _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(2, name) + b"".join(_f(5, s) for s in stats)))  # noqa: E731
    event = lambda mid, off_ps, dur_ps: _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps))  # noqa: E731
    plane = (_f(1, 7) + _f(2, "/device:TPU:0")
             + _f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "tf_op")))
             + _f(5, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "flops")))
             + meta(10, "jit_rt_decode_multi_n8(123)")
             + meta(11, "%fusion.7 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %p)", stat(2, i=99),
                    stat(1, s="jit(rt_decode_multi_n8)/while/body/layer_3/mlp/dot_general:"))
             + meta(12, "%flash_fwd.1 = (bf16[4,64,16]{2,1,0}) custom-call(bf16[4,64,16]{2,1,0} %q, bf16[4,32,16]{2,1,0} %k, bf16[4,32,16]{2,1,0} %v), custom_call_target=\"tpu_custom_call\"")
             + _f(3, _f(1, 1) + _f(2, "XLA Modules") + _f(3, 1000) + event(10, 5_000_000, 40_000_000))
             + _f(3, _f(1, 2) + _f(2, "XLA Ops") + _f(3, 1000) + event(11, 6_000_000, 1_500_000) + event(12, 9_000_000, 500_000)))
    return _f(1, plane)


def test_the_device_plane_is_read_from_its_wire_format(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_device_xspace())
    events = pt.load_events(str(path))
    assert events["modules"] == [["jit_rt_decode_multi_n8", 6000.0, 40000.0]]  # line timestamp 1000 ns + 5 us
    assert events["ops"] == [["fusion.7", "jit(rt_decode_multi_n8)/while/body/layer_3/mlp/dot_general:", 7000.0, 1500.0],
                             ["flash_fwd.1", "", 10000.0, 500.0]]
    assert list(events["hlo"]) == ["flash_fwd.1"] and events["window"] is None and events["spans"] == []
    assert costs_kernels.flash_dims(events["hlo"]["flash_fwd.1"], "flash_fwd") == (4, 64, 32, 16)
    assert pt.steps_of(events["modules"][0][0]) == 8 and pt.steps_of("jit_rt_decode") == 1


def test_host_spans_and_their_attributes_are_read_from_a_trace_captured_here(tmp_path):
    import threading

    import jax

    from ray_tpu.util import xprof

    def stepper():
        with xprof.span("rt.engine.iter", chunks=0, decode_slots=2, steps=1):
            with xprof.span("rt.engine.dispatch", steps=1, slots=2, rows=345):
                jax.numpy.ones((8,)).block_until_ready()
            with xprof.span("rt.engine.kv_insert", rid="abc-1", rows=64):
                pass

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        t = threading.Thread(target=stepper)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = pt.load_events(path)
    assert events["modules"] == [] and events["ops"] == []  # no device plane on a CPU
    lo, hi = pt.window_of(events)
    by_name = {e[0]: e for e in pt.stepper_spans(events)}
    assert set(by_name) == {"rt.engine.iter", "rt.engine.dispatch", "rt.engine.kv_insert"}
    assert by_name["rt.engine.dispatch"][3] == {"steps": 1, "slots": 2, "rows": 345}
    assert by_name["rt.engine.kv_insert"][3] == {"rid": "abc-1", "rows": 64}
    it, child = by_name["rt.engine.iter"], by_name["rt.engine.dispatch"]
    assert lo <= it[1] <= child[1] and child[1] + child[2] <= it[1] + it[2] <= hi
    assert len({e[4] for e in events["spans"]}) == 1  # one thread made them


# -- the reductions' arithmetic ---------------------------------------------------------

def test_scopes_look_through_transformations_and_recomputation():
    assert pt.scope_parts("jit(step)/transpose(jvp(loss))/mul:") == ["step", "loss", "mul"]
    cases = {
        "jit(step)/jvp(Transformer)/while/body/closed_call/layers/attn/attn._flash_bhsd/flash_fwd/pallas_call:": "attn",
        "jit(step)/transpose(jvp(Transformer))/while/body/closed_call/checkpoint/layers/mlp/up/dot_general:": "mlp",
        "jit(step)/transpose(jvp(Transformer))/while/body/closed_call/checkpoint/rematted_computation/layers/mlp_norm/mul:": "mlp_norm",
        "jit(step)/transpose(jvp(Transformer))/lm_head/dot_general:": "lm_head",
        "jit(step)/jvp(loss)/reduce_max:": "loss",
        "jit(step)/optimizer/sqrt:": "optimizer",
        "jit(rt_decode_multi_n8)/while/body/sample/argmax:": "sample",
        "jit(rt_decode)/layer_11/attn/dot_general:": "attn",
        "jit(step)/transpose(jvp(Transformer))/while:": pt.UNSCOPED,
        "": pt.UNSCOPED,
    }
    assert {path: pt.scope_of(path) for path in cases} == cases


def test_self_time_by_scope_takes_nested_operations_out_of_their_parent():
    # a `while` of 100 ms holds an attn op of 30 ms and an mlp op of 50 ms, the second of which holds
    # a 10 ms operation of no scope; after it an optimizer op of 20 ms, half of it past the window
    events = {"window": [0, 120 * MS], "spans": [], "modules": [["jit_step", 0, 130 * MS]], "hlo": {}, "ops": [
        ["while.1", "jit(step)/jvp(Transformer)/while:", 0, 100 * MS],
        ["fusion.1", "jit(step)/jvp(Transformer)/while/body/layers/attn/dot_general:", 5 * MS, 30 * MS],
        ["fusion.2", "jit(step)/transpose(jvp(Transformer))/while/body/checkpoint/layers/mlp/dot_general:", 40 * MS, 50 * MS],
        ["copy.3", "", 50 * MS, 10 * MS],
        ["fusion.4", "jit(step)/optimizer/mul:", 110 * MS, 20 * MS],
    ]}
    by_scope = pt.device_seconds_by_scope(events)
    assert by_scope == pytest.approx({"attn": 0.030, "mlp": 0.040, "optimizer": 0.010, pt.UNSCOPED: 0.020 + 0.010})
    assert sum(by_scope.values()) == pytest.approx(0.110)  # the busy time inside the window, counted once
    # a step: the window holds 120 of the step program's 130 ms, so 12/13 of a step
    assert pt.scope_ms_per_step({**events, "modules": [["jit_step", 0, 130 * MS], ["jit_step", 200 * MS, 130 * MS]],
                                 "window": [0, 400 * MS]}, ("attn", "mlp")) == pytest.approx((30 + 50 - 10) / 2)
    assert pt.scope_ms_per_step(events, ("lm_head", "loss")) is None  # no such operation: not 0


def test_collective_operations_are_found_in_each_form_and_counted_by_self_time():
    """The four forms a v5e's compiled step holds them in (`lib/program_trace.COLLECTIVES`), from
    the four-chip cell's HLO; an operand or a metadata path that only mentions one is none."""
    forms = {
        '%all-gather.208 = bf16[1,2048,8,128]{1,3,2,0:T(8,128)(2,1)} all-gather(%param_0.1356), channel_id=75, replica_groups=[1,4]<=[4], dimensions={1}': "all-gather",
        '%fusion.447 = bf16[528,8192]{1,0:T(8,128)(2,1)S(1)} fusion(%copy-done.24), kind=kCustom, calls=%all-reduce-scatter.6.clone.clone, metadata={op_name="jit(step)/mlp/up/dot_general"}': "reduce-scatter",
        '%all-reduce.66 = bf16[2048,92544]{1,0} all-reduce(%convolution_bitcast_fusion.6), channel_id=22, to_apply=%add.1.clone': "all-reduce",
        '%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%x), source_target_pairs={{0,1}}': "collective-permute",
        '%collective-permute-done.1 = f32[8]{0} collective-permute-done(%collective-permute-start.1)': "collective-permute",
        '%all-to-all.1 = f32[4,8]{1,0} all-to-all(%y), replica_groups={{0,1,2,3}}': "all-to-all",
        '%async-collective-start.11 = (bf16[1,512,8192]{2,1,0}, bf16[1,2048,8192]{2,1,0}, s32[2]{0}) fusion(%copy-done.15), kind=kCustom, calls=%fused_computation.385': "async-collective",
        '%async-collective-done.11 = bf16[1,2048,8192]{2,1,0} fusion(%get-tuple-element.2337), kind=kCustom, calls=%fused_computation.386': "async-collective",
        # a matmul that carries a gather along is computation: it is what hides the transfer
        '%fusion.449 = (bf16[2,8,4096,128]{2,3,0,1}, bf16[1,512,8,128]{1,3,2,0}) fusion(%p.1, %p.2), kind=kCustom, calls=%async_collective_fusion.449': None,
        '%fusion.9 = bf16[8,8]{1,0} fusion(%all-gather.208), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(step)/all-gather"}': None,
        '%flash_fwd.18 = (bf16[32,4096,128]{2,1,0}) custom-call(bf16[32,4096,128]{2,1,0} %q), custom_call_target="tpu_custom_call"': None,
    }
    assert {hlo: pt.collective_kind(hlo) for hlo in forms} == forms
    # a step of 100 ms: a `while` of 80 holding a 10 ms gather (8 of it hidden under nothing here: it is
    # on the operation line, so the core waits) and a 20 ms matmul; after it a 6 ms reduce-scatter fusion
    events = {"window": [0, 200 * MS], "spans": [], "hlo": {}, "modules": [["jit_step", 0, 100 * MS], ["jit_step", 100 * MS, 100 * MS]],
              "collectives": {"all-gather.2": "all-gather", "fusion.7": "reduce-scatter"}, "ops": [
        ["while.1", "jit(step)/while:", 0, 80 * MS], ["all-gather.2", "jit(step)/while/body/mlp/dot_general:", 5 * MS, 10 * MS],
        ["fusion.3", "jit(step)/while/body/mlp/dot_general:", 20 * MS, 20 * MS], ["fusion.7", "jit(step)/mlp/dot_general:", 85 * MS, 6 * MS],
        ["while.1", "jit(step)/while:", 100 * MS, 80 * MS], ["all-gather.2", "jit(step)/while/body/mlp/dot_general:", 105 * MS, 10 * MS]]}
    assert pt.device_seconds_by_collective(events) == pytest.approx({"all-gather": 0.020, "reduce-scatter": 0.006})
    assert pt.device_seconds_by_collective(events, with_scope=True) == pytest.approx(
        {"all-gather in mlp": 0.020, "reduce-scatter in mlp": 0.006})
    assert pt.ms_per_step(events, 0.026) == pytest.approx(13.0)
    assert pt.device_seconds_by_collective({**events, "collectives": {}}) == {}  # one chip: nothing, and the readers print nothing
    assert pt.device_seconds_by_collective({**events, "window": None}) is None


def test_steps_per_multi_step_execution_and_device_time_per_step():
    events = {"window": [0, 1000 * MS], "spans": [], "ops": [], "hlo": {}, "modules": [
        ["jit_rt_decode", 10 * MS, 30 * MS], ["jit_rt_decode_multi_n8", 50 * MS, 200 * MS],
        ["jit_rt_decode_multi_n4", 300 * MS, 90 * MS], ["jit_rt_prefill_b128", 400 * MS, 18 * MS],
        ["jit_rt_decode_multi_n8", 900 * MS, 200 * MS],  # runs past the window: left out
    ]}
    assert pt.decode_ms_per_step(events) == pytest.approx((30 + 200 + 90) / (1 + 8 + 4))
    assert [m[0] for m in pt.executions(events, "jit_rt_prefill_b")] == ["jit_rt_prefill_b128"]
    assert pt.decode_ms_per_step({**events, "modules": events["modules"][3:4]}) is None


def test_idle_seconds_go_to_the_innermost_span():
    # device busy 0-10, 40-60, 90-100 of a 100 ms window; one iteration 5-95 holding dispatch 5-12,
    # readback 12-62 and sample 62-90; a plan span 96-99 after it; a span of another thread covers all
    events = {"window": [0, 100 * MS], "modules": [], "hlo": {},
              "ops": [["busy", "", 0, 10 * MS], ["busy", "", 40 * MS, 20 * MS], ["busy", "", 90 * MS, 10 * MS]],
              "spans": [span("rt.engine.iter", 5, 90), span("rt.engine.dispatch", 5, 7), span("rt.engine.readback", 12, 50),
                        span("rt.engine.sample", 62, 28), span("rt.engine.plan", 96, 3),
                        span("rt.other", 0, 100, thread="python#2")]}
    idle = pt.idle_by_span(events)
    assert idle == pytest.approx({"rt.engine.dispatch": 0.002, "rt.engine.readback": 0.028 + 0.002, "rt.engine.sample": 0.028})
    assert sum(idle.values()) == pytest.approx(0.060)
    # idle inside the iteration but in none of its children, and idle before the first span
    events["spans"][3] = span("rt.engine.sample", 62, 20)
    events["ops"][0] = ["busy", "", 2 * MS, 8 * MS]
    idle = pt.idle_by_span(events)
    assert idle["rt.engine.iter"] == pytest.approx(0.008) and idle[pt.NO_SPAN] == pytest.approx(0.002)
    segs = pt.innermost_segments(pt.stepper_spans(events))
    assert segs["rt.engine.iter"] == [[82 * MS, 95 * MS]] and segs["rt.engine.sample"] == [[62 * MS, 82 * MS]]
    assert pt.overlap_each([[5 * MS, 95 * MS]], pt.idle_intervals(events, 0, 100 * MS)) == [pytest.approx(60 * MS)]


def test_an_empty_window_gives_none_and_not_zero():
    empty = {"window": None, "spans": [span("rt.engine.sample", 1, 2)], "hlo": {},
             "modules": [["jit_rt_decode", 0, MS]], "ops": [["fusion.1", "jit(rt_decode)/layer_0/attn/add:", 0, MS]]}
    assert pt.window_of(empty) is None and pt.window_of({**empty, "window": [5, 5]}) is None
    assert pt.idle_by_span(empty) is None and pt.device_seconds_by_scope(empty) is None
    assert pt.device_seconds_by_program(empty) is None and pt.decode_ms_per_step(empty) is None
    assert pt.spans_named(empty, "rt.engine.sample") == [] and pt.kernel_calls(empty, "flash_fwd") == []
    assert pt.scope_ms_per_step(empty, ("attn",)) is None and pt.kernel_ms_per_step(empty, "flash_fwd") is None


def test_flash_costs_from_the_shapes_of_a_call():
    hlo = ("%flash_bwd.12 = (f32[64,4096,128]{2,1,0}, bf16[64,4096,128]{2,1,0}, bf16[64,4096,128]{2,1,0}) custom-call("
           "bf16[64,4096,128]{2,1,0} %q, bf16[64,4096,128]{2,1,0} %g, f32[64,4096,1]{2,1,0} %lse, f32[64,4096,1]{2,1,0} %delta, "
           "bf16[64,2048,128]{2,1,0:T(8,128)(2,1)S(1)} %k, bf16[64,2048,128]{2,1,0} %v), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={bf16[64,4096,128]{2,1,0}}")
    assert costs_kernels.flash_dims(hlo, "flash_bwd") == (64, 4096, 2048, 128)
    assert costs_kernels.flash_dims(hlo, "other") is None and costs_kernels.flash_dims("%x = f32[] add()", "flash_fwd") is None
    fwd = costs_kernels.flash_fwd_flops(64, 4096, 4096, 128)
    assert fwd == 2 * 64 * 4096 * 4096 * 128 and costs_kernels.flash_bwd_flops(64, 4096, 4096, 128) == 2 * fwd
    assert costs_kernels.flash_fwd_flops(1, 8, 8, 4, causal=False) == 4 * 8 * 8 * 4


# -- the readers on a recorded trace ----------------------------------------------------

RECORDED = {
    "train": ("train_steps", "mistral-7b-v0.3", {
        "attn_dev_ms.train": 69.0131, "mlp_dev_ms.train": 128.1634, "head_dev_ms.train": 50.5997,
        "opt_dev_ms.train": 19.8377, "flash_ms.train": 32.4744, "flash_fwd_roofline.train": 28.1355,
        "flash_bwd_roofline.train": 44.1598, "unscoped_dev_share.train": 3.4514}),
    "chat": ("serve_open", "internlm2-1.8b", {
        "sample_ms_p50.chat": 8.3553, "iter_host_ms_p50.serve": 21.2908, "kv_insert_share.serve": 23.7053,
        "decode_dev_ms_per_step.serve": 34.9406, "decode_roofline.serve": 12.9778,
        "prefill_dev_ms_p50.chat": 18.1939, "idle_named_share.serve": 99.7565}),
    "decode": ("serve_closed", "internlm2-1.8b", {
        "iter_host_ms_p50.serve": 7.9882, "kv_insert_share.serve": 26.1903, "decode_dev_ms_per_step.serve": 28.7532,
        "decode_roofline.serve": 15.9669, "idle_named_share.serve": 98.964}),
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "program_trace_events.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("piece", sorted(RECORDED))
def test_the_readers_on_a_recorded_trace(recorded, monkeypatch, piece):
    """Every metric of PR 24 whose driver ran the piece reads a value there, the value it
    read when the piece was recorded (my chip run, PR 24), and no share passes 100%."""
    import run as R

    from lib import peaks

    driver, config, want = RECORDED[piece]
    monkeypatch.setattr(pt, "for_record", lambda record: recorded[piece])
    with open(os.path.join(R.HERE, "configs", config + ".json")) as f:
        record = {"cell": piece, "trace": {}, "model": json.load(f)["model"], "peaks": peaks.PEAKS["TPU v5 lite"], "chips": 1}
    readers = {name: mod for name, mod in R.load_metric_readers().items() if hasattr(mod, "pt") and driver in mod.DRIVERS}
    got = {name: mod.read(record) for name, mod in readers.items()}
    # a step on one chip moves no data between chips: those two read nothing, not 0
    nothing = {"collective_dev_ms.train", "collective_dev_share.train"} & set(readers)
    assert {name for name, v in got.items() if v is None} == nothing
    got = {name: v for name, v in got.items() if v is not None}
    assert got == pytest.approx(want, rel=1e-4)
    assert all(v <= 100.0 for name, v in got.items() if readers[name].UNIT == "%")
    # the same step's record from a server over four chips: no roofline against one chip's peak
    if "decode_roofline.serve" in readers:
        assert readers["decode_roofline.serve"].read(dict(record, chips=4)) is None


def test_a_program_without_names_reads_as_nothing_not_zero(recorded, monkeypatch):
    """The parent of PR 24 under these files: no `rt.*` span, programs and kernels under
    other names. Every reader that needs one leaves its metric out; none prints 0."""
    import run as R

    from lib import peaks

    piece = dict(recorded["chat"], spans=[], modules=[[m[0].replace("jit_rt_", "jit__"), m[1], m[2]] for m in recorded["chat"]["modules"]])
    monkeypatch.setattr(pt, "for_record", lambda record: piece)
    with open(os.path.join(R.HERE, "configs", "internlm2-1.8b.json")) as f:
        record = {"cell": "chat", "trace": {}, "model": json.load(f)["model"], "peaks": peaks.PEAKS["TPU v5 lite"], "chips": 1}
    readers = R.load_metric_readers()
    assert {name: readers[name].read(record) for name in RECORDED["chat"][2]} == dict.fromkeys(RECORDED["chat"][2])
    train = dict(recorded["train"], hlo={}, ops=[[op.replace("flash_", "attn._flash_"), path.replace("optimizer", "").replace("loss", ""), s, d]
                                                 for op, path, s, d in recorded["train"]["ops"]])
    monkeypatch.setattr(pt, "for_record", lambda record: train)
    got = {name: readers[name].read(record) for name in RECORDED["train"][2]}
    assert [n for n, v in got.items() if v is None] == ["opt_dev_ms.train", "flash_ms.train", "flash_fwd_roofline.train", "flash_bwd_roofline.train"]
    assert got["unscoped_dev_share.train"] > 10 and got["attn_dev_ms.train"] == pytest.approx(69.0131, rel=1e-4)
    # and a run with no trace at all
    monkeypatch.undo()
    assert pt.for_record({"cell": "chat"}) is None and pt.for_record({"cell": "no-such-cell", "trace": {}}) is None


def test_idle_by_span_and_programs_of_the_recorded_decode_slice(recorded):
    idle = pt.idle_by_span(recorded["decode"])
    assert idle["rt.engine.kv_insert"] == pytest.approx(0.215942, rel=1e-4) and max(idle, key=idle.get) == "rt.engine.kv_insert"
    assert idle[pt.NO_SPAN] == pytest.approx(0.00242, rel=1e-3)
    lo, hi = pt.window_of(recorded["decode"])
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in pt.idle_intervals(recorded["decode"], lo, hi)) / 1e9)
    whole = {m[0] for m in pt.executions(recorded["decode"], "jit_rt_decode")}
    assert whole == {"jit_rt_decode", "jit_rt_decode_multi_n8", "jit_rt_decode_multi_n4"}
    assert pt.device_seconds_by_scope(recorded["train"])["mlp"] == pytest.approx(0.128163, rel=1e-4)
