"""The two readers of a prefill chunk's latent attention (`metrics/latent_chunk_ms_per_ktok.longctx.py`,
`metrics/latent_chunk_kernel_share.longctx.py`) over a traced window written by hand: a program with the
kernel `latent_chunk` under its chunk loop, and one without it, as the parent of the PR that brought the
kernel; the decode kernel's reader (`latent_attn`) counts none of the new kernel's operations."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("latent_chunk_ms_per_ktok.longctx", "latent_chunk_kernel_share.longctx")
CELLS = ["dots3-note-prev.serve-longctx", "openpangu-ultra-moe-718b.serve-longctx-mla", "xing4.0-29b-a4b.serve-sessions-mhc48"]


def _events(kernel: bool):
    """A window of one decode step, one whole prefill chunk (two layers, two blocks of keys each in the
    first) and a chunk the window's end cuts."""
    body = "jit(rt_prefill_b1024)/layer_{}/attn/latent/while/body/"
    inner = "jit(latent_chunk_attention)/jit(_latent_chunk_block)/latent_chunk/pallas_call" if kernel else "dot_general"
    name = "latent_chunk" if kernel else "fusion"
    ops = [["latent_attn.3", "jit(rt_decode)/layer_1/attn/latent/latent_attn/pallas_call", 100, 100],
           ["while.7", "jit(rt_prefill_b1024)/layer_1/attn/latent/while", 1000, 700],        # 700 - 610 = 90 of its own
           ["convolution_convert_fusion.4", body.format(1) + "dot_general", 1010, 50],       # the expansion
           [name + ".18", body.format(1) + inner, 1060, 250],
           ["convolution_convert_fusion.4", body.format(1) + "dot_general", 1320, 50],
           [name + ".18", body.format(1) + inner, 1370, 260],
           ["fusion.9", "jit(rt_prefill_b1024)/layer_1/mlp/experts/dot", 1700, 300],
           [name + ".19", body.format(2) + inner, 2000, 240],
           ["fusion.9", "jit(rt_prefill_b1024)/layer_2/mlp/experts/dot", 2240, 160],
           [name + ".18", body.format(1) + inner, 3700, 250]]                               # in the chunk that is cut
    spans = [["rt.engine.prefill", 990, 20, {"tokens": 800}, "stepper"], ["rt.engine.prefill", 3590, 500, {"tokens": 1024}, "stepper"],
             ["rt.engine.dispatch", 90, 5, {"rows": 1000}, "stepper"]]
    return {"window": [0, 4000], "spans": spans, "hlo": {}, "collectives": {}, "ops": ops,
            "modules": [["jit_rt_decode", 100, 100], ["jit_rt_prefill_b1024", 1000, 1400], ["jit_rt_prefill_b1024", 3600, 800]]}


@pytest.fixture
def readers(monkeypatch):
    import run as R
    from lib import program_trace as pt

    monkeypatch.setattr(pt, "stepper_spans", lambda ev: ev["spans"])
    monkeypatch.setattr(pt, "for_record", lambda record: record.get("trace"))
    return R.load_metric_readers()


@pytest.mark.parametrize("block", ["pangu_moe", "dots3", "xing4"])
def test_the_chunks_latent_time_a_thousand_tokens_reads_with_the_kernel_and_without(readers, block):
    """Self time under `latent` in the executions wholly inside the window (the loop's own 90, two
    expansions, three block products), over the tokens of the prefill span wholly inside it."""
    for kernel in (True, False):
        record = {"trace": _events(kernel), "chips": 1, "block": block}
        latent_ns = 90 + 2 * 50 + 250 + 260 + 240
        assert readers[NEW[0]].read(record) == pytest.approx(latent_ns / 1e6 / 0.8)
        assert readers["prefill_dev_ms_per_ktok.longctx"].read(record) == pytest.approx(1400 / 1e6 / 0.8)
        assert readers["latent_prefill_share.mla"].read(record) == pytest.approx(100 * latent_ns / 1400)


def test_the_kernels_share_counts_its_calls_in_whole_chunks_and_nothing_on_a_program_without_it(readers):
    from lib import program_trace as pt

    with_kernel, without = _events(True), _events(False)
    record = {"trace": with_kernel, "chips": 1, "block": "pangu_moe"}
    assert readers[NEW[1]].read(record) == pytest.approx(100 * (250 + 260 + 240) / (90 + 100 + 250 + 260 + 240))
    assert readers[NEW[1]].read({"trace": without, "chips": 1, "block": "pangu_moe"}) is None
    # the decode kernel's reader matches by the whole name: `latent_attn`, never `latent_chunk`
    assert [c[0] for c in pt.kernel_calls(with_kernel, "latent_attn")] == ["latent_attn.3"]
    assert len(pt.kernel_calls(with_kernel, "latent_chunk")) == 4 and pt.kernel_calls(without, "latent_chunk") == []


def test_no_trace_no_scope_and_no_tokens_read_nothing_and_raise_nothing(readers):
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_prefill_b128", 100, 800]],
            "ops": [["fusion.1", "jit(rt_prefill_b128)/layer_1/attn/kv_attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    no_tokens = dict(_events(True), spans=[])
    for name in NEW:
        assert readers[name].read({"chips": 1, "block": "pangu_moe"}) is None  # an untraced run
        assert readers[name].read({"trace": bare, "chips": 1, "block": "llama"}) is None
    assert readers[NEW[0]].read({"trace": no_tokens, "chips": 1, "block": "pangu_moe"}) is None


def test_benchmark_json_lists_the_two_for_the_three_latent_cells_at_the_end():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    import run as R

    readers = R.load_metric_readers()
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    for entry in bench["per_layer"][-2:]:
        mod = readers[entry["name"]]
        assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
        assert entry["workloads"] == CELLS and mod.DRIVERS == ("serve_closed",) and set(entry) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert bench["per_layer"][-2]["better"] == "lower" and bench["per_layer"][-1]["better"] == "higher"
