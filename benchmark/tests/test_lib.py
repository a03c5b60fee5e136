import json
import os

import numpy as np
import pytest

from lib import arrivals, costs, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_pctl_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.pctl(xs, 0.5) == 51  # round(0.5 * 99) = 50 -> xs[50]
    assert stats.pctl(xs, 0.9) == 90
    assert stats.pctl(xs, 1.0) == 100
    assert stats.pctl([], 0.9) is None
    assert stats.pctl([7.0], 0.9) == 7.0


def test_costs_mistral_two_layers():
    m = _model("mistral-7b-v0.3")
    # one layer: 4096*128*(64+16) + 3*4096*14336 = 41,943,040 + 176,160,768
    assert costs.layer_matmul_params(m) == 218_103_808
    assert costs.matmul_params(m) == 2 * 218_103_808 + 4096 * 32768  # 570.4M
    assert costs.total_params(m) == 704_663_552  # the issue's 704.6M
    # 6 * 570.4M + 6 * 2 layers * 4096 wide * 4096 positions = 3.4225G + 0.2013G
    assert costs.train_flops_per_token(m, 4096) == pytest.approx(3.62e9, rel=2e-3)


def test_costs_internlm2():
    m = _model("internlm2-1.8b")
    assert round(costs.total_params(m) / 1e9, 3) == 1.889
    assert costs.kv_bytes_per_token(m) == 96 * 1024
    # bf16 weights of the blocks and the head, plus 1000 live rows
    want = (24 * (2048 * 128 * 48 + 3 * 2048 * 8192) + 2048 * 92544) * 2 + 1000 * 96 * 1024
    assert costs.decode_step_bytes(m, 1000) == want


def test_a_utilisation_is_taken_against_all_the_chips_of_the_cell():
    import run as R

    readers = R.load_metric_readers()
    record = {"steps": 50, "tokens_per_step": 32768, "window_s": 51.0, "seq": 4096, "chips": 1,
              "model": _model("internlm2-1.8b"), "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    one = readers["mfu.train"].read(record)
    assert one == pytest.approx(100 * 50 * 32768 / 51.0 * costs.train_flops_per_token(record["model"], 4096) / 197e12)
    assert readers["mfu.train"].read(dict(record, chips=4)) == pytest.approx(one / 4)
    assert readers["mfu.train"].read(dict(record, block=None)) == one  # no block: lib/costs.py
    # a serve record: one chip reads a share of its bandwidth, more than one reads nothing
    serve = {"window_s": 10.0, "window": [0.0, 10.0], "chips": 1, "model": record["model"], "peaks": record["peaks"],
             "rows": [dict(i=0, due=None, sent=1.0, prompt_len=100, max_tokens=50, n_out=50, ttft_s=0.1, latency_s=2.1,
                           ok=True, rejected=False, interrupted=False)]}
    assert 0 < readers["decode_hbm_util.serve"].read(serve) < 100
    assert readers["decode_hbm_util.serve"].read(dict(serve, chips=4)) is None


def test_arrivals_repeat_for_a_seed_and_differ_between_seeds():
    def draw(seed):
        rng = arrivals.rng_for(seed, 2)
        return (arrivals.exponential_gaps(2.0, 50, rng).tolist(),
                arrivals.lengths({"dist": "lognormal", "median": 256, "sigma": 0.8, "lo": 32, "hi": 1536}, 50, rng).tolist(),
                arrivals.token_ids(16, 1000, rng))

    a, b, c = draw(3_000_000_001), draw(3_000_000_001), draw(3_000_000_002)
    assert a == b
    assert a[0] != c[0] and a[1] != c[1] and a[2] != c[2]
    # another seed is another order of the same work
    assert sorted(a[0]) == sorted(c[0]) and sorted(a[1]) == sorted(c[1])


def test_arrivals_follow_their_distributions():
    rng = arrivals.rng_for(1)
    gaps = arrivals.exponential_gaps(2.0, 200, rng)
    assert gaps.sum() == pytest.approx(100.0, rel=0.02)  # n / rate
    lens = arrivals.lognormal_lengths(256, 0.8, 32, 1536, 201, rng)
    assert int(np.median(lens)) == 256 and lens.min() >= 32 and lens.max() <= 1536
    uni = arrivals.uniform_lengths(64, 256, 32, rng)
    assert uni.min() >= 64 and uni.max() <= 256 and abs(uni.mean() - 160) < 1


def test_trace_reduce_on_a_recorded_trace():
    """Two steps of the train cell on a v5e (PR 23, chip call 5), cut to the events the
    reduction reads (`bench.window` set round the two steps by hand). The expected numbers
    were read from the trace's own lines: two `jit_step` modules of 282.5 ms each."""
    from lib import trace_reduce

    with open(os.path.join(BENCH, "tests", "trace_events.json")) as f:
        events = json.load(f)
    out = trace_reduce.reduce_events(events, chips=1)
    assert out["window_s"] == pytest.approx(0.570140031)
    assert out["busy_s"] == pytest.approx(0.565053493)
    assert out["modules"][0][0].startswith("jit_step") and out["modules"][0][1] == pytest.approx(0.565062816)
    assert out["device_ops"][0] == ["jit_step:fusion.221", pytest.approx(0.046646063)]
    assert not any("while" in name for name, _ in out["device_ops"])  # self time, not children's
    assert out["idle_gaps"][0] == ["bench.wait", pytest.approx(0.002465246)]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_trace_reduce_union_self_time_and_gap_names():
    from lib import trace_reduce

    events = {
        "window": [0, 1000],
        "host": [["bench.window", 100, 800], ["bench.step", 100, 30], ["bench.wait", 130, 480],
                 ["$engine.py:1 loop", 0, 1000], ["$engine.py:2 sample", 590, 120]],
        "devices": {"/device:TPU:0": {"modules": [["jit_f(1)", 200, 400]], "ops": [
            ["while.1", 200, 400], ["fusion.1", 200, 100], ["fusion.2", 300, 250],   # nested in the while
            ["copy.1", 700, 100], ["fusion.1", 50, 20],                             # the last lies outside the window
        ]}},
    }
    out = trace_reduce.reduce_events(events)
    assert out["window_s"] == pytest.approx(800e-9)
    assert out["busy_s"] == pytest.approx(500e-9)                 # [200,600] and [700,800]
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"jit_f:fusion.2": 250e-9, "jit_f:fusion.1": 100e-9, "copy.1": 100e-9, "jit_f:while.1": 50e-9})
    # three gaps of 100 ns: [100, 200] mostly under bench.wait (a bench span wins over a Python
    # function), [600, 700] and [800, 900] under no bench span: the innermost Python function
    assert sorted(map(tuple, out["idle_gaps"])) == [
        ("bench.wait", pytest.approx(100e-9)), ("engine.py:1 loop", pytest.approx(100e-9)),
        ("engine.py:2 sample", pytest.approx(100e-9))]
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"window": [0, 1], "host": [], "devices": {}})


def test_open_loop_schedule_is_one_cycle_that_the_seed_rotates():
    import importlib.util

    spec = importlib.util.spec_from_file_location("serve_open", os.path.join(BENCH, "drivers", "serve_open.py"))
    serve_open = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_open)
    with open(os.path.join(BENCH, "traffic", "chat-open.json")) as f:
        tr = json.load(f)

    def window(seed):
        reqs = serve_open.schedule(tr, 40.0, seed, 1000)
        assert reqs == serve_open.schedule(tr, 40.0, seed, 1000)
        ramp = tr["ramp_seconds"]
        assert all(0 <= r["due"] < ramp for r in reqs if r["phase"] == 0)
        assert all(ramp <= r["due"] < ramp + 40.0 for r in reqs if r["phase"] == 1)
        return [(len(r["prompt"]), r["max_tokens"]) for r in reqs if r["phase"] == 1], reqs

    a, ra = window(3_000_000_001)
    b, rb = window(3_000_000_002)
    assert len(a) == len(b) == round(tr["rate_per_s"] * 40.0)
    assert a != b and sorted(a) == sorted(b)
    k = b.index(a[0])
    assert b[k:] + b[:k] == a                      # the same cycle from another phase
    assert ra[-1]["prompt"] != rb[-1]["prompt"]    # other token ids


def test_hostwatch_counts_the_collectors_runs_and_ends_what_it_started():
    """The note holds the collector's runs and the process's CPU time inside the watch;
    stopping ends the watch's thread and takes its callback away."""
    import gc
    import json
    import threading
    import time

    from lib import hostwatch

    before = threading.active_count(), len(gc.callbacks)
    watch = hostwatch.start()
    time.sleep(0.1)
    gc.collect()
    stop_at = time.perf_counter() + 0.3
    while time.perf_counter() < stop_at:
        sum(range(100_000))
    time.sleep(0.1)
    note = watch.stop()
    assert note.startswith("host in the window: ")
    seen = json.loads(note.split(": ", 1)[1])
    assert seen["gc_collections"][2] >= 1 and seen["gc_s"] > 0
    assert 0.5 < seen["wall_s"] < 5 and seen["process_cpu_s"] > 0.2
    assert 0 <= seen["machine_steal_share"] <= 1
    assert "wake_worst_late_s" in seen
    assert (threading.active_count(), len(gc.callbacks)) == before
    quiet = hostwatch.start(ticker=False)  # a traced run's: no thread for the profiler to record
    assert threading.active_count() == before[0]
    assert "wake_worst_late_s" not in quiet.stop() and len(gc.callbacks) == before[1]
