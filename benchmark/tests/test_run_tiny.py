"""`run.py` end to end on a tiny test-only configuration. The chip check, the
benchmark file and the traffic directory are switched here, by the test; the command
itself has no option for it. The CPU has no device plane to trace, so the traced run
gets a recorded summary in place of `trace_reduce.reduce_dir`."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    import run as R
    from lib import trace_reduce

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny.json"))
    monkeypatch.setattr(R, "TRAFFIC_DIR", os.path.join(HERE, "traffic"))

    def cpu_devices(chips):
        import jax

        return jax.devices(), {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

    monkeypatch.setattr(R, "require_chip", cpu_devices)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d, chips=1: {
        "busy_s": 0.5, "window_s": 1.0, "device_ops": [["fusion.1", 0.4]],
        "idle_gaps": [["bench.wait", 0.1]], "modules": []})
    return R


CELLS = {
    "tiny.train": ({"train_tok_s", "setup_s"},
                   {"compile_s", "window_compiles", "step_ms_p50.train", "mfu.train"}),
    "tiny.closed": ({"serve_out_tok_s", "tpot_ms_p90", "setup_s"},
                    {"compile_s", "window_compiles", "slot_occupancy.decode",
                     "decode_tok_per_iter.decode", "tpot_ms_p50.serve", "decode_hbm_util.serve"}),
    "tiny.open": ({"tpot_ms_p90", "setup_s"},
                  {"compile_s", "window_compiles", "queue_ms_p50.chat", "ttft_ms_p90.chat", "ttft_ms_p50.chat",
                   "prefill_ms_p50.chat", "tpot_ms_p50.serve", "decode_hbm_util.serve",
                   "gen_late_ms_p90.chat"}),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_cell_end_to_end(tiny, capsys, cell, trace):
    assert tiny.main(["--workload", cell, "--seed", "3000000001", "--seconds", "3",
                      "--trace", str(trace)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == CELLS[cell][trace]
    assert all(m["value"] == m["value"] and "unit" in m for m in line["metrics"].values())
    assert line["metrics"].get("window_compiles", {"value": 0})["value"] == 0
    assert ("breakdown" in line) == bool(trace)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])


def test_no_tpu_means_no_result(monkeypatch, capsys):
    import run as R

    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "mistral-7b-v0.3.train-seq4k", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert "TPU" in str(e.value)
    assert not any(l.startswith("{") for l in capsys.readouterr().out.splitlines())


def test_benchmark_json_and_the_files_agree():
    import run as R

    bench = json.load(open(R.BENCH_FILE))
    readers = R.load_metric_readers()
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            mod = readers[m["name"]]
            assert (mod.UNIT, mod.SOURCE) == (m["unit"], m["source"]), m["name"]
            if kind == "per_layer":
                assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"]), m["name"]
    for w in bench["workloads"]:
        traffic = json.load(open(os.path.join(R.TRAFFIC_DIR, w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(R.HERE, "drivers", traffic["driver"] + ".py"))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" not in m or w["name"] in m["workloads"]:
                assert traffic["driver"] in readers[m["name"]].DRIVERS, (w["name"], m["name"])
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(R.ROOT, c["file"])))
        assert cfg["source"] == c["source"] and sorted(cfg["reduced"]) == sorted(c["reduced"])
