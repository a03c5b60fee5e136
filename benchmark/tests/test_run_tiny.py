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
    import lib
    import run as R
    from lib import trace_reduce

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny.json"))
    monkeypatch.setattr(R, "TRAFFIC_DIR", os.path.join(HERE, "traffic"))
    # a test-only block's two modules, as a later PR would add them beside the real ones
    monkeypatch.setattr(lib, "__path__", list(lib.__path__) + [os.path.join(HERE, "lib")])

    def cpu_devices(chips):
        import jax

        return jax.devices(), {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

    monkeypatch.setattr(R, "require_chip", cpu_devices)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d, chips=1: {
        "busy_s": 0.5, "window_s": 1.0, "device_ops": [["fusion.1", 0.4]],
        "idle_gaps": [["bench.wait", 0.1]], "modules": []})
    return R


TRAIN = ({"train_tok_s", "setup_s"}, {"compile_s", "window_compiles", "step_ms_p50.train", "mfu.train"})
CLOSED = ({"serve_out_tok_s", "tpot_ms_p90", "setup_s"},
          {"compile_s", "window_compiles", "slot_occupancy.decode",
           "decode_tok_per_iter.decode", "tpot_ms_p50.serve", "decode_hbm_util.serve"})
CELLS = {
    "tiny.train": TRAIN,
    "tiny.train-fsdp2": TRAIN,   # a mesh from the traffic file, over two (virtual) devices
    "tiny.closed": CLOSED,
    "tiny.open": ({"tpot_ms_p90", "setup_s"},
                  {"compile_s", "window_compiles", "queue_ms_p50.chat", "ttft_ms_p90.chat", "ttft_ms_p50.chat",
                   "prefill_ms_p50.chat", "tpot_ms_p50.serve", "decode_hbm_util.serve",
                   "gen_late_ms_p90.chat"}),
}


def _run(R, capsys, cell, trace, seconds="3"):
    """(result line, the `[bench]` lines before it) of one run through `main`."""
    assert R.main(["--workload", cell, "--seed", "3000000001", "--seconds", seconds,
                   "--trace", str(trace)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    return line, out[:-1]


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_cell_end_to_end(tiny, capsys, cell, trace):
    line, log = _run(tiny, capsys, cell, trace)
    assert set(line["metrics"]) == CELLS[cell][trace]
    # a serve run says what the host did to its window (`lib/hostwatch.py`), in its log only
    assert any("host in the window" in l for l in log) == (cell in ("tiny.closed", "tiny.open"))
    assert all(m["value"] == m["value"] and "unit" in m for m in line["metrics"].values())
    assert line["metrics"].get("window_compiles", {"value": 0})["value"] == 0
    assert ("breakdown" in line) == bool(trace)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert len(line["device"]["memory_peak_bytes_by_chip"]) == (2 if cell == "tiny.train-fsdp2" else 1)


def test_a_mesh_that_is_not_the_cells_chips_means_no_result(tiny, capsys):
    with pytest.raises(SystemExit) as e:  # the traffic's mesh is {"fsdp": 2}, the cell's chips 1
        tiny.main(["--workload", "tiny.train-badmesh", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None) and "mesh" in str(e.value)
    assert not any(l.startswith("{") for l in capsys.readouterr().out.splitlines())


def test_a_new_driver_file_reports_the_record_it_declares(tiny, capsys, monkeypatch):
    """`tests/drivers/train_steps_again.py` says `RECORD = "train_steps"`: its cell prints
    that record's metrics, end to end and per layer, and no reader names the file."""
    monkeypatch.setattr(tiny, "DRIVERS_DIR", os.path.join(HERE, "drivers"))
    assert tiny.load_driver("train_steps_again")[1] == "train_steps"
    assert set(_run(tiny, capsys, "tiny.train-again", 0, "2")[0]["metrics"]) == TRAIN[0]
    assert set(_run(tiny, capsys, "tiny.train-again", 1, "2")[0]["metrics"]) == TRAIN[1]


def test_a_configuration_that_names_its_block_gets_that_blocks_modules(tiny, capsys):
    """`tests/configs/toy.json` says `"block": "toy"`: `tests/lib/reference_toy.py` decides
    `correct` in the train and the serve driver, and `costs_toy.py` (twice the dense block's
    operations) is what `mfu.train` counts with. No non-test file names the block."""
    from lib import blocks

    toy = blocks.reference({"block": "toy"})
    assert toy.__name__ == "lib.reference_toy" and blocks.reference({}).__name__ == "lib.reference"
    assert blocks.costs({"block": "toy"}).__name__ == "lib.costs_toy"
    del toy.CALLS[:]
    line, notes = _run(tiny, capsys, "toy.train", 0, "2")
    assert set(line["metrics"]) == TRAIN[0] and toy.CALLS
    assert any(f"tolerance {toy.LOSS_ABS_TOL:.1e}" in n for n in notes)
    line, notes = _run(tiny, capsys, "toy.closed", 0, "2")
    assert set(line["metrics"]) == CLOSED[0]
    assert any(f"of {toy.MAX_PROBES} probes sent" in n for n in notes)
    mfu = tiny.load_metric_readers()["mfu.train"]
    record = {"steps": 5, "tokens_per_step": 256, "window_s": 1.0, "seq": 128, "chips": 1,
              "model": json.load(open(os.path.join(HERE, "configs", "toy.json")))["model"], "peaks": {"bf16_flops": 1e12}}
    assert mfu.read(dict(record, block="toy")) == pytest.approx(2 * mfu.read(dict(record, block=None)))


def test_a_block_without_its_modules_or_their_names_means_no_run(tiny):
    from lib import blocks

    with pytest.raises(ModuleNotFoundError):
        blocks.costs({"block": "none-such"})
    with pytest.raises(SystemExit) as e:
        blocks._module("costs_kernels", {}, blocks.COSTS_NAMES)  # a module that lacks the names
    assert "lacks" in str(e.value)


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
    import math

    from lib import blocks

    kinds = {kind for mod in readers.values() for kind in mod.DRIVERS}
    for w in bench["workloads"]:
        traffic = json.load(open(os.path.join(R.TRAFFIC_DIR, w["traffic"] + ".json")))
        # the driver's file is there, and the kind of record it declares has readers
        record_kind = R.load_driver(traffic["driver"])[1]
        assert record_kind in kinds, (w["name"], record_kind)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" not in m or w["name"] in m["workloads"]:
                assert record_kind in readers[m["name"]].DRIVERS, (w["name"], m["name"])
        # a mesh is the cell's chips, axis by axis the program's own names; no mesh is one chip
        mesh = traffic.get("mesh")
        assert math.prod((mesh or {"dp": 1}).values()) == w["chips"], (w["name"], mesh)
        if mesh:
            from ray_tpu.parallel.mesh import AXIS_ORDER

            assert set(mesh) <= set(AXIS_ORDER) and traffic["batch"] % w["chips"] == 0
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(R.ROOT, c["file"])))
        assert cfg["source"] == c["source"] and sorted(cfg["reduced"]) == sorted(c["reduced"])
        # a block named by a configuration has both its modules, with the contract's names
        blocks.reference(cfg), blocks.costs(cfg)
