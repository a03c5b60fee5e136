"""The `granite_hybrid` block's benchmark files on the CPU at tiny widths: the benchmark's plain
reference (`lib/reference_granite_hybrid.py`, which imports nothing of the program) against the
repo's (`ray_tpu/models/granite_hybrid.py:forward_plain`), the two controls (float8 operands, a
bfloat16 recurrent state) against the stated precision, the costs module against ISSUE 32's
arithmetic at the published widths, the configuration file against the catalog's keys, the new
readers on a recorded trace's events, and `run.py` end to end through
`drivers/serve_closed_state.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import blocks, costs_granite_hybrid as costs, reference_granite_hybrid as reference, scope_trace_state

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "granite-4.0-h-micro.serve-sessions48"


def _model(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models import granite_hybrid
    from ray_tpu.models.transformer import ModelConfig

    model = _model("tests/configs/tiny-granite.json")["model"]
    fields = {k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()}
    cfg = ModelConfig(**fields)
    return cfg, model, granite_hybrid.init_params(cfg, jax.random.PRNGKey(4))


def fp8(a):
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)  # a pair of converts the compiler may drop


def test_the_block_has_every_name_the_harness_and_the_driver_ask():
    assert blocks.reference({"block": "granite_hybrid"}) is reference
    assert blocks.costs({"block": "granite_hybrid"}) is costs
    for name in ("score", "compare_scored", "MEAN_DEFICIT_TOL", "NEAR_TIE_MARGIN", "MIN_COMPARED_POSITIONS", "MAX_PROBES"):
        assert hasattr(reference, name), name
    assert hasattr(costs, "ssm_state_bytes") and hasattr(costs, "mamba_layers_step_bytes")


@pytest.mark.parametrize("q_block", [16, 7, 64], ids=["blocks", "blocks-that-pad", "one-block"])
def test_the_benchmarks_reference_is_the_repos_plain_reference(tiny, q_block):
    """Two forward passes written apart, the recurrence token by token in both."""
    from ray_tpu.models import granite_hybrid

    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 96, size=(45,)), jnp.int32)
    want = np.asarray(granite_hybrid.forward_plain(params, cfg, tokens))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: reference.forward(p, model, t, q_block=q_block))(params, tokens))
    np.testing.assert_allclose(got, want, atol=2e-6)
    targets = jnp.roll(tokens, -1)
    assert float(reference.loss(params, model, tokens, targets)) == pytest.approx(
        float(jnp.mean(reference.token_losses(params, model, tokens, targets))))


def test_greedy_by_full_passes_walks_the_repos_argmax(tiny):
    from ray_tpu.models import granite_hybrid

    cfg, model, params = tiny
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 96, size=(30,)), jnp.int32)
    ids, margins = jax.jit(lambda p, x: reference.greedy(p, model, x, 5))(params, prompt)
    seq = list(np.asarray(prompt))
    for j in range(5):
        logits = np.asarray(granite_hybrid.forward_plain(params, cfg, jnp.asarray(seq, jnp.int32)))[-1]
        assert int(np.argmax(logits)) == int(ids[j])
        top = np.sort(logits)[-2:]
        assert float(margins[j]) == pytest.approx(top[1] - top[0], abs=1e-6)
        seq.append(int(ids[j]))


def test_the_controls_move_the_logits_far_more_than_the_stated_precision(tiny):
    """The contract's control on this block, at a size a test holds: both operands of every matrix
    product rounded to float8 e4m3. On the chip the limits it has to fail are on the scored ids
    (PERF.md §6, PR 32); here the same rounding is read on the logits, beside bfloat16's. A
    bfloat16 recurrent state is a control of its own: it rounds what a step carries to the next."""
    _, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 96, size=(45,)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = reference.forward(params, model, tokens)
        rms = {name: float(jnp.sqrt(jnp.mean((reference.forward(params, model, tokens, **kw) - ref) ** 2)))
               for name, kw in (("fp8", dict(operand=fp8)), ("bf16", dict(operand=bf16)), ("state", dict(state=bf16)))}
    assert rms["fp8"] > 4 * rms["bf16"] > 0 and rms["state"] > 0
    near, far = reference.NEAR_TIE_MARGIN * 0.9, reference.NEAR_TIE_MARGIN * 1.1
    assert reference.compare_greedy([1, 2], [1.0, far], [1, 9]) == (False, 1)
    assert reference.compare_greedy([1, 2], [1.0, near], [1, 9]) == (True, 1)
    # scored ids: every position counts, also after one that differs; an id may lie this far under and no further
    assert reference.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, near, 0.0]) == (True, 3, [0.2])
    assert reference.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, far, 0.0]) == (False, 3, [0.2])


@pytest.mark.parametrize("padded", [40, 64], ids=["whole", "padded-to-a-programs-length"])
def test_score_is_the_forward_pass_at_the_last_positions(tiny, padded):
    _, model, params = tiny
    seq = jnp.asarray(np.random.default_rng(6).integers(0, 96, size=(40,)), jnp.int32)
    given = jnp.pad(seq, (0, padded - 40))
    ids, margins, own = jax.jit(lambda p, s, n: reference.score(p, model, s, 5, length=n, q_block=16))(
        params, given, jnp.int32(40))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(reference.forward(params, model, seq))[34:39]
    np.testing.assert_array_equal(np.asarray(ids), logits.argmax(-1))
    top = np.sort(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(margins), top[:, -1] - top[:, -2], atol=1e-7)
    np.testing.assert_allclose(np.asarray(own), logits[np.arange(5), np.asarray(seq)[35:]] - top[:, -1], atol=1e-7)


def test_the_costs_are_the_issues_arithmetic_at_the_published_widths():
    cfg = _model("configs/granite-4.0-h-micro.json")["model"]
    assert round(costs.mamba_params(cfg) / 1e6, 2) == 25.82 and costs.mamba_small_params(cfg) == 4352 * 5 + 3 * 64 + 4096
    assert round((costs.mamba_params(cfg) + costs.mamba_small_params(cfg)) / 1e6, 2) == 25.85
    assert round(costs.mlp_params(cfg) / 1e6, 2) == 50.33 and round(costs.attn_params(cfg) / 1e6, 2) == 10.49
    assert round(costs.total_params(cfg) / 1e9, 3) == 3.191 and round(2 * costs.total_params(cfg) / 1e9, 2) == 6.38
    assert costs.recurrent_state_bytes(cfg) == 36 * 64 * 64 * 128 * 4 and round(costs.recurrent_state_bytes(cfg) / 1e6, 1) == 75.5
    assert costs.ssm_state_bytes(cfg) - costs.recurrent_state_bytes(cfg) == 36 * 3 * 4352 * 2  # 0.94 MB of convolution inputs
    assert costs.kv_bytes_per_token(cfg) == 8192
    # a slot: state, and K and V at 4096 rows; 48 of them beside the weights
    slot = costs.ssm_state_bytes(cfg) + 4096 * costs.kv_bytes_per_token(cfg)
    assert round(slot / 1e6) == 110 and round((48 * slot + 2 * costs.total_params(cfg)) / 1e9, 2) == 11.66
    # a decode step of 48 slots at 900 rows each: the state is more than the weights
    step = costs.decode_step_bytes(cfg, 48 * 900)
    assert 2 * 48 * costs.ssm_state_bytes(cfg) > 2 * costs.matmul_params(cfg) and 13.5e9 < step < 14.5e9
    assert costs.decode_step_bytes(cfg, 48 * 900, slots=12) < step
    assert costs.matmul_params(cfg) < costs.total_params(cfg)
    assert costs.train_flops_per_token(cfg, 4096) > 6 * costs.matmul_params(cfg)


def test_the_programs_tree_and_cache_are_the_sizes_the_costs_module_counts():
    from ray_tpu.models import granite_hybrid
    from ray_tpu.models.transformer import ModelConfig

    model = _model("configs/granite-4.0-h-micro.json")["model"]
    cfg = ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})
    assert granite_hybrid.num_params(cfg) == costs.total_params(model)
    assert granite_hybrid.state_bytes(cfg) == costs.ssm_state_bytes(model)
    caches = jax.eval_shape(lambda: granite_hybrid.init_caches(cfg, 48, 4096))
    held = sum(a.size * a.dtype.itemsize for c in caches for a in c)
    assert held == 48 * (costs.ssm_state_bytes(model) + 4096 * costs.kv_bytes_per_token(model))


def test_the_configuration_file_holds_the_catalogs_keys_and_the_one_cut():
    whole, bench = _model("configs/granite-4.0-h-micro.json"), json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == list(whole["reduced"]) == ["max_position_embeddings"] and entry["source"] == whole["source"]
    m = whole["model"]
    same = {"hidden_size": "hidden", "shared_intermediate_size": "mlp_dim", "num_attention_heads": "n_heads",
            "num_key_value_heads": "n_kv_heads", "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
            "max_position_embeddings": "max_seq", "rms_norm_eps": "norm_eps", "layer_types": "layer_types",
            "tie_word_embeddings": "tie_embeddings", "mamba_d_conv": "mamba_d_conv", "mamba_d_head": "mamba_d_head",
            "mamba_d_state": "mamba_d_state", "mamba_n_heads": "mamba_n_heads", "mamba_chunk_size": "mamba_chunk_size",
            "attention_multiplier": "attention_multiplier", "embedding_multiplier": "embedding_multiplier",
            "residual_multiplier": "residual_multiplier", "logits_scaling": "logits_scaling",
            "position_embedding_type": "position_embedding_type"}
    for published, field in same.items():
        assert whole[published] == m[field], (published, field)
    assert whole["mamba_expand"] * whole["hidden_size"] == m["mamba_n_heads"] * m["mamba_d_head"]
    assert whole["mamba_n_groups"] == 1 and whole["num_local_experts"] == 0
    assert [i for i, t in enumerate(whole["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    cut = whole["reduced"]["max_position_embeddings"]
    assert (cut["from"], cut["to"]) == (131072, 4096) == (whole["published_counts"]["max_position_embeddings"], whole["max_position_embeddings"])
    traffic = _model("traffic/sessions-closed48.json")
    assert traffic["max_seq"] == m["max_seq"] >= traffic["prompt_len"]["hi"] + traffic["max_tokens"]["hi"]
    assert traffic["slots"] == traffic["clients"] == costs.DECODE_SLOTS
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-micro", "sessions-closed48", 1)


def _events():
    """A traced window of one decode execution of 2 steps and one prefill chunk, by hand."""
    ops = [["while.1", "jit(rt_decode_multi_n2)/while", 100, 800],
           ["fusion.1", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/ssm/mul", 120, 100],
           ["fusion.2", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/conv/add", 300, 40],
           ["fusion.3", "jit(rt_decode_multi_n2)/while/body/layer_5/attn/kv_attn/dot", 400, 200],
           ["fusion.4", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/in_proj/dot", 620, 50],
           ["fusion.5", "jit(rt_prefill_b512)/layer_1/attn/ssm/dot", 1100, 300],
           ["fusion.6", "jit(rt_prefill_b512)/layer_1/mlp/dot", 1400, 500]]
    spans = [["rt.engine.dispatch", 90, 20, {"steps": 2, "slots": 40, "rows": 1000}, "stepper"]]
    return {"window": [0, 2000], "spans": spans,
            "modules": [["jit_rt_decode_multi_n2", 100, 800], ["jit_rt_prefill_b512", 1100, 800]],
            "ops": ops, "hlo": {}, "collectives": {}}


def test_the_scope_readers_on_a_recorded_window(monkeypatch):
    import run as R
    from lib import program_trace as pt

    events = _events()
    monkeypatch.setattr(pt, "for_record", lambda record: events if "trace" in record else None)
    monkeypatch.setattr(pt, "spans_named", lambda ev, name, whole=True: [s for s in ev["spans"] if s[0] == name])
    readers = R.load_metric_readers()
    model = _model("configs/granite-4.0-h-micro.json")["model"]
    record = {"trace": {}, "chips": 1, "block": "granite_hybrid", "model": model, "peaks": {"hbm_bytes_per_s": 819e9},
              "counters": {"state_prefill_positions": 5120, "state_prefill_padding": 640}}
    table = scope_trace_state.by_program_and_scope(events)
    assert table[("jit_rt_decode_multi_n2", None)] == 800 - 390 and table[("jit_rt_decode_multi_n2", "ssm")] == 100
    assert readers["ssm_dev_ms_per_step.sessions"].read(record) == pytest.approx(140 / 1e6 / 2)
    assert readers["kv_attn_dev_ms_per_step.sessions"].read(record) == pytest.approx(200 / 1e6 / 2)
    assert readers["ssm_prefill_share.sessions"].read(record) == pytest.approx(100 * 300 / 800)
    assert readers["scan_pad_share.sessions"].read(record) == 12.5
    # over whole mamba layers (layer 1 here; layer 5 is attention): 190 ns in 2 steps, against their
    # matrices and 40 slots' state read and written at 819 GB/s
    assert scope_trace_state.layers_ms_per_decode_step(events, {1}) == pytest.approx(190 / 1e6 / 2)
    assert readers["ssm_state_roofline.sessions"].read(record) == pytest.approx(
        100 * (costs.mamba_layers_step_bytes(model, 40) / 819e9) / (95 / 1e9))
    assert costs.mamba_layers_step_bytes(model, 40) == 36 * 2 * (costs.mamba_params(model) + costs.mlp_params(model)) + 80 * costs.ssm_state_bytes(model)
    # a program without the block's scopes and counts, as the parent commit is: nothing, and no error
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_decode", 100, 800]],
            "ops": [["fusion.1", "jit(rt_decode)/layer_1/attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    monkeypatch.setattr(pt, "for_record", lambda record: bare)
    for name in ("ssm_dev_ms_per_step.sessions", "ssm_state_roofline.sessions", "kv_attn_dev_ms_per_step.sessions",
                 "ssm_prefill_share.sessions", "scan_pad_share.sessions"):
        for block in ("granite_hybrid", None):  # and a dense configuration's record, whose costs module has no state
            assert readers[name].read(dict(record, counters={}, block=block)) is None


@pytest.mark.parametrize("start_s, lead_s, opens_late", [(0.0, 0.3, False), (0.5, 0.3, True), (0.2, 0.0, True)])
def test_the_traced_window_is_whole_whatever_the_profilers_start_takes(monkeypatch, start_s, lead_s, opens_late):
    """The driver's `trace_span`: `bench.window` lasts `trace_seconds` and opens at its time where the
    profiler's start fits the lead, and lasts `trace_seconds` all the same where it does not (the
    first check of PR 32: a start of 2.97 s left a window of 29 ms with `serving.trace_span`)."""
    import asyncio
    import time
    import types

    from drivers import serve_closed_state as driver

    marks = {}

    class Span:
        def __init__(self, name):
            assert name == "bench.window"

        def __enter__(self):
            marks["opened"] = time.monotonic()

        def __exit__(self, *exc):
            marks["closed"] = time.monotonic()

    def start_trace(trace_dir):
        time.sleep(start_s)
        marks["started"] = time.monotonic()

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: marks.setdefault("stopped", time.monotonic()))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    monkeypatch.setattr(driver, "TRACE_LEAD_S", lead_s)
    ctx, notes = types.SimpleNamespace(trace_dir="unused", traffic={"trace_seconds": 0.4}), []
    t0 = time.monotonic() + 0.4
    asyncio.run(driver.trace_span(ctx, t0, notes))
    assert marks["started"] <= marks["opened"] <= marks["closed"] <= marks["stopped"]
    assert 0.4 <= marks["closed"] - marks["opened"] < 0.5
    assert (marks["opened"] - t0 > 0.05) == opens_late and marks["opened"] >= t0
    assert len(notes) == 1 and notes[0].startswith("profiler: asked for ")


def test_the_cell_end_to_end_through_the_state_driver(monkeypatch, tmp_path, capsys):
    import run as R
    from lib import trace_reduce
    from ray_tpu._private.config import CONFIG

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny-granite.json"))
    monkeypatch.setattr(R, "TRAFFIC_DIR", os.path.join(HERE, "traffic"))
    monkeypatch.setattr(R, "require_chip", lambda chips: (
        jax.devices(), {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}))
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d, chips=1: {
        "busy_s": 0.5, "window_s": 1.0, "device_ops": [["fusion.1", 0.4]], "idle_gaps": [], "modules": []})
    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    CONFIG._cache.pop("llm_sched_token_budget", None)
    monkeypatch.delenv("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", raising=False)
    try:
        for trace, want in ((0, {"serve_out_tok_s", "tpot_ms_p90", "setup_s"}),
                            (1, {"compile_s", "window_compiles", "slot_occupancy.decode", "decode_tok_per_iter.decode",
                                 "tpot_ms_p50.serve", "decode_hbm_util.serve", "scan_pad_share.sessions",
                                 "ttft_ms_p90.sessions"})):
            assert R.main(["--workload", "tiny-granite.sessions", "--seed", "3000000007", "--seconds", "3",
                           "--trace", str(trace)]) == 0
            out = capsys.readouterr().out.strip().splitlines()
            line = json.loads(out[-1])
            assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, out
            assert set(line["metrics"]) == want
            # both comparisons: the probes sent before the window, and a sample of what the window finished
            assert any("probes of 36 + 6 tokens" in l and "enough=True" in l for l in out)
            assert any("requests the window finished" in l and "enough=True" in l for l in out)
            if trace:
                assert 0 < line["metrics"]["scan_pad_share.sessions"]["value"] < 50
                assert line["metrics"]["window_compiles"]["value"] == 0
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)


@pytest.mark.parametrize("args, says", [(["control", "--long"], "self-token z-score")])
def test_the_calibration_tool_runs_at_the_tests_widths(args, says):
    """`tools/calibrate_granite_hybrid.py` is run by hand on the chip when the block's limits need
    their readings again; here only that its readings come out at tiny widths, in a process of their own."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "calibrate_granite_hybrid.py"), *args,
                          "--seed", "5", "--tiny"], capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0 and says in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
