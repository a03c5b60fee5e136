"""The `lfm2` block's benchmark files on the CPU at tiny widths: the benchmark's plain reference
(`lib/reference_lfm2.py`, which imports nothing of the program) against the repo's
(`ray_tpu/models/lfm2.py:forward_plain`), the control (float8 operands) against the stated
precision, the costs module against ISSUE 34's arithmetic at the published widths, the
configuration file against the catalog's keys, the new readers on a recorded trace's events and
a recorded note, and `run.py` end to end through `drivers/serve_closed_state.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import blocks, costs_lfm2 as costs, expert_counts, reference_lfm2 as reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "lfm2-24b-a2b.serve-decode64"
NEW = ("experts_roofline.decode64", "experts_hit_per_step.decode64", "expert_load_max_over_mean.decode64",
       "conv_dev_ms_per_step.decode64", "experts_prefill_share.decode64")


def _model(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


def _config(model):
    from ray_tpu.models.transformer import ModelConfig

    return ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models import lfm2

    model = _model("tests/configs/tiny-lfm2.json")["model"]
    cfg = _config(model)
    return cfg, model, lfm2.init_params(cfg, jax.random.PRNGKey(4))


def fp8(a):
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)  # a pair of converts the compiler may drop


def test_the_block_has_every_name_the_harness_and_the_driver_ask():
    assert blocks.reference({"block": "lfm2"}) is reference
    assert blocks.costs({"block": "lfm2"}) is costs
    for name in ("score", "compare_scored", "MEAN_DEFICIT_TOL", "NEAR_TIE_MARGIN", "FAR_SHARE_TOL", "MIN_COMPARED_POSITIONS", "MAX_PROBES"):
        assert hasattr(reference, name), name
    assert hasattr(costs, "experts_step_bytes") and hasattr(costs, "experts_hit") and costs.DECODE_TOKENS == 64


@pytest.mark.parametrize("q_block", [16, 7, 64], ids=["blocks", "blocks-that-pad", "one-block"])
def test_the_benchmarks_reference_is_the_repos_plain_reference(tiny, q_block):
    """Two forward passes written apart: the repo's loops over the experts in Python and sums the
    chosen ones' weights with the program's `sigmoid_routing`; this one routes by its own lines."""
    from ray_tpu.models import lfm2

    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 96, size=(45,)), jnp.int32)
    want = np.asarray(lfm2.forward_plain(params, cfg, tokens))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: reference.forward(p, model, t, q_block=q_block))(params, tokens))
    np.testing.assert_allclose(got, want, atol=2e-6)
    targets = jnp.roll(tokens, -1)
    assert float(reference.loss(params, model, tokens, targets)) == pytest.approx(
        float(jnp.mean(reference.token_losses(params, model, tokens, targets))))


def test_greedy_by_full_passes_walks_the_repos_argmax(tiny):
    from ray_tpu.models import lfm2

    cfg, model, params = tiny
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 96, size=(30,)), jnp.int32)
    ids, margins = jax.jit(lambda p, x: reference.greedy(p, model, x, 5))(params, prompt)
    seq = list(np.asarray(prompt))
    for j in range(5):
        logits = np.asarray(lfm2.forward_plain(params, cfg, jnp.asarray(seq, jnp.int32)))[-1]
        assert int(np.argmax(logits)) == int(ids[j])
        top = np.sort(logits)[-2:]
        assert float(margins[j]) == pytest.approx(top[1] - top[0], abs=1e-6)
        seq.append(int(ids[j]))


def test_the_control_moves_the_logits_far_more_than_the_stated_precision(tiny):
    """The contract's control on this block, at a size a test holds: both operands of every matrix
    product but the router's rounded to float8 e4m3. On the chip the limits it has to fail are on
    the scored ids (PERF.md §6, PR 34); here the same rounding is read on the logits, beside
    bfloat16's."""
    _, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 96, size=(45,)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = reference.forward(params, model, tokens)
        rms = {name: float(jnp.sqrt(jnp.mean((reference.forward(params, model, tokens, operand=op) - ref) ** 2)))
               for name, op in (("fp8", fp8), ("bf16", bf16))}
    assert rms["fp8"] > 4 * rms["bf16"] > 0
    near, far = reference.NEAR_TIE_MARGIN * 0.9, reference.NEAR_TIE_MARGIN * 1.1
    assert reference.compare_greedy([1, 2], [1.0, far], [1, 9]) == (False, 1)
    assert reference.compare_greedy([1, 2], [1.0, near], [1, 9]) == (True, 1)
    # scored ids: every position counts, also after one that differs; of a sequence's positions a quarter
    # may lie further under than the margin (a router's flip is a heavy tail), and no more
    assert reference.compare_scored([1, 2, 3, 4], [1.0, 0.2, 1.0, 1.0], [1, 9, 3, 4], [0.0, far, 0.0, 0.0]) == (True, 4, [0.2])
    assert reference.compare_scored([1, 2, 3, 4], [1.0, 0.2, 1.0, 1.0], [1, 9, 3, 4], [0.0, far, near, 0.0]) == (True, 4, [0.2])
    assert reference.compare_scored([1, 2, 3, 4], [1.0, 0.2, 1.0, 1.0], [1, 9, 8, 4], [0.0, far, far, 0.0]) == (False, 4, [0.2, 1.0])
    assert reference.compare_scored([7] * 16, [1.0] * 16, [7] * 16, [far] * 4 + [0.0] * 12)[0] is True
    assert reference.compare_scored([7] * 16, [1.0] * 16, [7] * 16, [far] * 5 + [0.0] * 11)[0] is False
    assert reference.MEAN_DEFICIT_TOL < reference.NEAR_TIE_MARGIN * reference.FAR_SHARE_TOL * 2


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
    np.testing.assert_allclose(np.asarray(margins), top[:, -1] - top[:, -2], atol=2e-6)
    np.testing.assert_allclose(np.asarray(own), logits[np.arange(5), np.asarray(seq)[35:]] - top[:, -1], atol=2e-6)


def test_the_routed_sum_is_the_chosen_experts_weighted_by_their_scores(tiny):
    """The reference's expert layer against a loop a token: four lines of numpy."""
    _, model, params = tiny
    p = jax.tree_util.tree_map(np.asarray, params["layer_2"]["mlp"])
    m = np.random.default_rng(8).normal(size=(5, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.experts(params["layer_2"]["mlp"], jnp.asarray(m), model))
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    for t in range(5):
        s = 1.0 / (1.0 + np.exp(-(m[t] @ p["router"]["kernel"])))
        ids = np.argsort(-(s + p["router"]["bias"]), kind="stable")[:2]
        want = sum(s[e] / (s[ids].sum() + 1e-6) * ((silu(m[t] @ p["experts"]["gate"][e]) * (m[t] @ p["experts"]["up"][e]))
                                                    @ p["experts"]["down"][e]) for e in ids)
        np.testing.assert_allclose(got[t], want, atol=2e-6)


def test_the_costs_are_the_issues_arithmetic_at_the_published_widths():
    cfg = _model("configs/lfm2-24b-a2b.json")["model"]
    assert round((costs.conv_params(cfg) + 3 * 2048) / 1e6, 2) == 16.78 and round((costs.attn_params(cfg) + 128) / 1e6, 2) == 10.49
    assert round(costs.expert_params(cfg) / 1e6, 3) == 9.437 and round(64 * costs.expert_params(cfg) / 1e6, 2) == 603.98
    assert round(costs.total_params(cfg) / 1e9, 3) == 5.178 and round(2 * costs.total_params(cfg) / 1e9, 2) == 10.36
    assert round(2 * 8 * 64 * costs.expert_params(cfg) / 1e9, 2) == 9.66  # the experts' share of the weights
    assert costs.conv_state_bytes(cfg) == 7 * 2 * 2048 * 2 == 57344 and costs.kv_bytes_per_token(cfg) == 4096
    # a slot: convolution inputs, and K and V at 4096 rows; 64 of them beside the weights
    slot = costs.conv_state_bytes(cfg) + 4096 * costs.kv_bytes_per_token(cfg)
    assert round(slot / 1e6, 1) == 16.8 and round(64 * slot / 1e9, 2) == 1.08
    assert round((64 * slot + 2 * costs.total_params(cfg)) / 1e9, 2) == 11.43
    # 64 decoding slots hit 63 of a layer's 64 experts; 12 hit 34 and 16 hit 41
    assert round(costs.experts_hit(cfg, 64)) == 63 and round(costs.experts_hit(cfg, 12)) == 34 and round(costs.experts_hit(cfg, 16)) == 41
    hit = costs.experts_hit(cfg, 64)
    assert round(costs.experts_step_bytes(cfg, hit) / 1e9, 1) == 9.5 and costs.experts_step_bytes(cfg, 64) == 2 * 8 * 64 * costs.expert_params(cfg)
    step = costs.decode_step_bytes(cfg, 64 * 1500)
    assert round(step / 1e9, 1) == 10.6 and costs.decode_step_bytes(cfg, 64 * 1500, tokens=12) < 0.6 * step
    # what a token multiplies: 2B-class active parameters at full depth, 0.65B in this cut
    assert costs.matmul_params(cfg) == costs.fixed_matmul_params(cfg) + 8 * 4 * costs.expert_params(cfg) < costs.total_params(cfg) / 7
    assert costs.train_flops_per_token(cfg, 4096) > 6 * costs.matmul_params(cfg)


def test_the_programs_tree_and_cache_are_the_sizes_the_costs_module_counts():
    from ray_tpu.models import lfm2

    model = _model("configs/lfm2-24b-a2b.json")["model"]
    cfg = _config(model)
    assert lfm2.num_params(cfg) == costs.total_params(model)
    assert lfm2.state_bytes(cfg) == costs.conv_state_bytes(model)
    caches = jax.eval_shape(lambda: lfm2.init_caches(cfg, 64, 4096))
    held = sum(a.size * a.dtype.itemsize for c in caches for a in c)
    assert held == 64 * (costs.conv_state_bytes(model) + 4096 * costs.kv_bytes_per_token(model))


def test_the_configuration_file_holds_the_catalogs_keys_and_the_four_cuts():
    whole, bench = _model("configs/lfm2-24b-a2b.json"), json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == list(whole["reduced"]) == ["num_hidden_layers", "layer_types", "num_dense_layers", "max_position_embeddings"]
    assert entry["source"] == whole["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    m = whole["model"]
    same = {"hidden_size": "hidden", "intermediate_size": "mlp_dim", "num_attention_heads": "n_heads",
            "num_key_value_heads": "n_kv_heads", "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
            "max_position_embeddings": "max_seq", "norm_eps": "norm_eps", "layer_types": "layer_types",
            "num_dense_layers": "first_k_dense", "num_experts": "n_routed_experts_total", "num_experts_per_tok": "experts_per_token",
            "moe_intermediate_size": "moe_mlp_dim", "routed_scaling_factor": "routed_scaling_factor", "conv_L_cache": "conv_L_cache"}
    for published, field in same.items():
        assert whole[published] == m[field], (published, field)
    # the published widths, uncut
    assert (whole["hidden_size"], whole["num_attention_heads"], whole["num_key_value_heads"], whole["intermediate_size"]) == (2048, 32, 8, 11776)
    assert (whole["num_experts"], whole["moe_intermediate_size"], whole["num_experts_per_tok"], whole["vocab_size"], whole["conv_L_cache"]) == (64, 1536, 4, 65536, 3)
    assert m["n_routed_experts"] == m["n_routed_experts_total"] and m["first_expert"] == 0 and m["n_shared_experts"] == 0
    assert whole["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"} and m["rope_theta"] == 1e6
    assert whole["conv_bias"] is False and whole["use_expert_bias"] is True and whole["norm_topk_prob"] is True and m["tie_embeddings"] is True
    assert whole["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]
    for key, (was, now) in {"num_hidden_layers": (40, 9), "num_dense_layers": (2, 1), "max_position_embeddings": (128000, 4096)}.items():
        assert (whole["reduced"][key]["from"], whole["reduced"][key]["to"]) == (was, now) == (whole["published"][key], whole[key])
    assert set(whole["assumed"]) >= {"tie_word_embeddings", "expert_bias", "weights", "embedding", "conv_state_dtype"}
    assert "5 pipeline stages" in whole["stands_for"] and "1 chip shares a layer" in whole["stands_for"]
    traffic = _model("traffic/decode-closed64.json")
    assert traffic["max_seq"] == m["max_seq"] >= traffic["prompt_len"]["hi"] + traffic["max_tokens"]["hi"]
    assert traffic["slots"] == traffic["clients"] == costs.DECODE_TOKENS and traffic["driver"] == "serve_closed_state"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "lo": 128, "hi": 2048}
    assert traffic["max_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "lo": 256, "hi": 1024}
    assert (traffic["pool"], traffic["order_seed"], traffic["phase"], traffic["ramp_seconds"], traffic["trace_seconds"]) == (96, 34, 0, 12, 3)
    assert traffic["flags"]["llm_sched_token_budget"] == 1024 and traffic["temperature"] == 0.0
    assert traffic["window_check"]["n_last"] <= traffic["max_tokens"]["lo"] and max(traffic["window_check"]["lens"]) == 3072
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-24b-a2b", "decode-closed64", 1)
    for name in NEW:
        assert next(x for x in bench["per_layer"] if x["name"] == name)["workloads"] == [CELL]


def _events():
    """A traced window of one decode execution of 2 steps and one prefill chunk, by hand."""
    ops = [["while.1", "jit(rt_decode_multi_n2)/while", 100, 800],
           ["fusion.1", "jit(rt_decode_multi_n2)/while/body/layer_2/mlp/experts/while/body/dot_general", 120, 300],
           ["fusion.2", "jit(rt_decode_multi_n2)/while/body/layer_2/attn/conv/add", 430, 40],
           ["fusion.3", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/kv_attn/dot", 480, 100],
           ["fusion.4", "jit(rt_decode_multi_n2)/while/body/layer_2/attn/in_proj/dot", 620, 50],
           ["fusion.5", "jit(rt_decode_multi_n2)/while/body/layer_2/attn/out_proj/dot", 700, 30],
           ["fusion.6", "jit(rt_decode_multi_n2)/while/body/layer_2/mlp/router/dot", 750, 20],
           ["fusion.7", "jit(rt_prefill_b512)/layer_2/mlp/experts/while/body/dot_general", 1100, 600],
           ["fusion.8", "jit(rt_prefill_b512)/layer_2/attn/in_proj/dot", 1700, 200]]
    spans = [["rt.engine.dispatch", 90, 20, {"steps": 2, "slots": 64, "rows": 96000}, "stepper"]]
    return {"window": [0, 2000], "spans": spans,
            "modules": [["jit_rt_decode_multi_n2", 100, 800], ["jit_rt_prefill_b512", 1100, 800]],
            "ops": ops, "hlo": {}, "collectives": {}}


def test_the_new_readers_on_a_recorded_window_and_a_recorded_note(monkeypatch):
    import run as R
    from lib import program_trace as pt

    events = _events()
    monkeypatch.setattr(pt, "for_record", lambda record: events if "trace" in record else None)
    readers = R.load_metric_readers()
    model = _model("configs/lfm2-24b-a2b.json")["model"]
    window = {"pairs_routed": 40960, "pairs_held": 40960, "experts_hit": 5100, "tiles_run": 5300, "layer_steps": 88,
              "decode_experts_hit": 5040, "decode_layer_steps": 80, "max_load": 800, "mean_load": 640.0}
    record = {"trace": {}, "chips": 1, "block": "lfm2", "model": model, "peaks": {"hbm_bytes_per_s": 819e9},
              "counters": {}, "notes": ["host: fine", f"experts in the window: {window}"]}
    assert expert_counts.window(record) == window and expert_counts.hit_per_decode_step(record) == 63.0
    assert readers["experts_hit_per_step.decode64"].read(record) == 63.0
    assert readers["expert_load_max_over_mean.decode64"].read(record) == 1.25
    assert readers["conv_dev_ms_per_step.decode64"].read(record) == pytest.approx(120 / 1e6 / 2)
    assert readers["experts_prefill_share.decode64"].read(record) == pytest.approx(75.0)
    assert readers["experts_dev_ms_per_step.longctx"].read(record) == pytest.approx(300 / 1e6 / 2)
    # 63 experts a layer hit, 8 layers, three matrices each in bf16, against 150 ns a step
    assert readers["experts_roofline.decode64"].read(record) == pytest.approx(
        100 * (8 * 63 * 3 * 2048 * 1536 * 2 / 819e9) / (150 / 1e9))
    # a program without the block's scopes and counts, as the parent commit is: nothing, and no error
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_decode", 100, 800]],
            "ops": [["fusion.1", "jit(rt_decode)/layer_1/attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    monkeypatch.setattr(pt, "for_record", lambda record: bare)
    for name in NEW:
        for block, notes in (("lfm2", []), (None, ["experts in the window: not a dict"]), ("dots3", None)):
            assert readers[name].read(dict(record, block=block, notes=notes)) is None
    # dots3's window has no such counts: the note is there and the readers still return nothing
    monkeypatch.setattr(pt, "for_record", lambda record: events)
    old = dict(record, block="dots3", notes=["experts in the window: {'pairs_routed': 9, 'pairs_held': 1, 'max_load': 1, 'mean_load': 0.5}"])
    assert readers["experts_hit_per_step.decode64"].read(old) is None and readers["experts_roofline.decode64"].read(old) is None


def test_the_cell_end_to_end_through_the_state_driver(monkeypatch, tmp_path, capsys):
    import run as R
    from lib import trace_reduce
    from ray_tpu._private.config import CONFIG

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny-lfm2.json"))
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
                                 "ttft_ms_p90.sessions", "experts_hit_per_step.decode64",
                                 "expert_load_max_over_mean.decode64"})):
            assert R.main(["--workload", "tiny-lfm2.decode", "--seed", "3000000007", "--seconds", "3",
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
                # 4 slots of 2 experts a token over 8: between 2 and 8 hit a layer and step
                assert 2 <= line["metrics"]["experts_hit_per_step.decode64"]["value"] <= 8
                assert line["metrics"]["expert_load_max_over_mean.decode64"]["value"] >= 1
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)


@pytest.mark.parametrize("args, says", [(["control"], "self-token z-score")])
def test_the_calibration_tool_runs_at_the_tests_widths(args, says):
    """`tools/calibrate_lfm2.py` is run by hand on the chip when the block's limits need their
    readings again; here only that its readings come out at tiny widths, in a process of their own."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "calibrate_lfm2.py"), *args,
                          "--seed", "5", "--tiny"], capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0 and says in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
