"""The `pangu_moe` block's benchmark files on the CPU at tiny widths: the benchmark's plain
reference (`lib/reference_pangu_moe.py`, which imports nothing of the program) against the repo's
(`ray_tpu/models/pangu_moe.py:forward_plain`) whatever its loops' sizes, the control (one precision
below bfloat16) and a left-out norm against it, the costs module against ISSUE 39's arithmetic and
the program's own tree, the new readers on a recorded trace's events, and `run.py` end to end
through `drivers/serve_closed_counts.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import blocks, costs_pangu_moe, reference_pangu_moe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "openpangu-ultra-moe-718b.serve-longctx-mla"
NEW = ("latent_dev_ms_per_step.mla", "latent_roofline.mla", "latent_attn_roofline.mla", "latent_prefill_share.mla",
       "latent_rows_read_over_live.mla")


def _model(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


def _config(model):
    from ray_tpu.models.transformer import ModelConfig

    return ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models import pangu_moe

    model = _model("tests/configs/tiny-pangu.json")["model"]
    cfg = _config(model)
    return cfg, model, pangu_moe.init_params(cfg, jax.random.PRNGKey(4))


def fp8(a):
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def test_the_block_has_every_name_the_harness_asks():
    assert blocks.reference({"block": "pangu_moe"}) is reference_pangu_moe
    assert blocks.costs({"block": "pangu_moe"}) is costs_pangu_moe
    assert callable(reference_pangu_moe.score) and callable(reference_pangu_moe.compare_scored)
    assert reference_pangu_moe.MEAN_DEFICIT_TOL > 0


@pytest.mark.parametrize("q_block, head_group, columns", [(16, 8, 2048), (4, 2, 8), (64, 1, 12)],
                         ids=["blocks", "head-groups-and-columns", "one-block"])
def test_the_benchmarks_reference_is_the_repos_plain_reference(tiny, monkeypatch, q_block, head_group, columns):
    """Two forward passes written apart: a share of the experts (8 to 15 of 32), query blocks that
    cut the sequence (and pad it: 45 is no multiple of any), the row-wise parts by blocks of rows,
    heads taken in groups, a gated product's inner width by blocks of columns."""
    from ray_tpu.models import pangu_moe

    cfg, model, params = tiny
    monkeypatch.setattr(reference_pangu_moe, "HEAD_GROUP", head_group)
    monkeypatch.setattr(reference_pangu_moe, "COLUMNS", columns)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 96, size=(45,)), jnp.int32)
    want = np.asarray(pangu_moe.forward_plain(params, cfg, tokens))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: reference_pangu_moe.forward(p, model, t, q_block=q_block))(params, tokens))
    np.testing.assert_allclose(got, want, atol=2e-5)
    targets = jnp.roll(tokens, -1)
    assert float(reference_pangu_moe.loss(params, model, tokens, targets)) == pytest.approx(
        float(jnp.mean(reference_pangu_moe.token_losses(params, model, tokens, targets))))


def test_greedy_by_full_passes_walks_the_repos_argmax(tiny):
    from ray_tpu.models import pangu_moe

    cfg, model, params = tiny
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 96, size=(30,)), jnp.int32)
    ids, margins = jax.jit(lambda p, x: reference_pangu_moe.greedy(p, model, x, 5))(params, prompt)
    seq = list(np.asarray(prompt))
    for j in range(5):
        logits = np.asarray(pangu_moe.forward_plain(params, cfg, jnp.asarray(seq, jnp.int32)))[-1]
        assert int(np.argmax(logits)) == int(ids[j])
        top = np.sort(logits)[-2:]
        assert float(margins[j]) == pytest.approx(top[1] - top[0], abs=1e-4)
        seq.append(int(ids[j]))


def test_the_control_and_a_left_out_norm_move_the_logits_far_more_than_the_stated_precision(tiny):
    """The contract's control on this block, at a size a test holds: both operands of every matrix
    product but the router's rounded to float8 e4m3. On the chip the limit it has to fail is
    MEAN_DEFICIT_TOL on the scored ids (PERF.md §6, PR 39); here the same rounding is read on the
    logits, beside bfloat16's and beside the reference with one of a layer's four norms left out."""
    _, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 96, size=(45,)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = reference_pangu_moe.forward(params, model, tokens)
        rms = lambda **kw: float(jnp.sqrt(jnp.mean((reference_pangu_moe.forward(params, model, tokens, **kw) - ref) ** 2)))  # noqa: E731
        rms8, rms16 = rms(operand=fp8), rms(operand=bf16)
        dropped = [rms(drop=name) for name in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")]
    assert rms8 > 2 * rms16 > 0
    assert min(dropped) > 5 * rms16  # another function, not a rounding of this one in the stated precision
    near, far = reference_pangu_moe.NEAR_TIE_MARGIN - 0.01, reference_pangu_moe.NEAR_TIE_MARGIN + 0.01
    assert reference_pangu_moe.compare_greedy([1, 2], [1.0, far], [1, 9]) == (False, 1)
    assert reference_pangu_moe.compare_greedy([1, 2], [1.0, near], [1, 9]) == (True, 1)
    # scored ids: every position counts, also after one that differs; an id may lie this far under and no further
    assert reference_pangu_moe.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, near, 0.0]) == (True, 3, [0.2])
    assert reference_pangu_moe.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, far, 0.0]) == (False, 3, [0.2])


@pytest.mark.parametrize("padded", [40, 64], ids=["whole", "padded-to-a-programs-length"])
def test_score_is_the_forward_pass_at_the_last_positions(tiny, padded):
    _, model, params = tiny
    seq = jnp.asarray(np.random.default_rng(6).integers(0, 96, size=(40,)), jnp.int32)
    given = jnp.pad(seq, (0, padded - 40))
    ids, margins, own = jax.jit(lambda p, s, n: reference_pangu_moe.score(p, model, s, 5, length=n, q_block=16))(
        params, given, jnp.int32(40))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(reference_pangu_moe.forward(params, model, seq))[34:39]
    np.testing.assert_array_equal(np.asarray(ids), logits.argmax(-1))
    top = np.sort(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(margins), top[:, -1] - top[:, -2], atol=1e-5)
    np.testing.assert_allclose(np.asarray(own), logits[np.arange(5), np.asarray(seq)[35:]] - top[:, -1], atol=1e-5)


def test_the_costs_are_the_issues_arithmetic_and_the_programs_own_tree():
    from ray_tpu.models import pangu_moe

    cfg = _model("configs/openpangu-ultra-moe-718b.json")["model"]
    C = costs_pangu_moe
    D = cfg["hidden"]
    # ISSUE 39: attention 196.58M (11.80 + 37.75 + 4.42 + 16.78 + 125.83), shared expert and a routed expert 47.19M, router 1.97M
    assert round(C.attn_params(cfg) / 1e6, 2) == 196.58 == round(11.80 + 37.75 + 4.42 + 16.78 + 125.83, 2)
    assert round(C.expert_params(cfg) / 1e6, 2) == 47.19 and round(D * 256 / 1e6, 2) == 1.97
    expert_layer = C.attn_params(cfg) + D * 256 + 9 * C.expert_params(cfg)
    dense_layer = C.attn_params(cfg) + 3 * D * cfg["mlp_dim"]
    # the issue's 623.3M and 621.3M add rounded parts (245.8M + 8 x 47.19M; 196.58M + 424.67M): 623.21M and 621.25M
    assert round(expert_layer / 1e6, 2) == 623.21 and round(dense_layer / 1e6, 2) == 621.25
    assert round(2 * cfg["vocab_size"] * D / 1e6, 1) == 294.9
    assert C.total_params(cfg) == dense_layer + 5 * expert_layer + 2 * cfg["vocab_size"] * D + C.norm_params(cfg)
    assert round(C.total_params(cfg) / 1e9, 2) == 4.03 and round(2 * C.total_params(cfg) / 1e9, 2) == 8.06
    # the program's own tree, leaf by leaf
    assert C.total_params(cfg) == pangu_moe.num_params(_config(cfg))
    # a cached token: 576 values a layer, 1152 bytes read and 1280 held; 278,528 operations a row a layer
    assert C.latent_row_bytes(cfg) == 1152 and C.kv_bytes_per_token(cfg) == 6912 and C.latent_row_flops(cfg) == 278528
    held = jax.eval_shape(lambda: pangu_moe.init_caches(_config(cfg), 16, 32768))
    assert sum(a.size * 2 for (a,) in held) == 6 * 16 * 32768 * 1280
    # at 241 FLOP a byte the attention sits on a v5e's ridge: the two sides of its roofline are within 2%
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    rows = 16 * 12288
    by_bytes, by_flops = 6 * rows * 1152 / 819e9, 6 * rows * 278528 / 197e12
    assert C.latent_step_need_s(cfg, rows, peaks) == max(by_bytes, by_flops) and abs(by_bytes / by_flops - 1) < 0.02
    # a call of the kernel is one layer's part of the step: the mathematics' work, not the 640 lanes a row is kept in
    assert C.latent_attn_call_need_s(cfg, rows, peaks) == pytest.approx(max(rows * 1152 / 819e9, rows * 278528 / 197e12))
    # a decode step of 16 slots at 12k rows each: the fixed matrices, the experts hit (3.2 of 8 a layer), every live row
    step = C.decode_step_bytes(cfg, rows)
    assert 3.1 < C.experts_hit(cfg, 16) < 3.3 and 6.5e9 < step < 7.2e9 < 2 * C.total_params(cfg)
    assert C.decode_step_bytes(cfg, rows, tokens=1) < step and C.decode_step_bytes(cfg, 0) < step - rows * 6900
    assert C.matmul_params(cfg) < C.total_params(cfg)
    assert C.train_flops_per_token(cfg, 4096) > 6 * C.matmul_params(cfg)


def test_the_configuration_file_holds_the_catalogs_keys_and_the_cuts():
    whole, bench = _model("configs/openpangu-ultra-moe-718b.json"), json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "openpangu-ultra-moe-718b")
    assert sorted(entry["reduced"]) == sorted(whole["reduced"]) and entry["source"] == whole["source"]
    assert sorted(whole["reduced"]) == sorted(["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
                                               "max_position_embeddings", "num_nextn_predict_layers"])
    catalog = {"attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
               "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
               "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
               "routed_scaling_factor": 2.5, "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
    for key, published in catalog.items():  # every key of the catalog's row, changed only where `reduced` says, and to what it says
        if key in whole["reduced"]:
            assert whole["reduced"][key]["from"] == published and whole["reduced"][key]["to"] == whole[key] != published
            assert whole["published_counts"][key] == published and {"from", "to", "why"} <= set(whole["reduced"][key])
        else:
            assert whole[key] == published, key
    m = whole["model"]
    same = {"hidden_size": "hidden", "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
            "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads", "num_experts_per_tok": "experts_per_token",
            "rope_theta": "rope_theta", "num_hidden_layers": "n_layers", "n_routed_experts": "n_routed_experts",
            "vocab_size": "vocab_size", "max_position_embeddings": "max_seq", "rms_norm_eps": "norm_eps",
            "first_k_dense_replace": "first_k_dense", "n_shared_experts": "n_shared_experts",
            "tie_word_embeddings": "tie_embeddings"}
    same.update({k: k for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "routed_scaling_factor")})
    for published, field in same.items():
        assert whole[published] == m[field], (published, field)
    assert m["n_routed_experts_total"] == 256 and m["mla_rescale"] is False and m["block"] == whole["block"] == "pangu_moe"
    assert whole["sandwich_norm"] is True and "sandwich_norm" not in m  # the block is the one with the post-norms: no field says so
    assert {"routing", "rotary", "norms", "weights", "cache", "mtp"} <= set(whole["assumed"]) and "32 chips" in whole["stands_for"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("openpangu-ultra-moe-718b", "longctx-mla16", 1)
    traffic = _model("traffic/longctx-mla16.json")
    assert traffic["order_seed"] == 39 and traffic["phase"] == 0 and traffic["slots"] == traffic["clients"] == 16
    assert max(traffic["window_check"]["lens"]) >= traffic["prompt_len"]["hi"] + traffic["max_tokens"]["hi"]
    for name in NEW:
        entry = next(mm for mm in bench["per_layer"] if mm["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == "model block"
    config = bench["configs"][-1]  # new entries at the end of their lists, in the form a file is refused for before any run
    assert config["name"] == "openpangu-ultra-moe-718b" and bench["workloads"][-1] is cell and len(config["source"]) <= 200
    for said in (config, cell):
        assert 1 <= len(said["why"]) <= 200 and "\n" not in said["why"] and "\t" not in said["why"], (said["name"], len(said["why"]))


def _events():
    """A traced window of one decode execution of 2 steps (two layers' kernel calls each) and one prefill chunk, by hand."""
    ops = [["while.1", "jit(rt_decode_multi_n2)/while", 100, 800],
           ["fusion.1", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/latent/dot", 120, 40],
           ["latent_attn.3", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/latent/latent_attn/pallas_call", 160, 100],
           ["latent_attn.4", "jit(rt_decode_multi_n2)/while/body/layer_2/attn/latent/latent_attn/pallas_call", 270, 120],
           ["fusion.2", "jit(rt_decode_multi_n2)/while/body/layer_1/mlp/experts/while/body/dot", 400, 200],
           ["latent_attn.3", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/latent/latent_attn/pallas_call", 610, 100],
           ["latent_attn.4", "jit(rt_decode_multi_n2)/while/body/layer_2/attn/latent/latent_attn/pallas_call", 720, 140],
           ["fusion.4", "jit(rt_prefill_b1024)/layer_1/attn/latent/while/body/dot", 1100, 600],
           ["fusion.5", "jit(rt_prefill_b1024)/layer_1/mlp/experts/dot", 1700, 200]]
    spans = [["rt.engine.prefill", 1000, 50, {"tokens": 500}, "stepper"], ["rt.engine.dispatch", 90, 5, {"rows": 1000}, "stepper"],
             ["rt.engine.dispatch", 95, 5, {"rows": 3000}, "stepper"]]
    return {"window": [0, 2000], "spans": spans, "modules": [["jit_rt_decode_multi_n2", 100, 800], ["jit_rt_prefill_b1024", 1100, 800]],
            "ops": ops, "hlo": {}, "collectives": {}}


def test_the_new_readers_on_a_recorded_window(monkeypatch):
    import run as R
    from lib import program_trace as pt

    events = _events()
    monkeypatch.setattr(pt, "for_record", lambda record: events if "trace" in record else None)
    monkeypatch.setattr(pt, "stepper_spans", lambda ev: ev["spans"])
    readers = R.load_metric_readers()
    model = _model("configs/openpangu-ultra-moe-718b.json")["model"]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    record = {"trace": {}, "chips": 1, "block": "pangu_moe", "model": model, "peaks": peaks,
              "counters": {"latent_rows_visible": 2000, "latent_rows_read": 2600}}
    latent_ns = 40 + 100 + 120 + 100 + 140
    assert readers["latent_dev_ms_per_step.mla"].read(record) == pytest.approx(latent_ns / 1e6 / 2)
    need = costs_pangu_moe.latent_step_need_s(model, 2000.0, peaks)
    assert readers["latent_roofline.mla"].read(record) == pytest.approx(100 * need / (latent_ns / 1e9 / 2))
    call = costs_pangu_moe.latent_attn_call_need_s(model, 2000.0, peaks)
    assert readers["latent_attn_roofline.mla"].read(record) == pytest.approx(100 * call / (120 / 1e9))  # `stats.pctl`'s median of 100, 120, 100, 140
    assert readers["latent_prefill_share.mla"].read(record) == pytest.approx(100 * 600 / 800)
    assert readers["latent_rows_read_over_live.mla"].read(record) == 1.3
    # a program without the block's scopes, kernel and counts, as the parent commit is, and another block's costs: nothing, and no error
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_decode", 100, 800]],
            "ops": [["fusion.1", "jit(rt_decode)/layer_1/attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    monkeypatch.setattr(pt, "for_record", lambda record: bare)
    for name in NEW:
        for rec in ({"trace": {}, "chips": 1, "block": "dots3", "model": model, "peaks": peaks, "counters": {}},
                    {"chips": 1, "block": "pangu_moe", "model": model, "peaks": peaks, "counters": {}, "notes": ["experts in the window: {'pairs_routed': 1}"]}):
            assert readers[name].read(rec) is None, name


def test_the_cell_end_to_end_through_the_counts_driver(monkeypatch, tmp_path, capsys):
    import run as R
    from lib import trace_reduce
    from ray_tpu._private.config import CONFIG

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny-pangu.json"))
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
                                 "tpot_ms_p50.serve", "decode_hbm_util.serve", "expert_pairs_held_share.longctx",
                                 "latent_rows_read_over_live.mla"})):
            assert R.main(["--workload", "tiny-pangu.longctx-mla", "--seed", "3000000007", "--seconds", "3",
                           "--trace", str(trace)]) == 0
            out = capsys.readouterr().out.strip().splitlines()
            line = json.loads(out[-1])
            assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, out
            assert set(line["metrics"]) == want
            assert any("experts in the window" in l and "pairs_held" in l and "latent_rows" not in l for l in out)
            # both comparisons: the probes sent before the window, and a sample of what the window finished
            assert any("probes of 36 + 6 tokens" in l and "enough=True" in l for l in out)
            assert any("requests the window finished" in l and "enough=True" in l for l in out)
            if trace:  # 8 of 32 experts held: a quarter of the pairs under even routing; on the CPU every row of every slot is read
                assert 10 < line["metrics"]["expert_pairs_held_share.longctx"]["value"] < 45
                assert line["metrics"]["latent_rows_read_over_live.mla"]["value"] > 1.0
                assert line["metrics"]["window_compiles"]["value"] == 0
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)


def test_the_calibration_tool_runs_at_the_tests_widths():
    """`tools/calibrate_pangu_moe.py` is run by hand on the chip when the block's limits need their readings
    again; here only that its reading comes out at tiny widths, in a process of its own."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "calibrate_pangu_moe.py"), "control", "--long",
                          "--drop", "mlp_norm", "--seed", "5", "--tiny"], capture_output=True, text=True, timeout=600, env=env)
    said = out.stdout
    assert out.returncode == 0 and "long all 16 positions sound" in said and "agrees=" in said, said[-2000:] + out.stderr[-2000:]
    assert "probes control (float8 e4m3 operands): rms" in said and "probes fault (no mlp_norm): rms" in said
