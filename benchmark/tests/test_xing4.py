"""The `xing4` block's benchmark files on the CPU at tiny widths: the benchmark's plain reference
(`lib/reference_xing4.py`, which imports nothing of the program) against the program's cached paths
whatever its loops' sizes, its two controls (operands one precision below bfloat16; 1 Sinkhorn step for
20), the costs module against ISSUE 42's arithmetic and the program's own tree, the configuration file
against the catalog's row, the new readers on a recorded trace's events, and `run.py` end to end through
`drivers/serve_closed_mhc.py`, whose check of the mechanism has to fail a server built with 1 Sinkhorn step."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import blocks, costs_xing4, reference_xing4

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing4.0-29b-a4b.serve-sessions-mhc48"
NEW = ("hc_dev_ms_per_step.mhc", "hc_prefill_share.mhc", "hc_ops_per_step.mhc", "hc_stream_need_share.mhc")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _model(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


def _config(model):
    from ray_tpu.models.transformer import ModelConfig

    return ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models import xing4

    model = _model("tests/configs/tiny-xing4.json")["model"]
    cfg = _config(model)
    return cfg, model, xing4.init_params(cfg, jax.random.PRNGKey(4))


def fp8(a):
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _forward(params, model, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, t: reference_xing4.forward(p, model, t, **kw))(params, tokens))


def test_the_block_has_every_name_the_harness_asks():
    assert blocks.reference({"block": "xing4"}) is reference_xing4
    assert blocks.costs({"block": "xing4"}) is costs_xing4
    assert callable(reference_xing4.score) and callable(reference_xing4.compare_scored)
    assert reference_xing4.MEAN_DEFICIT_TOL > 0 and 0 < reference_xing4.FAR_SHARE_TOL < 1
    assert 0 < reference_xing4.MECHANISM_DEFICIT_TOL < reference_xing4.MEAN_DEFICIT_TOL / 10  # float32 against float32
    for name in ("latent_step_need_s", "latent_attn_call_need_s", "experts_step_bytes", "experts_hit", "hc_sublayer_bytes"):
        assert callable(getattr(costs_xing4, name)), name  # what the readers that are there look for with `hasattr`


@pytest.mark.parametrize("q_block, head_group, columns", [(16, 8, 2048), (4, 2, 8), (64, 1, 12)],
                         ids=["blocks", "head-groups-and-columns", "one-block"])
def test_the_reference_is_the_programs_cached_paths_whatever_its_loops_sizes(tiny, monkeypatch, q_block, head_group, columns):
    """Two forward passes written apart: the program's chunked prefill and cached decode steps, and the
    reference with query blocks that cut the sequence (and pad it: 45 is no multiple of any), the row-wise
    parts by blocks of rows, heads taken in groups, a gated product's inner width by blocks of columns."""
    from ray_tpu.models import xing4

    cfg, model, params = tiny
    monkeypatch.setattr(reference_xing4, "HEAD_GROUP", head_group)
    monkeypatch.setattr(reference_xing4, "COLUMNS", columns)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 96, size=(45,)), jnp.int32)
    got = _forward(params, model, tokens, q_block=q_block)
    caches = xing4.init_caches(cfg, 2, 128)
    prefill, decode = jax.jit(xing4.prefill, static_argnums=1), jax.jit(xing4.decode, static_argnums=1)
    pad = jnp.zeros((1, 32), jnp.int32).at[0, :32].set(tokens[:32])
    _, caches, _ = prefill(params, cfg, pad, caches, jnp.int32(1), jnp.int32(0), jnp.int32(40))
    pad = jnp.zeros((1, 8), jnp.int32).at[0, :8].set(tokens[32:40])
    last, caches, _ = prefill(params, cfg, pad, caches, jnp.int32(1), jnp.int32(32), jnp.int32(40))
    want = [np.asarray(last)]
    for at in range(40, 45):
        logits, caches, _ = decode(params, cfg, jnp.asarray([0, tokens[at]]), caches, jnp.asarray([0, at]), jnp.asarray([False, True]))
        want.append(np.asarray(logits)[1])
    np.testing.assert_allclose(got[39:], np.stack(want), atol=3e-5)
    targets = jnp.roll(tokens, -1)
    assert float(reference_xing4.loss(params, model, tokens, targets)) == pytest.approx(
        float(jnp.mean(reference_xing4.token_losses(params, model, tokens, targets))))


def test_greedy_by_full_passes_walks_the_references_argmax(tiny):
    cfg, model, params = tiny
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 96, size=(11,)), jnp.int32)
    ids, margins = jax.jit(lambda p, t: reference_xing4.greedy(p, model, t, 4))(params, prompt)
    seq = list(np.asarray(prompt))
    for j in range(4):
        logits = _forward(params, model, jnp.asarray(seq, jnp.int32))[-1]
        assert int(ids[j]) == int(np.argmax(logits)) and float(margins[j]) == pytest.approx(float(np.sort(logits)[-1] - np.sort(logits)[-2]), abs=1e-4)
        seq.append(int(ids[j]))
    assert reference_xing4.compare_greedy(np.asarray(ids), np.asarray(margins), np.asarray(ids)) == (True, int((np.asarray(margins) >= reference_xing4.NEAR_TIE_MARGIN).sum()))


def test_the_two_controls_move_the_logits_far_more_than_the_stated_precision(tiny):
    """Operands rounded to bfloat16 (the precision the configuration states) move a logit by some 0.05 rms at
    these widths (a token's coefficients pass through two sigmoids and an exponential: more than pangu's
    0.01); rounded to float8 e4m3, one precision below, by eight times that; 1 Sinkhorn step for 20, in
    float32, by nearly three times: a mixing matrix that is not yet doubly stochastic is another function."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 96, size=(48,)), jnp.int32)
    exact = _forward(params, model, tokens, q_block=16)
    rms = lambda a: float(np.sqrt(np.mean((a - exact) ** 2)))  # noqa: E731
    stated, below = rms(_forward(params, model, tokens, q_block=16, operand=bf16)), rms(_forward(params, model, tokens, q_block=16, operand=fp8))
    one_step = rms(_forward(params, model, tokens, q_block=16, sinkhorn_iters=1))
    assert 0.001 < stated < 0.08 and below > 5 * stated and one_step > 2 * stated, (stated, below, one_step)
    twenty = rms(_forward(params, model, tokens, q_block=16, sinkhorn_iters=20))
    assert twenty == 0.0 and rms(_forward(params, model, tokens, q_block=16, sinkhorn_iters=19)) < 0.1 * one_step


@pytest.mark.parametrize("padded", [False, True])
def test_score_is_the_forward_pass_at_the_last_positions(tiny, padded):
    cfg, model, params = tiny
    seq = jnp.asarray(np.random.default_rng(5).integers(0, 96, size=(41,)), jnp.int32)
    logits = _forward(params, model, seq, q_block=16)[41 - 8 - 1:40]
    given = jnp.pad(seq, (0, 23)) if padded else seq
    with jax.default_matmul_precision("highest"):
        ids, margins, own = jax.jit(lambda p, s, n: reference_xing4.score(p, model, s, 8, length=n, q_block=16))(params, given, jnp.int32(41))
    np.testing.assert_array_equal(np.asarray(ids), logits.argmax(-1))
    top = np.sort(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(margins), top[:, -1] - top[:, -2], atol=1e-4)
    np.testing.assert_allclose(np.asarray(own), logits[np.arange(8), np.asarray(seq[-8:])] - top[:, -1], atol=1e-4)
    agrees, compared, parted = reference_xing4.compare_scored(np.asarray(ids), np.asarray(margins), np.asarray(ids), [0.0] * 8)
    assert agrees and compared == 8 and parted == []
    far = reference_xing4.NEAR_TIE_MARGIN + 0.1  # at most half of a sequence's positions may lie that far under
    assert reference_xing4.compare_scored([1] * 8, [0.1] * 8, [2] * 8, [far] * 4 + [0.0] * 4) == (True, 8, [0.1] * 8)
    assert reference_xing4.compare_scored([1] * 8, [0.1] * 8, [2] * 8, [far] * 5 + [0.0] * 3)[0] is False


def test_the_costs_are_the_issues_arithmetic_and_the_programs_own_tree():
    from ray_tpu.models import xing4

    cfg = _model("configs/xing4.0-29b-a4b.json")["model"]
    C, D = costs_xing4, cfg["hidden"]
    # ISSUE 42: attention 28.41M a layer, a hyper-connection 0.344M a sub-layer (0.69M a layer), a dense layer 128.2M,
    # an expert 11.01M, 64 of them 704.6M, the router 0.23M, an expert layer 745.0M with 40.3M outside the routed experts
    assert round(C.attn_params(cfg) / 1e6, 2) == 28.41 and C.hc_params(cfg) == 14336 * 24 + 24 + 3 and round(2 * C.hc_params(cfg) / 1e6, 2) == 0.69
    assert round(3 * D * cfg["mlp_dim"] / 1e6, 2) == 99.09 and round(C.dense_layer_params(cfg) / 1e6, 1) == 128.2
    assert round(C.expert_params(cfg) / 1e6, 2) == 11.01 and round(64 * C.expert_params(cfg) / 1e6, 1) == 704.6
    assert round(C.expert_layer_params(cfg) / 1e6, 1) == 745.0 and round(C.expert_layer_fixed_params(cfg) / 1e6, 1) == 40.3
    assert round(2 * cfg["vocab_size"] * D / 1e6, 1) == 939.5
    # the cut: 128.2 + 5 x 745.0 + 939.5 = 4.793B parameters, 9.59 GB in bfloat16; the whole model 29.5B, 59 GB
    assert C.total_params(cfg) == C.dense_layer_params(cfg) + 5 * C.expert_layer_params(cfg) + 2 * cfg["vocab_size"] * D + D
    assert round(C.total_params(cfg) / 1e9, 3) == 4.793 and round(2 * C.total_params(cfg) / 1e9, 2) == 9.59
    whole = dict(cfg, n_layers=40, first_k_dense=2)
    assert round(C.total_params(whole) / 1e9, 1) == 29.5 and round(2 * C.total_params(whole) / 1e9) == 59
    assert C.total_params(cfg) == xing4.num_params(_config(cfg))  # the program's own tree, leaf by leaf
    # the cache: 7680 bytes a token as the slabs hold it (640 lanes x 2 bytes x 6 layers), 48 slots of 8192 rows 3.02 GB,
    # weights and cache 12.6 GB; of a row the mathematics reads 1152 bytes and spends 69,632 operations, 60 a byte
    assert C.cache_bytes_per_token(cfg) == 7680 and round(48 * 8192 * C.cache_bytes_per_token(cfg) / 1e9, 2) == 3.02
    held = jax.eval_shape(lambda: xing4.init_caches(_config(cfg), 48, 8192))
    assert sum(a.size * 2 for (a,) in held) == 48 * 8192 * C.cache_bytes_per_token(cfg)
    assert round((2 * C.total_params(cfg) + 48 * 8192 * 7680) / 1e9, 1) == 12.6
    assert C.latent_row_bytes(cfg) == 1152 and C.kv_bytes_per_token(cfg) == 6912 and C.latent_row_flops(cfg) == 69632
    assert round(C.latent_row_flops(cfg) / C.latent_row_bytes(cfg)) == 60 < 241
    rows = 48 * 2600
    assert C.latent_step_need_s(cfg, rows, PEAKS) == 6 * rows * 1152 / 819e9 > 6 * rows * 69632 / 197e12  # bound by bytes
    assert C.latent_attn_call_need_s(cfg, rows, PEAKS) == pytest.approx(rows * 1152 / 819e9)
    # a decode step of 48 slots: 192 pairs hit 61 of a layer's 64 experts; 8.3 GB of weights and about 1 GB of rows
    assert round(C.experts_hit(cfg, 48)) == 61 and round(C.decode_step_bytes(cfg, 0) / 1e9, 1) == 8.3
    assert C.decode_step_bytes(cfg, rows) - C.decode_step_bytes(cfg, 0) == pytest.approx(rows * 6912) and 0.8e9 < rows * 6912 < 1.0e9
    assert round(C.experts_step_bytes(cfg, 64) / 1e9, 2) == 7.05  # what every chunk reads: all of the five layers' experts
    assert C.experts_step_bytes(cfg, C.experts_hit(cfg, 48)) < C.experts_step_bytes(cfg, 64)
    assert C.matmul_params(cfg) < C.total_params(cfg) and C.train_flops_per_token(cfg, 4096) > 6 * C.matmul_params(cfg)
    # the hyper-connection: a 1024-token chunk's streams are 29 MB, read once and written once a sub-layer
    assert round(1024 * 4 * D * 2 / 1e6) == 29 and 1024 * 4 * D * 2 * 2 < C.hc_sublayer_bytes(cfg, 1024) < 1024 * 4 * D * 2 * 2.6
    assert C.hc_sublayer_bytes(cfg, 1024) == (1024 * 10 * D + 4 * D * 24) * 2  # streams in and out, the mixture, the output, Phi


def test_the_configuration_file_holds_the_catalogs_keys_and_the_cuts():
    whole, bench = _model("configs/xing4.0-29b-a4b.json"), json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert sorted(entry["reduced"]) == sorted(whole["reduced"]) and entry["source"] == whole["source"]
    assert sorted(whole["reduced"]) == sorted(["num_hidden_layers", "first_k_dense_replace", "max_position_embeddings", "num_nextn_predict_layers"])
    catalog = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
               "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
               "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
               "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
               "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
               "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
               "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                "original_max_position_embeddings": 4096, "type": "yarn"},
               "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
               "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
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
            "first_k_dense_replace": "first_k_dense", "n_shared_experts": "n_shared_experts", "tie_word_embeddings": "tie_embeddings",
            "mhc_h_res_clamp_min": "hc_res_clamp_min", "mhc_h_res_clamp_max": "hc_res_clamp_max"}
    same.update({k: k for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "routed_scaling_factor",
                                "hc_mult", "hc_sinkhorn_iters", "hc_eps", "rope_scaling")})
    for published, field in same.items():
        assert whole[published] == m[field], (published, field)
    assert m["n_routed_experts_total"] == 64 == m["n_routed_experts"] and m["first_expert"] == 0 and m["mla_rescale"] is False
    assert m["block"] == whole["block"] == "xing4" and "7" in whole["stands_for"] and "No width is cut" in whole["stands_for"]
    assert {"collapse", "hyper_connection", "hyper_connection_layout", "rotary", "routing", "norms", "weights", "cache", "mtp"} <= set(whole["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("xing4.0-29b-a4b", "sessions-mhc48", 1)
    tr = _model("traffic/sessions-mhc48.json")  # ISSUE 42's parameters, to the letter
    assert (tr["driver"], tr["slots"], tr["clients"], tr["max_seq"], tr["pool"], tr["order_seed"], tr["phase"]) == ("serve_closed_mhc", 48, 48, 8192, 96, 42, 0)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048, "sigma": 0.6, "lo": 512, "hi": 6144}
    assert tr["max_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5, "lo": 128, "hi": 1024}
    assert (tr["temperature"], tr["top_k"], tr["ramp_seconds"], tr["trace_seconds"]) == (0.0, 0, 20, 3)
    assert tr["flags"]["llm_sched_token_budget"] == 2048 and tr["warmup"]["prompt_lens"] == [16, 32, 64, 128, 256, 512, 1024, 2048]
    assert tr["counts"] == {"latent": ["rows_visible", "rows_read"], "hc": ["token_sublayers"]} and tr["probe"]["prompt_len"] > 2048
    assert max(tr["window_check"]["lens"]) >= tr["prompt_len"]["hi"] + tr["max_tokens"]["hi"] and tr["window_check"]["n_last"] == 128
    for name in NEW:
        entry = next(mm for mm in bench["per_layer"] if mm["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] == "model block" and entry["source"] == "program_span"
    listed = {mm["name"] for kind in ("end_to_end", "per_layer") for mm in bench[kind] if CELL in mm.get("workloads", ())}
    assert {"serve_out_tok_s", "tpot_ms_p90", "latent_attn_roofline.mla", "latent_roofline.mla", "latent_dev_ms_per_step.mla",
            "latent_prefill_share.mla", "latent_rows_read_over_live.mla", "experts_dev_ms_per_step.longctx", "experts_hit_per_step.decode64",
            "experts_roofline.decode64", "kv_insert_share.serve", "ttft_ms_p90.sessions", "decode_dev_ms_per_step.serve", "compile_s",
            "expert_load_max_over_mean.decode64", "experts_prefill_share.decode64", "prefill_dev_ms_per_ktok.longctx"} <= listed
    # `costs_xing4.decode_step_bytes` takes even routing's 61 experts hit where the seeded bias hits 43: no share over it is listed
    assert not {"decode_roofline.serve", "decode_hbm_util.serve"} & listed
    assert tr["driver"] == "serve_closed_mhc" and tr["mechanism"]["n_layers"] == 3 and tr["mechanism"]["prompt_len"] > 2048
    # entries in the form a file is refused for before any run; held to no place in their lists, so that the next
    # configuration's appends leave this test standing (`test_pangu_moe.py`'s asks for the last place and fell to this PR's)
    config = entry = next(c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert len(config["source"]) <= 200 and {mm["name"] for mm in bench["per_layer"]} >= set(NEW)
    assert len(bench["workloads"]) >= 9 and sum(w["chips"] == 4 for w in bench["workloads"]) <= len(bench["workloads"]) // 4
    for said in (config, cell):
        assert 1 <= len(said["why"]) <= 200 and "\n" not in said["why"] and "\t" not in said["why"], (said["name"], len(said["why"]))


def _events():
    """A traced window of one decode execution of 2 steps (a layer's two sub-layers each) and one prefill chunk, by hand."""
    d, p = "jit(rt_decode_multi_n2)/while/body/layer_1/", "jit(rt_prefill_b1024)/layer_1/"
    ops = [["while.1", "jit(rt_decode_multi_n2)/while", 100, 800]]
    for step in (0, 400):
        ops += [["fusion.1", d + "hc/map/reduce_sum", 110 + step, 10], ["fusion.2", d + "hc/map/dot_general", 120 + step, 20],
                ["hc_map.3", d + "hc/map/jit(hc_map)/hc_map/pallas_call", 140 + step, 30 + step // 40],
                ["fusion.4", d + "attn/latent/dot", 180 + step, 40], ["fusion.5", d + "hc/pre/mul", 220 + step, 10],
                ["fusion.6", d + "hc/post/concatenate", 240 + step, 20], ["fusion.7", d + "mlp/experts/dot", 270 + step, 100]]
    ops += [["fusion.8", p + "hc/map/dot_general", 1100, 100], ["hc_map.9", p + "hc/map/jit(hc_map)/hc_map/pallas_call", 1200, 60],
            ["fusion.10", p + "hc/post/concatenate", 1300, 40], ["fusion.11", p + "mlp/experts/dot", 1400, 600]]
    return {"window": [0, 2100], "spans": [["rt.engine.prefill", 1090, 920, {"tokens": 1000, "bucket": 1024}]], "modules": [["jit_rt_decode_multi_n2", 100, 800], ["jit_rt_prefill_b1024", 1100, 900]],
            "ops": ops, "hlo": {}, "collectives": {}}


def test_the_new_readers_on_a_recorded_window(monkeypatch):
    import run as R
    from lib import program_trace as pt
    from lib import scope_trace_hc as sth

    events = _events()
    monkeypatch.setattr(pt, "for_record", lambda record: events if "trace" in record else None)
    monkeypatch.setattr(pt, "stepper_spans", lambda ev: ev["spans"])
    readers = R.load_metric_readers()
    model = _model("configs/xing4.0-29b-a4b.json")["model"]
    record = {"trace": {}, "chips": 1, "block": "xing4", "model": model, "peaks": PEAKS, "slots": 48, "counters": {}}
    hc_ns = 2 * (10 + 20 + 10 + 20) + 30 + 40
    assert readers["hc_dev_ms_per_step.mhc"].read(record) == pytest.approx(hc_ns / 1e6 / 2)
    assert readers["hc_ops_per_step.mhc"].read(record) == 5.0
    assert readers["hc_prefill_share.mhc"].read(record) == pytest.approx(100 * 200 / 800)
    need = 12 * costs_xing4.hc_sublayer_bytes(model, 1000) / 819e9  # the chunk's real tokens, not its bucket
    assert readers["hc_stream_need_share.mhc"].read(record) == pytest.approx(100 * need / (900 / 1e9))
    assert sth.part_of("jit(rt_decode)/layer_0/hc/map/dot_general") == "map" and sth.part_of("jit(rt_decode)/layer_0/attn/latent/dot") is None
    # the accepted readers of `latent` and `experts` take none of the hyper-connection's time
    assert readers["latent_dev_ms_per_step.mla"].read(record) == pytest.approx(2 * 40 / 1e6 / 2)
    assert readers["experts_dev_ms_per_step.longctx"].read(record) == pytest.approx(2 * 100 / 1e6 / 2)
    # a program without the block's scopes and kernel, as the parent commit is, and another block's costs: nothing, and no error
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_decode", 100, 800]],
            "ops": [["fusion.1", "jit(rt_decode)/layer_1/attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    monkeypatch.setattr(pt, "for_record", lambda record: bare)
    for name in NEW:
        for rec in ({"trace": {}, "chips": 1, "block": "pangu_moe", "model": model, "peaks": PEAKS, "counters": {}, "slots": 16},
                    {"chips": 1, "block": "xing4", "model": model, "peaks": PEAKS, "counters": {}, "slots": 48}):
            assert readers[name].read(rec) is None, name


def test_the_cell_end_to_end_through_its_driver(monkeypatch, tmp_path, capsys):
    import run as R
    from lib import trace_reduce
    from ray_tpu._private.config import CONFIG

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny-xing4.json"))
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
                                 "tpot_ms_p50.serve", "ttft_ms_p90.sessions", "experts_hit_per_step.decode64",
                                 "expert_load_max_over_mean.decode64", "latent_rows_read_over_live.mla"})):
            assert R.main(["--workload", "tiny-xing4.sessions-mhc", "--seed", "3000000007", "--seconds", "3",
                           "--trace", str(trace)]) == 0
            out = capsys.readouterr().out.strip().splitlines()
            line = json.loads(out[-1])
            assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, out
            assert set(line["metrics"]) == want
            assert any("experts in the window" in l and "decode_experts_hit" in l and "decode_layer_steps" in l for l in out)
            assert any("probes of 36 + 6 tokens" in l and "enough=True" in l for l in out)
            assert any("requests the window finished" in l and "enough=True" in l for l in out)
            assert any(l.startswith("[bench] mechanism: 8 probes of 36 + 6 tokens through 3 layers served in float32") and "ids differ at 0;" in l
                       and "agrees=True, enough=True" in l for l in out)
            assert jax.config.jax_default_matmul_precision is None  # the check's "highest" is set back
            if trace:  # a step of up to 4 slots routes 4 to 16 pairs over a layer's 16 experts
                assert 3.5 < line["metrics"]["experts_hit_per_step.decode64"]["value"] <= 16
                assert line["metrics"]["latent_rows_read_over_live.mla"]["value"] > 1.0
                assert line["metrics"]["window_compiles"]["value"] == 0
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)


def test_correct_needs_the_mechanism_and_a_server_with_one_sinkhorn_step_fails_it(monkeypatch):
    """`drivers/serve_closed_mhc.py`: the record's `correct` is the timed run's and the mechanism check's. The check
    itself, on the CPU at the tests' widths (a vocabulary of 96 parts the ids far less often than one of 131072, so the
    limit here is the tests' own): the block as configured reads 0, and the same block served with 1 Sinkhorn step
    for the reference's 20 does not agree."""
    import sys

    from drivers import serve_closed_mhc as mhc
    from ray_tpu._private.config import CONFIG

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import calibrate_xing4

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    monkeypatch.setattr(reference_xing4, "MECHANISM_DEFICIT_TOL", 1e-3)
    try:
        ctx = calibrate_xing4.context(2147484001, tiny=True)
        monkeypatch.setattr(mhc.counts, "run", lambda ctx: {"correct": True, "notes": ["the timed run"]})
        record = mhc.run(ctx)
        assert record["correct"] is True and record["notes"][0] == "the timed run" and "ids differ at 0;" in record["notes"][1], record
        agrees, note, r = mhc.check_mechanism(ctx, served={"hc_sinkhorn_iters": 1})
        assert agrees is False and len(r["parted"]) > 0 and r["mean_deficit"] > 5e-3 and "the server with hc_sinkhorn_iters=1" in note, note
        monkeypatch.setattr(mhc, "check_mechanism", lambda ctx: (False, "mechanism: no", r))
        assert mhc.run(ctx)["correct"] is False
        monkeypatch.setattr(mhc.counts, "run", lambda ctx: {"correct": False, "notes": []})
        monkeypatch.setattr(mhc, "check_mechanism", lambda ctx: (True, "mechanism: yes", r))
        assert mhc.run(ctx)["correct"] is False
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
        os.environ.pop("RAY_TPU_LLM_PREFILL_BUCKET_MIN", None)


def test_the_calibration_tool_runs_at_the_tests_widths():
    """`tools/calibrate_xing4.py` is run by hand on the chip when the block's limits need their readings again;
    here only that its readings come out at tiny widths, in a process of its own."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "calibrate_xing4.py"), "control", "--long",
                          "--tiny", "--seed", "2147484001"], env=env, capture_output=True, text=True, timeout=900, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("[control]")]
    assert any("sound" in l and "agrees=True" in l and "further under than 0.7 / 1.0 / 2.0" in l for l in lines)
    for control in ("float8 e4m3 operands", "1 Sinkhorn step"):  # the readings are shown; the limits are the cell's, not these widths'
        assert any(control + "): rms" in l for l in lines) and any(control in l and "mean deficit" in l for l in lines), control
