"""The `dots3` block's benchmark files on the CPU at tiny widths: the benchmark's plain
reference (`lib/reference_dots3.py`, which imports nothing of the program) against the repo's
(`ray_tpu/models/dots3.py:forward_plain`), the control (one precision below bfloat16) against the
block's own limits, the costs module's arithmetic at the published widths, the new readers on
a recorded trace's events, and `run.py` end to end through `drivers/serve_closed_long.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import blocks, costs_dots3, reference_dots3, scope_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _model(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models import dots3
    from ray_tpu.models.transformer import ModelConfig

    model = _model("tests/configs/tiny-dots3.json")["model"]
    fields = {k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()}
    cfg = ModelConfig(**fields)
    return cfg, model, dots3.init_params(cfg, jax.random.PRNGKey(4))


def fp8(a):
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def test_the_block_has_every_name_the_harness_asks():
    assert blocks.reference({"block": "dots3"}) is reference_dots3
    assert blocks.costs({"block": "dots3"}) is costs_dots3


@pytest.mark.parametrize("q_block, head_group", [(16, 16), (4, 2), (64, 1)], ids=["blocks", "bands-and-head-groups", "one-block"])
def test_the_benchmarks_reference_is_the_repos_plain_reference(tiny, monkeypatch, q_block, head_group):
    """Two forward passes written apart: a share of the experts (4 to 7 of 16), a context past
    the selection (8) and several windows (5), query blocks that cut the sequence (and pad it:
    45 is no multiple of any), heads taken in groups, a sliding layer's keys by the band of
    q_block + 4 that a block can see."""
    from ray_tpu.models import dots3

    cfg, model, params = tiny
    monkeypatch.setattr(reference_dots3, "HEAD_GROUP", head_group)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 96, size=(45,)), jnp.int32)
    want = np.asarray(dots3.forward_plain(params, cfg, tokens))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: reference_dots3.forward(p, model, t, q_block=q_block))(params, tokens))
    np.testing.assert_allclose(got, want, atol=2e-5)
    targets = jnp.roll(tokens, -1)
    assert float(reference_dots3.loss(params, model, tokens, targets)) == pytest.approx(
        float(jnp.mean(reference_dots3.token_losses(params, model, tokens, targets))))


def test_greedy_by_full_passes_walks_the_repos_argmax(tiny):
    from ray_tpu.models import dots3

    cfg, model, params = tiny
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 96, size=(30,)), jnp.int32)
    ids, margins = jax.jit(lambda p, x: reference_dots3.greedy(p, model, x, 5))(params, prompt)
    seq = list(np.asarray(prompt))
    for j in range(5):
        logits = np.asarray(dots3.forward_plain(params, cfg, jnp.asarray(seq, jnp.int32)))[-1]
        assert int(np.argmax(logits)) == int(ids[j])
        top = np.sort(logits)[-2:]
        assert float(margins[j]) == pytest.approx(top[1] - top[0], abs=1e-4)
        seq.append(int(ids[j]))


def test_the_control_moves_the_logits_far_more_than_the_stated_precision(tiny):
    """The contract's control on this block, at a size a test holds: both operands of every
    matrix product but the router's rounded to float8 e4m3. On the chip the limit it has to
    fail is MEAN_DEFICIT_TOL on the scored ids (PERF.md §6, PR 28); here the same rounding is
    read on the logits, beside bfloat16's."""
    _, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 96, size=(45,)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = reference_dots3.forward(params, model, tokens)
        d8 = reference_dots3.forward(params, model, tokens, operand=fp8) - ref
        d16 = reference_dots3.forward(params, model, tokens, operand=bf16) - ref
    rms8, rms16 = float(jnp.sqrt(jnp.mean(d8 ** 2))), float(jnp.sqrt(jnp.mean(d16 ** 2)))
    assert rms8 > 2 * rms16 > 0  # at these widths a flipped choice (4 of 16 experts, 8 of 40 keys) is most of either
    assert reference_dots3.compare_greedy([1, 2], [1.0, reference_dots3.NEAR_TIE_MARGIN + 0.01], [1, 9]) == (False, 1)
    assert reference_dots3.compare_greedy([1, 2], [1.0, reference_dots3.NEAR_TIE_MARGIN - 0.01], [1, 9]) == (True, 1)
    # scored ids: every position counts, also after one that differs; an id may lie this far under and no further
    near, far = reference_dots3.NEAR_TIE_MARGIN - 0.01, reference_dots3.NEAR_TIE_MARGIN + 0.01
    assert reference_dots3.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, near, 0.0]) == (True, 3, [0.2])
    assert reference_dots3.compare_scored([1, 2, 3], [1.0, 0.2, 1.0], [1, 9, 3], [0.0, far, 0.0]) == (False, 3, [0.2])


@pytest.mark.parametrize("padded", [40, 64], ids=["whole", "padded-to-a-programs-length"])
def test_score_is_the_forward_pass_at_the_last_positions(tiny, padded):
    _, model, params = tiny
    seq = jnp.asarray(np.random.default_rng(6).integers(0, 96, size=(40,)), jnp.int32)
    given = jnp.pad(seq, (0, padded - 40))
    ids, margins, own = jax.jit(lambda p, s, n: reference_dots3.score(p, model, s, 5, length=n, q_block=16))(
        params, given, jnp.int32(40))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(reference_dots3.forward(params, model, seq))[34:39]
    np.testing.assert_array_equal(np.asarray(ids), logits.argmax(-1))
    top = np.sort(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(margins), top[:, -1] - top[:, -2], atol=1e-5)
    np.testing.assert_allclose(np.asarray(own), logits[np.arange(5), np.asarray(seq)[35:]] - top[:, -1], atol=1e-5)


def test_the_costs_are_the_issues_arithmetic_at_the_published_widths():
    cfg = _model("configs/dots3-note-prev.json")["model"]
    assert round(costs_dots3.attn_params(cfg, True) / 1e6, 1) == 144.0
    assert round(costs_dots3.attn_params(cfg, False) / 1e6, 1) == 90.8
    assert round(costs_dots3.expert_params(cfg) / 1e6, 1) == 23.6
    assert round(costs_dots3.total_params(cfg) / 1e9, 3) == 4.087
    assert costs_dots3.kv_bytes_per_token(cfg) == 2 * (512 + 64 + 128) * 2
    # a decode step of 16 slots at 12k rows each reads far less than the 8.17 GB held:
    # the experts hit (12.7 of 32 a layer), 2048 selected rows a slot, every indexer key
    step = costs_dots3.decode_step_bytes(cfg, 16 * 12288)
    assert 12.5 < costs_dots3.experts_hit(cfg, 16) < 13 and 4.0e9 < step < 5.0e9
    assert costs_dots3.decode_step_bytes(cfg, 16 * 12288, tokens=1) < step
    assert costs_dots3.matmul_params(cfg) < costs_dots3.total_params(cfg)
    assert costs_dots3.train_flops_per_token(cfg, 4096) > 6 * costs_dots3.matmul_params(cfg)


def test_the_configuration_file_holds_the_catalogs_keys_and_the_cuts():
    whole, bench = _model("configs/dots3-note-prev.json"), json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-prev")
    assert sorted(entry["reduced"]) == sorted(whole["reduced"]) and entry["source"] == whole["source"]
    m = whole["model"]
    same = {"hidden_size": "hidden", "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
            "num_attention_heads": "n_heads", "num_experts_per_tok": "experts_per_token", "rope_theta": "rope_theta",
            "sliding_window_size": "sliding_window", "swa_num_attention_heads": "swa_n_heads",
            "num_hidden_layers": "n_layers", "n_routed_experts": "n_routed_experts", "vocab_size": "vocab_size",
            "max_position_embeddings": "max_seq", "rms_norm_eps": "norm_eps", "layer_types": "layer_types",
            "first_k_dense_replace": "first_k_dense", "n_shared_experts": "n_shared_experts"}
    same.update({k: k for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                                "index_n_heads", "index_head_dim", "index_topk", "swa_q_lora_rank", "swa_kv_lora_rank",
                                "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
                                "routed_scaling_factor")})
    for published, field in same.items():
        assert whole[published] == m[field], (published, field)
    for key, cut in whole["reduced"].items():
        assert cut["to"] == whole[key] or key == "layer_types"
        assert {"from", "to", "why"} <= set(cut)
    assert m["n_routed_experts_total"] == whole["published_counts"]["n_routed_experts"] == 256


def _events():
    """A traced window of one decode execution of 2 steps and one prefill chunk, by hand."""
    ops = [["while.1", "jit(rt_decode_multi_n2)/while", 100, 800],
           ["fusion.1", "jit(rt_decode_multi_n2)/while/body/layer_1/attn/indexer/dot", 120, 100],
           ["fusion.2", "jit(rt_decode_multi_n2)/while/body/layer_1/mlp/experts/while/body/dot", 300, 200],
           ["fusion.3", "jit(rt_decode_multi_n2)/while/body/layer_1/mlp/router/dot", 520, 50],
           ["fusion.4", "jit(rt_prefill_b1024)/layer_1/attn/indexer/dot", 1100, 300],
           ["fusion.5", "jit(rt_prefill_b1024)/layer_1/attn/latent/dot", 1400, 500]]
    return {"window": [0, 2000], "spans": [["rt.engine.prefill", 1000, 50, {"tokens": 500}, "stepper"]],
            "modules": [["jit_rt_decode_multi_n2", 100, 800], ["jit_rt_prefill_b1024", 1100, 800]],
            "ops": ops, "hlo": {}, "collectives": {}}


def test_the_scope_readers_on_a_recorded_window(monkeypatch):
    import run as R
    from lib import program_trace as pt

    events = _events()
    monkeypatch.setattr(pt, "for_record", lambda record: events if "trace" in record else None)
    monkeypatch.setattr(pt, "stepper_spans", lambda ev: ev["spans"])
    readers = R.load_metric_readers()
    record = {"trace": {}, "counters": {"expert_pairs_routed": 800, "expert_pairs_held": 100}}
    assert scope_trace.by_program_and_scope(events)[("jit_rt_decode_multi_n2", None)] == 800 - 350
    assert readers["indexer_dev_ms_per_step.longctx"].read(record) == pytest.approx(100 / 1e6 / 2)
    assert readers["experts_dev_ms_per_step.longctx"].read(record) == pytest.approx(200 / 1e6 / 2)
    assert readers["indexer_prefill_share.longctx"].read(record) == pytest.approx(100 * 300 / 800)
    assert readers["prefill_dev_ms_per_ktok.longctx"].read(record) == pytest.approx(800 / 1e6 / 0.5)
    assert readers["expert_pairs_held_share.longctx"].read(record) == 12.5
    # a program without the block's scopes and counts, as the parent commit is: nothing, and no error
    bare = {"window": [0, 2000], "spans": [], "modules": [["jit_rt_decode", 100, 800]],
            "ops": [["fusion.1", "jit(rt_decode)/layer_1/attn/dot", 120, 100]], "hlo": {}, "collectives": {}}
    monkeypatch.setattr(pt, "for_record", lambda record: bare)
    for name in ("indexer_dev_ms_per_step.longctx", "experts_dev_ms_per_step.longctx",
                 "indexer_prefill_share.longctx", "prefill_dev_ms_per_ktok.longctx", "expert_pairs_held_share.longctx"):
        assert readers[name].read({"trace": {}, "counters": {}}) is None
        assert readers[name].read({"counters": {}}) is None or name.startswith("expert_pairs")


def test_the_cell_end_to_end_through_the_long_context_driver(monkeypatch, tmp_path, capsys):
    import run as R
    from lib import trace_reduce
    from ray_tpu._private.config import CONFIG

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(R, "BENCH_FILE", os.path.join(HERE, "BENCHMARK.tiny-dots3.json"))
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
                                 "tpot_ms_p50.serve", "decode_hbm_util.serve", "expert_pairs_held_share.longctx"})):
            assert R.main(["--workload", "tiny-dots3.longctx", "--seed", "3000000007", "--seconds", "3",
                           "--trace", str(trace)]) == 0
            out = capsys.readouterr().out.strip().splitlines()
            line = json.loads(out[-1])
            assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, out
            assert set(line["metrics"]) == want
            assert any("experts in the window" in l for l in out)
            # both comparisons: the probes sent before the window, and a sample of what the window finished
            assert any("probes of 36 + 6 tokens" in l and "enough=True" in l for l in out)
            assert any("requests the window finished" in l and "enough=True" in l for l in out)
            if trace:  # 4 of 16 experts held: a quarter of the pairs under even routing
                assert 10 < line["metrics"]["expert_pairs_held_share.longctx"]["value"] < 45
                assert line["metrics"]["window_compiles"]["value"] == 0
    finally:
        CONFIG._cache.pop("llm_sched_token_budget", None)
        os.environ.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)


@pytest.mark.parametrize("args, says", [(["control", "--long"], "control mean deficit"), (["noise"], "logits equal bit for bit True")])
def test_the_calibration_tool_runs_at_the_tests_widths(args, says):
    """`tools/calibrate_dots3.py` is run by hand on the chip when the block's limits need their readings
    again; here only that both of its readings come out at tiny widths, in a process of their own."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_LLM_SCHED_TOKEN_BUDGET", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "calibrate_dots3.py"), *args, "--seed", "5", "--tiny"],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0 and says in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]


def test_the_replay_says_why_the_seed_may_not_rotate_this_cells_cycle():
    """`tools/replay_longctx.py` over the committed traffic file: where in the cycle a run starts moves both
    end-to-end metrics by more than half their bounds, and the file's own `phase` under noise does not."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import replay_longctx as R

    with open(R.TRAFFIC) as f:
        tr = json.load(f)
    plens, outs = R.cycle(tr, tr["order_seed"])
    runs, s_tok, s_tpot = R.over_starts(tr, plens, outs, tr["ramp_seconds"], 51.0)
    assert len(runs) == tr["pool"] and s_tok > 0.02 and s_tpot > 0.05
    assert all(15 <= n <= 30 for _, _, n in runs)  # a window holds well under one cycle
    s_tok, s_tpot = R.under_noise(tr, plens, outs, tr["ramp_seconds"], 51.0, 3.0)
    assert s_tok < 0.02 and s_tpot < 0.05
