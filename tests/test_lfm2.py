"""The `lfm2` block (`ray_tpu/models/lfm2.py`) at tiny widths on the CPU, float32: the engine's
cached paths against the benchmark's plain reference (`benchmark/lib/reference_lfm2.py`, which
imports nothing of the program) and the repo's (`forward_plain`), always on logits; what a
convolution's cached inputs force that rows indexed by position never did (a reset at a prompt's
first chunk, padding that is not shifted into the window, a gated-off slot left bit for bit); the
expert layer over all of a layer's experts against the sum of its shares; the two functions the
block shares with others (`ops/moe.py:sigmoid_routing`, `models/llama.py:_attn_cached`) with the
argument it added and without."""

import dataclasses
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lfm2, llama
from ray_tpu.models.transformer import ModelConfig, Transformer
from ray_tpu.ops.moe import grouped_experts, sigmoid_routing

LAYERS = ("conv", "full_attention", "conv", "conv", "full_attention", "conv")


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "lib", "reference_lfm2.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_lfm2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="lfm2", vocab_size=96, hidden=64, n_layers=6, n_heads=4, n_kv_heads=2, mlp_dim=96, max_seq=64,
        dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False, tie_embeddings=True,
        layer_types=LAYERS, rope_theta=1e6, first_k_dense=1, n_routed_experts_total=8, n_routed_experts=8,
        n_shared_experts=0, experts_per_token=2, moe_mlp_dim=32, conv_L_cache=3)
    base.update(kw)
    return ModelConfig(**base)


def _model_dict(cfg: ModelConfig) -> dict:
    """The configuration as the benchmark's reference reads it: `ModelConfig`'s field names."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, lfm2.init_params(cfg, jax.random.PRNGKey(1))


_PREFILL = jax.jit(lfm2.prefill, static_argnums=1)
_DECODE = jax.jit(lfm2.decode, static_argnums=1)
_PLAIN = jax.jit(lfm2.forward_plain, static_argnums=(1, 3))


def _plain(params, cfg, toks, experts=None):
    return np.asarray(_PLAIN(params, cfg, jnp.asarray(toks, jnp.int32), experts))


def _reference(params, cfg, toks, q_block=8):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, _model_dict(cfg), jnp.asarray(toks, jnp.int32), q_block=q_block))


def _tokens(n, vocab=96, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,)).astype(np.int32)


def _prefill(cfg, params, toks, chunks, caches, slot):
    """`toks` into `slot` in chunks of (tokens, bucket); the last chunk's logits. Only a prompt's
    last chunk is shorter than its bucket (`scheduler.next_plan` grants whole buckets before it)."""
    off, last = 0, None
    for n, bucket in chunks:
        pad = np.full((1, bucket), 7, np.int32)  # a real id: padding must not reach the window, not be a zero in it
        pad[0, :n] = toks[off:off + n]
        last, caches, _ = _PREFILL(params, cfg, jnp.asarray(pad), caches, jnp.int32(slot),
                                   jnp.int32(off), jnp.int32(len(toks)))
        off += n
    assert off == len(toks)
    return np.asarray(last), caches


def _decode(cfg, params, tok, caches, slot, at, slots=3):
    lens, gate, last = np.zeros((slots,), np.int32), np.zeros((slots,), bool), np.zeros((slots,), np.int32)
    lens[slot], gate[slot], last[slot] = at, True, tok
    logits, caches, _ = _DECODE(params, cfg, jnp.asarray(last), caches, jnp.asarray(lens), jnp.asarray(gate))
    return np.asarray(logits)[slot], caches


def _dirty(caches, seed=3):
    """Caches as a longer request left them: nothing in them is zero."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(caches))
    return [tuple(jax.random.normal(jax.random.fold_in(k, j), a.shape, a.dtype) for j, a in enumerate(c))
            for k, c in zip(keys, caches)]


# -- the cached paths against the two plain references -----------------------------------


def test_the_repos_plain_reference_is_the_benchmarks(model):
    cfg, params = model
    toks = _tokens(41)
    np.testing.assert_allclose(_plain(params, cfg, toks), _reference(params, cfg, toks), atol=2e-6)


@pytest.mark.parametrize("chunks", [
    ((29, 32),),                                  # the whole prompt, padded to its bucket
    ((16, 16), (8, 8), (5, 8)),                   # three chunks, the last padded
    ((1, 1), (4, 4), (24, 32)),                   # shorter than the convolution's window, then spanning
    ((1, 1), (1, 1), (1, 1), (26, 32)),           # the window carried over chunks of one token
], ids=["whole", "three-chunks", "short-then-spanning", "single-tokens"])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_benchmarks_reference(model, chunks):
    """Into a slot whose convolution inputs and rows a longer request left behind: the first
    chunk's reset, the padding left out of the window, and the window carried between programs."""
    cfg, params = model
    P, new = 29, 12
    toks = _tokens(P + new)
    ref = _reference(params, cfg, toks)
    last, caches = _prefill(cfg, params, toks[:P], chunks, _dirty(lfm2.init_caches(cfg, 3, 64)), slot=1)
    np.testing.assert_allclose(last, ref[P - 1], atol=3e-6)
    for j in range(new):
        logits, caches = _decode(cfg, params, toks[P + j], caches, 1, P + j)
        np.testing.assert_allclose(logits, ref[P + j], atol=3e-6)


def test_the_window_after_a_padded_chunk_holds_the_prompts_last_inputs_and_no_padding(model):
    """What unpadded chunks leave (to float32 rounding: the chunks differ): the padding's gated inputs are computed and dropped."""
    cfg, params = model
    toks = _tokens(13, seed=5)
    _, padded = _prefill(cfg, params, toks, ((13, 16),), _dirty(lfm2.init_caches(cfg, 2, 64)), slot=0)
    _, exact = _prefill(cfg, params, toks, ((8, 8), (4, 4), (1, 1)), lfm2.init_caches(cfg, 2, 64), slot=0)
    for i, kind in enumerate(LAYERS):
        if kind == "conv":
            np.testing.assert_allclose(np.asarray(padded[i][0])[0], np.asarray(exact[i][0])[0], atol=1e-5)
            assert np.abs(np.asarray(padded[i][0])[0]).max() > 0


def test_the_logits_are_not_all_but_an_argmax_at_the_input_token(model):
    """The head is the embedding again: drawn too wide, the input token's own row wins every
    position and greedy decoding repeats its input, whatever the layers compute."""
    cfg, params = model
    toks = _tokens(40, seed=4)
    assert np.mean(np.argmax(_plain(params, cfg, toks), axis=-1) == toks) < 0.2


@pytest.mark.parametrize("program", ["decode", "multi-step"])
def test_a_gated_off_slot_keeps_its_state_and_rows_bit_for_bit(model, engine, program):
    """A slot in the middle of a chunked prefill is stepped over by every interleaved decode step."""
    cfg, params = model
    caches = _dirty(lfm2.init_caches(cfg, 3, 64))
    before = [tuple(np.asarray(a) for a in c) for c in caches]
    last, lens = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 4, 30], jnp.int32)
    gate = jnp.asarray([True, False, True])
    n = len(lfm2.EXPERT_COUNTS)
    if program == "decode":
        _, after, (experts, state) = _DECODE(params, cfg, last, caches, lens, gate)
        steps = 1
    else:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=4))
        _, after, _, _, experts, state = multi(params, None, jnp.zeros((3,), jnp.int32), last, caches, lens, gate,
                                                  jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
        steps = 4
    assert state.tolist() == [0, 0, 0, 2 * steps]
    # two slots routed to 2 experts in each of 5 expert layers a step; the gated-off slot is routed nowhere
    named = dict(zip(lfm2.EXPERT_COUNTS, experts[:n].tolist()))
    assert named["pairs_routed"] == named["pairs_held"] == 2 * 2 * 5 * steps == int(experts[n:].sum())
    assert named["layer_steps"] == named["decode_layer_steps"] == 5 * steps
    assert 2 * 5 * steps <= named["experts_hit"] == named["decode_experts_hit"] == named["tiles_run"] <= 4 * 5 * steps
    for (b, a) in zip(before, after):
        for x, y in zip(b, a):
            np.testing.assert_array_equal(x[1], np.asarray(y)[1])
            assert not np.array_equal(x[0], np.asarray(y)[0])


def test_load_model_builds_the_blocks_tree():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-lfm2", model_config=cfg, seed=3))
    assert got_cfg.block == "lfm2" and "lm_head" not in params
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) == lfm2.num_params(cfg)
    conv, attn = params["layer_0"]["attn"], params["layer_1"]["attn"]
    assert set(conv) == {"in_proj", "conv", "out_proj"} and conv["in_proj"]["kernel"].shape == (64, 192)
    assert conv["conv"]["kernel"].shape == (3, 64) and conv["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert set(attn) == {"q", "k", "v", "o", "q_norm", "k_norm"} and attn["q_norm"]["scale"].shape == (16,)
    assert set(params["layer_0"]["mlp"]) == {"gate", "up", "down"} and set(params["layer_1"]["mlp"]) == {"router", "experts"}
    m = params["layer_1"]["mlp"]
    assert m["experts"]["gate"].shape == m["experts"]["up"].shape == (8, 64, 32) and m["experts"]["down"].shape == (8, 32, 64)
    assert m["router"]["kernel"].shape == (64, 8) and not np.asarray(m["router"]["bias"]).any()
    with pytest.raises(ValueError, match="holds every expert"):
        lfm2.param_shapes(tiny(n_routed_experts=4))


# -- the expert layer: all of a layer's experts, and its shares ------------------------------


@pytest.mark.parametrize("shares", [2, 4], ids=["two-halves", "four-quarters"])
def test_the_shares_of_the_expert_layer_add_up_to_the_layer_and_to_the_references_dense_sum(model, shares):
    """The guide's share test on a block that holds every expert: what `grouped_experts` gives over
    all 8 is the sum of what it gives over each share of them (`first`, as `dots3` runs it), and
    both are the benchmark reference's sum of every expert over every token. No part is computed
    by every share alike (there is no shared expert), so nothing is counted once."""
    cfg, params = model
    p = params["layer_2"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(7), (21, cfg.hidden))
    valid = jnp.arange(21) != 5
    ids, weights = sigmoid_routing(x, p["router"]["kernel"], p["router"]["bias"], cfg.experts_per_token,
                                   cfg.routed_scaling_factor, eps=lfm2.ROUTING_EPS)
    whole, counts = grouped_experts(x, ids, weights, valid, p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"])
    per, parts, held = 8 // shares, jnp.zeros_like(x), 0
    for first in range(0, 8, per):
        w = [p["experts"][k][first:first + per] for k in ("gate", "up", "down")]
        y, c = grouped_experts(x, ids, weights, valid, *w, first=first)
        parts, held = parts + y, held + int(c.sum())
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts)[first:first + per])
    assert held == int(counts.sum()) == 20 * cfg.experts_per_token
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=1e-5)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(reference.experts(p, x, _model_dict(cfg)))
    np.testing.assert_allclose(np.asarray(whole)[np.asarray(valid)], dense[np.asarray(valid)], atol=1e-5)
    assert not np.asarray(whole)[5].any()
    # and the model: the plain reference told to sum one share is the whole less the other
    toks = _tokens(17, seed=9)
    a, b = _plain(params, cfg, toks, experts=(0, 4)), _plain(params, cfg, toks, experts=(0, 8))
    assert np.abs(a - b).max() > 1e-3


def test_sigmoid_routing_with_and_without_the_sum_s_epsilon():
    """`eps` under the chosen scores' sum is this block's (the published code's `+ 1e-6`); absent,
    the weights are what `dots3` computes: the scores over their plain sum."""
    h = jax.random.normal(jax.random.PRNGKey(0), (9, 16))
    kernel, bias = jax.random.normal(jax.random.PRNGKey(1), (16, 8)), jnp.linspace(-0.2, 0.2, 8)
    scores = np.asarray(jax.nn.sigmoid(h @ kernel))
    ids, w = sigmoid_routing(h, kernel, bias, 3, 2.5)
    want_ids = np.argsort(-(scores + np.asarray(bias)), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    chosen = np.take_along_axis(scores, want_ids, axis=-1)
    np.testing.assert_allclose(np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    ids_e, w_e = sigmoid_routing(h, kernel, bias, 3, 2.5, eps=0.5)
    np.testing.assert_array_equal(np.asarray(ids_e), want_ids)
    np.testing.assert_allclose(np.asarray(w_e), chosen / (chosen.sum(-1, keepdims=True) + 0.5) * 2.5, rtol=1e-6)
    # the default adds nothing to the program: the two lower to the same text
    f = lambda eps: jax.jit(lambda a: sigmoid_routing(a, kernel, bias, 3, 2.5, **eps)).lower(h).as_text()  # noqa: E731
    assert f({}) == f({"eps": 0.0}) != f({"eps": 1e-6})


def test_attn_cached_with_norm_scales_is_the_references_layer_and_without_them_the_program_it_was(model):
    cfg, params = model
    p = dict(params["layer_1"]["attn"])
    p["q_norm"] = {"scale": jnp.linspace(0.5, 1.5, 16)}
    p["k_norm"] = {"scale": jnp.linspace(1.2, 0.7, 16)}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.hidden))
    k0 = jnp.zeros((1, 16, cfg.n_kv_heads, cfg.head_dim))
    args = (p, x, jnp.arange(16)[None], k0, k0, jnp.zeros((1,), jnp.int32), cfg)  # a slot of 0 rows: causal
    out, _, _ = llama._attn_cached(*args, qk_norm=(p["q_norm"]["scale"], p["k_norm"]["scale"]))
    with jax.default_matmul_precision("highest"):
        want = reference._attention(p, x[0], _model_dict(cfg), 16, lambda a: a)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), atol=2e-6)
    plain, _, _ = llama._attn_cached(*args)
    assert np.abs(np.asarray(plain) - np.asarray(out)).max() > 1e-3
    text = lambda **kw: jax.jit(lambda a: llama._attn_cached(p, a, *args[2:], **kw)).lower(x).as_text()  # noqa: E731
    assert text() == text(qk_norm=None) != text(qk_norm=(p["q_norm"]["scale"], p["k_norm"]["scale"]))


# -- through the engine ---------------------------------------------------------------


def _generate(engine, prompt, **sp):
    from ray_tpu.llm import SamplingParams

    out, done = [], threading.Event()

    def cb(tok, fin):
        out.append(tok)
        if fin:
            done.set()

    engine.submit(prompt, SamplingParams(**sp), cb)
    assert done.wait(300), engine.error
    return out


def _greedy_plain(cfg, params, prompt, n):
    ids = list(prompt) + [0] * n  # one shape: a causal model's logits do not see what follows
    for j in range(len(prompt), len(ids)):
        ids[j] = int(np.argmax(_plain(params, cfg, ids)[j - 1]))
    return ids[len(prompt):]


def _engine(model, **kw):
    from ray_tpu.llm import DecodeEngine

    cfg, params = model
    return DecodeEngine(cfg, params, **(dict(num_slots=3, max_seq=64, multi_step=4, token_budget=12) | kw))


@pytest.fixture(scope="module")
def engine(model):
    from ray_tpu._private.config import CONFIG

    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    eng = _engine(model)
    try:
        yield eng
    finally:
        eng.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


def test_the_engine_generates_the_plain_references_greedy_ids(engine, model):
    """Chunked by a 12-token budget, then the multi-step decode program."""
    cfg, params = model
    prompt = [int(t) for t in _tokens(27, seed=11)]
    assert _generate(engine, prompt, max_tokens=10) == _greedy_plain(cfg, params, prompt, 10)
    assert engine._prefix_cache is None
    st = engine.scheduler_stats()["model"]
    assert st["block"] == "lfm2" and st["cache_bytes"] == sum(a.nbytes for c in engine._caches for a in c)


def test_a_prompt_admitted_in_chunks_beside_a_decoding_slot_leaves_both_as_each_alone(engine, model):
    """One stream decodes while a long prompt is admitted chunk by chunk; every interleaved decode
    step runs over the slot whose prefill is half done. Both are token for token what the plain
    reference gives each alone."""
    cfg, params = model
    stream, long = [5, 9, 17], [int(t) for t in _tokens(55, seed=2)]
    want_stream, want_long = _greedy_plain(cfg, params, stream, 40), _greedy_plain(cfg, params, long, 6)
    before = engine.scheduler_stats()
    out, done = [], threading.Event()

    def cb(tok, fin):
        out.append(tok)
        if fin:
            done.set()

    from ray_tpu.llm import SamplingParams

    engine.submit(stream, SamplingParams(max_tokens=40), cb)
    while len(out) < 3:
        assert engine.error is None
        threading.Event().wait(0.005)
    assert _generate(engine, long, max_tokens=6) == want_long
    assert done.wait(300) and out == want_stream
    after = engine.scheduler_stats()
    assert after["interleaved_iterations"] - before["interleaved_iterations"] >= 3
    assert after["prefill_chunks"] - before["prefill_chunks"] >= 6


def test_slots_taken_over_under_load_give_the_plain_references_ids(engine, model):
    """Seven requests on three slots, sent together: every later one takes a slot whose window
    another request left filled, and is admitted in chunks beside slots that decode."""
    cfg, params = model
    prompts = [[int(t) for t in _tokens(n, seed=20 + n)] for n in (44, 9, 33, 21, 47, 12, 27)]
    want = [_greedy_plain(cfg, params, p, 9) for p in prompts]
    got = [None] * len(prompts)

    def one(i):
        got[i] = _generate(engine, prompts[i], max_tokens=9)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def test_a_taken_over_slot_holds_bit_for_bit_what_a_fresh_engines_holds(model):
    """One slot: the second request takes over what the first left. Its ids, and the slot's
    convolution inputs after it, are a fresh engine's to the bit."""
    first, second = [int(t) for t in _tokens(41, seed=6)], [int(t) for t in _tokens(18, seed=7)]
    used, fresh = _engine(model, num_slots=1), _engine(model, num_slots=1)
    try:
        _generate(used, first, max_tokens=11)
        got, want = _generate(used, second, max_tokens=7), _generate(fresh, second, max_tokens=7)
        assert got == want
        for i, kind in enumerate(LAYERS):
            if kind == "conv":
                np.testing.assert_array_equal(np.asarray(used._caches[i][0]), np.asarray(fresh._caches[i][0]))
    finally:
        used.shutdown()
        fresh.shutdown()


def test_scheduler_stats_count_the_experts_and_the_states_work(model):
    cfg = model[0]
    eng = _engine(model, num_slots=2, multi_step=1)
    try:
        eng.scheduler_stats()
        _generate(eng, [int(t) for t in _tokens(21, seed=13)], max_tokens=4)
        stats = eng.scheduler_stats()
        st, ex = stats["state"], stats["experts"]
        # budget 12: chunks of 8, 8 and 5 tokens in buckets 8, 8 and 8; 3 decode steps of one slot
        # (the fourth token is sampled from the third's logits and never fed)
        assert st["window"] == {"prefill_positions": 24, "prefill_padding": 3, "states_reset": 1, "decode_slot_steps": 3}
        assert st["bytes_per_slot"] == 4 * 2 * 64 * 4 == lfm2.state_bytes(cfg)
        w = ex["window"]
        assert (ex["held"], ex["of"], ex["first"]) == (8, 8, 0)
        # 21 prompt positions and 3 decode steps, 2 experts each, in 5 expert layers; padding routes nowhere
        assert w["pairs_routed"] == w["pairs_held"] == (21 + 3) * 2 * 5 == ex["pairs_routed"]
        assert w["layer_steps"] == 6 * 5 and w["decode_layer_steps"] == 3 * 5
        assert 2 * 15 == w["decode_experts_hit"] < w["experts_hit"] <= 2 * 15 + 8 * 15 and w["tiles_run"] >= w["experts_hit"]
        assert w["mean_load"] == pytest.approx(240 / 8) and w["max_load"] >= 30
        again = eng.scheduler_stats()
        assert again["state"]["window"]["prefill_positions"] == 0 and again["experts"]["window"]["pairs_routed"] == 0
        assert again["experts"]["pairs_routed"] == 240 and again["state"]["prefill_positions"] == 24
    finally:
        eng.shutdown()


def _refusals():
    from ray_tpu.llm import DecodeEngine, LLMConfig, load_model
    from ray_tpu.llm.kvcache import PrefixCacheManager
    from ray_tpu.llm.pd_disagg import DecodeServer, PrefillServer

    cfg = tiny()
    build = lambda **kw: DecodeEngine(cfg, {}, num_slots=1, max_seq=64, decode_loop=False, **kw)  # noqa: E731
    return {
        "lora": lambda: build(lora_config={"max_loras": 2, "rank": 4}),
        "speculation": lambda: build(spec_config={"method": "ngram"}),
        "tensor-parallel": lambda: build(tp=2),
        "prefix-cache": lambda: build(prefix_cache=PrefixCacheManager(4, 1 << 20, name="refused")),
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-lfm2", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-lfm2", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
        "checkpoint": lambda: load_model(LLMConfig(model_id="tiny-lfm2", model_config=cfg, checkpoint_path="/nowhere")),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step", "checkpoint"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'lfm2'"):
        _refusals()[what]()
