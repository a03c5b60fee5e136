"""The `granite_hybrid` block (`ray_tpu/models/granite_hybrid.py`, `ray_tpu/ops/ssd.py`) at
tiny widths on the CPU, float32: the two forms of the recurrence against each other and
against a token-by-token loop; the engine's cached paths against the repo's plain reference
(`forward_plain`); and what a state forces that rows indexed by position never did: a reset
at a prompt's first chunk, padding that is no step, a gated-off slot left bit for bit, a
prompt admitted in chunks beside a slot that decodes, a request that ends inside a
multi-step run."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.transformer import ModelConfig, Transformer
from ray_tpu.ops.ssd import ssd_chunked, ssd_step

LAYERS = ("mamba", "mamba", "attention", "mamba", "mamba")


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="granite_hybrid", vocab_size=96, hidden=64, n_layers=5, n_heads=4, n_kv_heads=2, mlp_dim=96,
        max_seq=64, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False,
        tie_embeddings=True, layer_types=LAYERS, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_d_conv=4, mamba_chunk_size=8, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=8.0, position_embedding_type="nope")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, gh.init_params(cfg, jax.random.PRNGKey(1))


_PREFILL = jax.jit(gh.prefill, static_argnums=1)
_DECODE = jax.jit(gh.decode, static_argnums=1)
_PLAIN = jax.jit(gh.forward_plain, static_argnums=1)


def _plain(params, cfg, toks):
    return np.asarray(_PLAIN(params, cfg, jnp.asarray(toks, jnp.int32)))


def _tokens(n, vocab=96, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,)).astype(np.int32)


def _prefill(cfg, params, toks, chunks, caches, slot):
    """`toks` into `slot` in chunks of (tokens, bucket); the last chunk's logits. Only a prompt's
    last chunk is shorter than its bucket (`scheduler.next_plan` grants whole buckets before it)."""
    off, last = 0, None
    for n, bucket in chunks:
        pad = np.full((1, bucket), 7, np.int32)  # a real id: padding must be no step, not a zero step
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


# -- the recurrence's two forms --------------------------------------------------------


def _terms(S, H=4, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(x=jax.random.normal(k[0], (S, H, P)), dt=jax.nn.softplus(jax.random.normal(k[1], (S, H)) - 1.0),
                A=-jnp.exp(jax.random.uniform(k[2], (H,)) * 2.0), B=jax.random.normal(k[3], (S, N)),
                C=jax.random.normal(k[4], (S, N)), D=jnp.ones((H,))), jax.random.normal(k[5], (H, P, N))


def _token_by_token(t, h, n):
    ys = []
    for i in range(n):
        h = jnp.exp(t["dt"][i] * t["A"])[:, None, None] * h + (t["dt"][i][:, None] * t["x"][i])[..., None] * t["B"][i]
        ys.append(jnp.sum(h * t["C"][i], axis=-1) + t["D"][:, None] * t["x"][i])
    return jnp.stack(ys), h


@pytest.mark.parametrize("chunk", [8, 16, 64], ids=["several-chunks", "ragged-last-chunk", "one-short-chunk"])
@pytest.mark.parametrize("carried", [False, True], ids=["from-zeros", "from-a-state"])
@pytest.mark.parametrize("n_valid", [37, 30, 3], ids=["no-padding", "padded", "mostly-padding"])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk, carried, n_valid):
    t, h0 = _terms(37, seed=chunk + n_valid)
    h0 = h0 if carried else jnp.zeros_like(h0)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = _token_by_token(t, h0, n_valid)
        y, h = ssd_chunked(**t, h0=h0, valid=jnp.arange(37) < n_valid, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y[:n_valid]), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-5)


@pytest.mark.parametrize("carried", [False, True], ids=["from-zeros", "from-a-state"])
def test_the_one_token_update_iterated_is_the_chunked_scan(carried):
    t, h0 = _terms(21, seed=5)
    h0 = h0 if carried else jnp.zeros_like(h0)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = ssd_chunked(**t, h0=h0, valid=jnp.ones((21,), bool), chunk=8)
    h, ys = jnp.stack([h0, h0 + 1.0]), []
    for i in range(21):
        # slot 1 rides along with its gate off
        y, h = ssd_step(jnp.stack([t["x"][i]] * 2), jnp.stack([t["dt"][i]] * 2), t["A"], jnp.stack([t["B"][i]] * 2),
                        jnp.stack([t["C"][i]] * 2), t["D"], h, jnp.asarray([True, False]))
        ys.append(y[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(ys)), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h[0]), np.asarray(want_h), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(h[1]), np.asarray(h0 + 1.0))


# -- the cached paths against the plain reference ----------------------------------------


@pytest.mark.parametrize("chunks", [
    ((29, 32),),                                  # the whole prompt, padded to its bucket
    ((16, 16), (8, 8), (5, 8)),                   # three chunks, the last padded
    ((2, 2), (4, 4), (23, 32)),                   # shorter than the convolution, than the scan's chunk; one over three
    ((1, 1), (1, 1), (1, 1), (26, 32)),           # the convolution's window carried over chunks of one token
], ids=["whole", "three-chunks", "short-then-spanning", "single-tokens"])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_plain_reference(model, chunks):
    """Into a slot whose state, window and rows a longer request left behind."""
    cfg, params = model
    P, new = 29, 12
    toks = _tokens(P + new)
    ref = _plain(params, cfg, toks)
    last, caches = _prefill(cfg, params, toks[:P], chunks, _dirty(gh.init_caches(cfg, 3, 64)), slot=1)
    np.testing.assert_allclose(last, ref[P - 1], atol=2e-6)
    for j in range(new):
        logits, caches = _decode(cfg, params, toks[P + j], caches, 1, P + j)
        np.testing.assert_allclose(logits, ref[P + j], atol=2e-6)


def test_the_logits_are_not_all_but_an_argmax_at_the_input_token(model):
    """The head is the embedding again: drawn too wide, the input token's own row wins every
    position and greedy decoding repeats its input, whatever the layers compute."""
    cfg, params = model
    toks = _tokens(40, seed=4)
    assert np.mean(np.argmax(_plain(params, cfg, toks), axis=-1) == toks) < 0.2


@pytest.mark.parametrize("program", ["decode", "multi-step"])
def test_a_gated_off_slot_keeps_its_state_and_rows_bit_for_bit(model, engine, program):
    """A slot in the middle of a chunked prefill is stepped over by every interleaved decode
    step; with a state that costs the whole prompt, not one row."""
    cfg, params = model
    caches = _dirty(gh.init_caches(cfg, 3, 64))
    before = [tuple(np.asarray(a) for a in c) for c in caches]
    last, lens = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 4, 30], jnp.int32)
    gate = jnp.asarray([True, False, True])
    if program == "decode":
        _, after, (counts,) = _DECODE(params, cfg, last, caches, lens, gate)
        assert counts.tolist() == [0, 0, 0, 2]
    else:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=4))
        _, after, _, _, counts = multi(params, None, jnp.zeros((3,), jnp.int32), last, caches, lens, gate,
                                          jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
        assert counts.tolist() == [0, 0, 0, 8]
    for (b, a) in zip(before, after):
        for x, y in zip(b, a):
            np.testing.assert_array_equal(x[1], np.asarray(y)[1])
            assert not np.array_equal(x[0], np.asarray(y)[0])


def test_load_model_builds_the_blocks_tree():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-granite", model_config=cfg, seed=3))
    m = params["layer_0"]["attn"]
    assert got_cfg.block == "granite_hybrid" and "lm_head" not in params
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) == gh.num_params(cfg)
    assert m["in_proj"]["kernel"].shape == (64, 2 * 128 + 2 * 16 + 8) and m["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert m["conv"]["kernel"].shape == (4, 128 + 32) and set(params["layer_2"]["attn"]) == {"q", "k", "v", "o"}
    # Mamba-2's own draws, kept in float32: A in [1, 16], the step's bias softplus's inverse of [0.001, 0.1]
    assert m["A_log"].dtype == m["dt_bias"].dtype == jnp.float32
    assert 0.0 <= float(m["A_log"].min()) and float(m["A_log"].max()) <= np.log(16.0)
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001


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
    assert st["block"] == "granite_hybrid" and st["cache_bytes"] == sum(a.nbytes for c in engine._caches for a in c)


def test_a_prompt_admitted_in_chunks_beside_a_decoding_slot_leaves_both_as_each_alone(engine, model):
    """The shape of `test_long_prefill_does_not_stall_decode_integration`: one stream decodes
    while a long prompt is admitted chunk by chunk; every interleaved decode step runs over the
    slot whose prefill is half done. Both are token for token what the plain reference gives
    each alone."""
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
    """Seven requests on three slots, sent together: every later one takes a slot whose state
    another request left advanced, and is admitted in chunks beside slots that decode."""
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
    state and convolution inputs after it, are a fresh engine's to the bit."""
    first, second = [int(t) for t in _tokens(41, seed=6)], [int(t) for t in _tokens(18, seed=7)]
    used, fresh = _engine(model, num_slots=1), _engine(model, num_slots=1)
    try:
        _generate(used, first, max_tokens=11)
        got, want = _generate(used, second, max_tokens=7), _generate(fresh, second, max_tokens=7)
        assert got == want
        for i, kind in enumerate(LAYERS):
            if kind == "mamba":
                for a, b in zip(used._caches[i], fresh._caches[i]):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        used.shutdown()
        fresh.shutdown()


def test_a_request_that_ends_inside_a_multi_step_run_leaves_its_slot_fit_for_the_next(model):
    """The run goes on to its end over a slot whose request stopped at its second token; the
    slot's next request must not be continued from that state."""
    cfg, params = model
    prompt, nxt = [int(t) for t in _tokens(8, seed=8)], [int(t) for t in _tokens(13, seed=9)]
    ids = _greedy_plain(cfg, params, prompt, 12)
    stop = next(j for j in range(2, 12) if ids[j] not in ids[:j] and j % 4 != 0)  # not a run's last token
    eng = _engine(model, num_slots=1)
    try:
        assert _generate(eng, prompt, max_tokens=12, stop_token_id=ids[stop]) == ids[:stop + 1]
        assert _generate(eng, nxt, max_tokens=9) == _greedy_plain(cfg, params, nxt, 9)
    finally:
        eng.shutdown()


def test_scheduler_stats_count_positions_padding_resets_and_steps(model):
    eng = _engine(model, num_slots=2, multi_step=1)
    try:
        eng.scheduler_stats()
        _generate(eng, [int(t) for t in _tokens(21, seed=13)], max_tokens=4)
        st = eng.scheduler_stats()["state"]
        # budget 12: chunks of 8, 8 and 5 tokens in buckets 8, 8 and 8; 3 decode steps of one slot
        # (the fourth token is sampled from the third's logits and never fed)
        assert st["window"] == {"prefill_positions": 24, "prefill_padding": 3, "states_reset": 1, "decode_slot_steps": 3}
        assert st["bytes_per_slot"] == 4 * (8 * 16 * 16 * 4 + 3 * 160 * 4) == gh.state_bytes(model[0])
        assert eng.scheduler_stats()["state"]["window"]["prefill_positions"] == 0 and st["prefill_positions"] == 24
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
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-granite", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-granite", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
        "checkpoint": lambda: load_model(LLMConfig(model_id="tiny-granite", model_config=cfg, checkpoint_path="/nowhere")),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step", "checkpoint"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'granite_hybrid'"):
        _refusals()[what]()
