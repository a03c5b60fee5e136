"""The `dots3` block (`ray_tpu/models/dots3.py`) at tiny widths on the CPU, float32:
the engine's cached paths against the repo's plain reference (`forward_plain`: whole
sequence, no cache, no blocks) at contexts past the selection size (`index_topk` 8) and
three times the window (5); the decode path against the prefill path; a selection at
least as large as the context against dense latent attention; the shares of an 8-way
(here 4-way) expert-parallel layer against the uncut layer; what the block refuses."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import dots3
from ray_tpu.models.transformer import ModelConfig, Transformer
from ray_tpu.ops import latent_attention as la, moe

LAYERS = ("full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention")


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="dots3", vocab_size=96, hidden=64, n_layers=5, n_heads=4, n_kv_heads=4, mlp_dim=96, max_seq=64,
        rope_theta=8e7, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False,
        layer_types=LAYERS, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, index_n_heads=4, index_head_dim=16, index_topk=8, sliding_window=5, swa_n_heads=2,
        swa_q_lora_rank=24, swa_kv_lora_rank=24, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
        swa_v_head_dim=8, swa_rope_theta=50000.0, n_routed_experts_total=16, n_routed_experts=16,
        first_expert=0, experts_per_token=4, moe_mlp_dim=24)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, dots3.init_params(cfg, jax.random.PRNGKey(1))


# eager dispatch of the loops and scatters is what takes the time on the CPU: one program per shape
_PREFILL = jax.jit(dots3.prefill, static_argnums=1)
_DECODE = jax.jit(dots3.decode, static_argnums=1)
_PLAIN = jax.jit(dots3.forward_plain, static_argnums=(1, 3))
_EXPERTS = jax.jit(lambda p, x, valid, cfg: moe.routed_experts(p, x, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                                              first=cfg.first_expert), static_argnums=3)  # as `dots3._forward` calls it


def _plain(params, cfg, toks, experts=None):
    return np.asarray(_PLAIN(params, cfg, jnp.asarray(toks, jnp.int32), experts))


def _tokens(n, vocab=96, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,)).astype(np.int32)


def _prefill(cfg, params, toks, chunks, caches, slot):
    """`toks` into `slot` in chunks of (tokens, bucket); the last chunk's logits."""
    off, last = 0, None
    for n, bucket in chunks:
        pad = np.zeros((1, bucket), np.int32)
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


@pytest.mark.parametrize("chunks", [
    ((29, 32),),                               # one chunk, padded
    ((16, 16), (8, 8), (5, 8)),                # chunk boundaries inside the window and the selection
    ((4, 4), (4, 4), (16, 16), (3, 4), (2, 2)),  # chunks shorter than the window
], ids=["whole", "three-chunks", "short-chunks"])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_plain_reference(model, chunks):
    cfg, params = model
    P, new = 29, 12  # 41 positions: past index_topk 8 and over three windows of 5; the ring wraps 8 times
    toks = _tokens(P + new)
    ref = _plain(params, cfg, toks)
    last, caches = _prefill(cfg, params, toks[:P], chunks, dots3.init_caches(cfg, 3, 64), slot=1)
    np.testing.assert_allclose(last, ref[P - 1], atol=2e-5)
    for j in range(new):
        logits, caches = _decode(cfg, params, toks[P + j], caches, 1, P + j)
        np.testing.assert_allclose(logits, ref[P + j], atol=2e-5)


LONG_CHUNKS = {
    "three-chunks-of-128": ((128, 128), (128, 128), (44, 128)),   # the last one padded; keys in blocks of 128
    "a-chunk-of-256-and-a-tail": ((256, 256), (44, 64)),          # the tail's bucket is too small for a tile
}


@pytest.mark.parametrize("chunks", sorted(LONG_CHUNKS))
def test_chunked_prefill_through_the_interpreted_chunk_kernel_matches_the_plain_reference(model, monkeypatch, chunks):
    """On the TPU a chunk's attention over each block of keys is the kernel `latent_chunk`; here the
    same trace with the kernel interpreted and the selection (8 keys a query) as its mask operand,
    over a cache of 512 rows (blocks of 128 keys): the prompt's last logits, and decode steps over
    the rows the chunks wrote. The three sliding layers run `_window_attn_prefill` as ever."""
    cfg, params = model
    toks, kernel, calls = _tokens(304, seed=6), la.latent_chunk_attention, []

    def interpreted(q_full, lat_rows, kv_b, offset, kb, *a, **kw):
        calls.append((q_full.shape[0], kb, la.chunk_tiles(q_full.shape[0], kb)))
        return kernel(q_full, lat_rows, kv_b, offset, kb, *a, interpret=True, **kw)

    monkeypatch.setattr(la, "latent_chunk_attention", interpreted)
    prefill = jax.jit(dots3.prefill, static_argnums=1)  # traced under the patch
    caches, off = dots3.init_caches(cfg, 3, 512), 0
    for n, bucket in LONG_CHUNKS[chunks]:
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :n] = toks[off:off + n]
        last, caches, _ = prefill(params, cfg, jnp.asarray(pad), caches, jnp.int32(1), jnp.int32(off), jnp.int32(300))
        off += n
    assert off == 300 and {c[2] for c in calls} == {(b, 128) if b >= 128 else None for _, b in LONG_CHUNKS[chunks]}
    want = _plain(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(last), want[299], atol=2e-5)
    for at in range(300, 304):
        logits, caches = _decode(cfg, params, toks[at], caches, 1, at)
        np.testing.assert_allclose(logits, want[at], atol=2e-5)


@pytest.mark.parametrize("P", [7, 20, 33])
def test_the_decode_path_gives_the_prefill_paths_logits(model, P):
    """Position P by a decode step (selected rows gathered, W_kvb folded into the query)
    and as the last token of a prefill chunk (keys and values expanded under the mask)."""
    cfg, params = model
    toks = _tokens(P + 1, seed=P)
    _, caches = _prefill(cfg, params, toks[:P], ((P, 64),), dots3.init_caches(cfg, 3, 64), slot=2)
    by_decode, _ = _decode(cfg, params, toks[P], caches, 2, P)
    by_prefill, _ = _prefill(cfg, params, toks, ((P + 1, 64),), dots3.init_caches(cfg, 3, 64), slot=0)
    np.testing.assert_allclose(by_decode, by_prefill, atol=2e-5)


def test_a_selection_no_smaller_than_the_context_is_dense_latent_attention(model):
    cfg, params = model
    wide = dataclasses.replace(cfg, index_topk=64)
    toks = _tokens(40, seed=3)
    dense = _plain(params, wide, toks)
    sparse = _plain(params, cfg, toks)
    assert np.abs(dense[:8] - sparse[:8]).max() < 1e-5 < np.abs(dense[20:] - sparse[20:]).max()
    last, caches = _prefill(wide, params, toks[:30], ((16, 16), (14, 16)), dots3.init_caches(wide, 3, 64), slot=0)
    np.testing.assert_allclose(last, dense[29], atol=2e-5)
    for j in range(30, 40):
        logits, caches = _decode(wide, params, toks[j], caches, 0, j)
        np.testing.assert_allclose(logits, dense[j], atol=2e-5)


@pytest.mark.parametrize("k", [1, 5, 8, 40])
def test_the_selection_without_a_sort_is_top_ks(k):
    """Also where scores are equal (at tiny sizes every head's relu can be 0 at once): of
    equal scores `lax.top_k` takes the lower index first, and so does the mask."""
    x = np.random.default_rng(k).normal(size=(6, 32)).astype(np.float32)
    x[2, 10:] = -1e30          # fewer than k entries that count: every masked one is marked
    x[3, :] = np.float32(0.5)  # all equal
    x[4, ::3] = np.float32(0.0)
    x[5, 5:9] = x[5].max()
    got = np.asarray(jax.jit(dots3._top_k_mask, static_argnums=1)(jnp.asarray(x), k))
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(jax.lax.top_k(jnp.asarray(x), min(k, 32))[1]), True, axis=-1)
    rows = [0, 1, 3, 4, 5] if k > 10 else range(6)
    np.testing.assert_array_equal(got[rows], want[rows])
    assert k <= 10 or got[2].all()


def test_the_shares_of_an_expert_parallel_layer_add_up_to_the_uncut_layer(model):
    """The guide's share test: the parts all the shares give, with the shared expert
    (which every chip computes alike) counted once, are the whole layer's output."""
    cfg, params = model
    p = params["layer_2"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.hidden))
    valid = jnp.ones((2, 9), bool)
    whole, counts = _EXPERTS(p, x, valid, cfg)
    shared = moe.swiglu(p["shared"], x.reshape(-1, cfg.hidden)).reshape(x.shape)
    parts, held = 0.0, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, n_routed_experts=4, first_expert=first)
        sp = dict(p, experts={k: v[first:first + 4] for k, v in p["experts"].items()})
        y, c = _EXPERTS(sp, x, valid, share)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts)[first:first + 4])
        parts, held = parts + (y - shared), held + int(c.sum())
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole), atol=1e-5)
    assert held == 2 * 9 * cfg.experts_per_token == int(counts.sum())
    # and the model: one share's forward is the plain reference told to compute that share
    share = dataclasses.replace(cfg, n_routed_experts=4, first_expert=4)
    toks = _tokens(20, seed=9)
    sliced = jax.tree_util.tree_map_with_path(
        lambda path, v: v[4:8] if any(getattr(k, "key", None) == "experts" for k in path) else v, params)
    np.testing.assert_allclose(_plain(sliced, share, toks), _plain(params, cfg, toks, experts=(4, 4)), atol=1e-5)


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


@pytest.fixture(scope="module")
def engine(model):
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine

    cfg, params = model
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    eng = DecodeEngine(cfg, params, num_slots=3, max_seq=64, multi_step=4, token_budget=12)
    try:
        yield eng
    finally:
        eng.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


@pytest.mark.parametrize("sampling", [dict(temperature=0.0), dict(temperature=0.0, top_k=1)],
                         ids=["multi-step", "multi-step-again"])
def test_the_engine_generates_the_plain_references_greedy_ids(engine, model, sampling):
    """Chunked by a 12-token budget (8- and 4-token chunks), then the multi-step decode
    program, with another request prefilling and decoding beside it in the second case."""
    cfg, params = model
    prompt = [int(t) for t in _tokens(27, seed=11)]
    want = _greedy_plain(cfg, params, prompt, 10)
    if "top_k" in sampling:
        other = threading.Thread(target=_generate, args=(engine, [int(t) for t in _tokens(19, seed=12)]),
                                 kwargs=dict(max_tokens=8))
        other.start()
    got = _generate(engine, prompt, max_tokens=10, **sampling)
    if "top_k" in sampling:
        other.join()
    assert got == want
    assert engine._prefix_cache is None


def test_slots_taken_over_from_longer_requests_under_load_give_the_plain_references_ids(engine, model):
    """Seven requests on three slots, sent together: every later one waits, then takes a slot whose
    cache rows, ring and indexer keys another (often longer) request left behind, and prefills in
    chunks beside slots that decode. Each reply is the plain reference's, as if it ran alone."""
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


def test_scheduler_stats_count_the_expert_layers_pairs(engine, model):
    cfg, _ = model
    engine.scheduler_stats()
    _generate(engine, [int(t) for t in _tokens(9, seed=13)], max_tokens=3)
    ex = engine.scheduler_stats()["experts"]
    # 9 prompt tokens and 2 decoded tokens pass 4 expert layers with 4 experts a token (the
    # third token is sampled from the second's logits and never fed), all 16 experts held here
    assert ex["window"]["pairs_routed"] == (9 + 2) * 4 * 4 == ex["window"]["pairs_held"]
    assert ex["held"] == ex["of"] == 16 and ex["pairs_routed"] >= ex["window"]["pairs_routed"]
    assert ex["window"]["max_load"] >= ex["window"]["mean_load"] == (9 + 2) * 16 / 16
    assert engine.scheduler_stats()["model"]["block"] == "dots3"


def test_the_expert_counts_are_one_running_sum_that_may_wrap(engine):
    """The stepper adds each dispatch's counts into one device array; a report reads it once
    and takes the difference from the last reading, which an int32 wrap leaves right."""
    before = engine.scheduler_stats()["experts"]
    (acc,) = engine._stats_acc
    assert acc.shape == (2 + 16,) and acc.dtype == jnp.int32
    engine._note_stats((jnp.full((18,), 2**31 - 5, jnp.int32),))
    engine._note_stats((jnp.full((18,), 2**31 - 5, jnp.int32),))  # past int32's largest
    ex = engine.scheduler_stats()["experts"]
    assert ex["window"]["pairs_routed"] == 2**32 - 10 == ex["pairs_routed"] - before["pairs_routed"]
    engine._note_stats((jnp.full((18,), 17, jnp.int32),))  # the running sum is past 2**32 now
    assert engine.scheduler_stats()["experts"]["window"]["pairs_held"] == 17
    engine._note_stats((jnp.arange(18, dtype=jnp.int32),))
    assert engine.scheduler_stats()["experts"]["window"] == {
        "pairs_routed": 0, "pairs_held": 1, "max_load": 17, "mean_load": float(np.mean(np.arange(2, 18)))}
    assert engine.scheduler_stats()["experts"]["window"]["pairs_held"] == 0


def _refusals():
    from ray_tpu.llm import DecodeEngine, LLMConfig
    from ray_tpu.llm.kvcache import PrefixCacheManager
    from ray_tpu.llm.pd_disagg import DecodeServer, PrefillServer

    cfg = tiny()
    build = lambda **kw: DecodeEngine(cfg, {}, num_slots=1, max_seq=64, decode_loop=False, **kw)  # noqa: E731
    return {
        "lora": lambda: build(lora_config={"max_loras": 2, "rank": 4}),
        "speculation": lambda: build(spec_config={"method": "ngram"}),
        "tensor-parallel": lambda: build(tp=2),
        "prefix-cache": lambda: build(prefix_cache=PrefixCacheManager(4, 1 << 20, name="refused")),
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-dots3", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-dots3", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'dots3'"):
        _refusals()[what]()


@pytest.mark.parametrize("call", ["submit_prefilled", "prefill_detached"])
def test_the_engines_pd_entry_points_refuse_the_block(engine, call):
    from ray_tpu.llm import SamplingParams

    with pytest.raises(NotImplementedError, match=r"block 'dots3'"):
        if call == "submit_prefilled":
            engine.submit_prefilled(np.zeros((5, 2, 4, 4, 16), np.float32), 4, np.zeros((96,), np.float32),
                                    SamplingParams(), lambda *_: None)
        else:
            engine.prefill_detached([1, 2, 3])


def test_load_model_builds_the_blocks_tree_in_param_dtype():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16, n_routed_experts=4, first_expert=8)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-dots3", model_config=cfg, seed=3))
    leaves = jax.tree_util.tree_leaves(params)
    assert got_cfg.block == "dots3" and all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == dots3.num_params(cfg)
    assert params["layer_1"]["mlp"]["experts"]["gate"].shape == (4, 64, 24)
    assert params["layer_1"]["mlp"]["router"]["kernel"].shape == (64, 16)
    assert "indexer" in params["layer_1"]["attn"] and "indexer" not in params["layer_2"]["attn"]
