"""The `pangu_moe` block (`ray_tpu/models/pangu_moe.py`) at tiny widths on the CPU, float32: the
engine's cached paths against the benchmark's plain reference (`benchmark/lib/reference_pangu_moe.py`,
which imports nothing of the program) and the repo's (`forward_plain`), always on logits and at a
tolerance that a left-out norm or a cached path in bfloat16 fails; the decode path against the chunk
path; the kernel that reads the slab (`ops/latent_attention.py`, interpreted) against the two
products; a gated-off slot; the 32 shares of an expert-parallel layer against the uncut layer; what
the block refuses."""

import dataclasses
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import pangu_moe as pm
from ray_tpu.models.transformer import ModelConfig, Transformer
from ray_tpu.ops import attention, latent_attention as la, moe

# float32 paths agree to rounding (1e-5 of logits whose standard deviation is 0.6); the controls
# move them by thousands of times that, so the limit needs no tuning
ATOL = 2e-5
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "lib", "reference_pangu_moe.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_pangu_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="pangu_moe", vocab_size=96, hidden=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=96, max_seq=64,
        rope_theta=25.6e6, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, mla_rescale=False,
        first_k_dense=1, n_routed_experts_total=64, n_routed_experts=64, first_expert=0,
        experts_per_token=8, moe_mlp_dim=24, routed_scaling_factor=2.5)
    base.update(kw)
    return ModelConfig(**base)


def _model_dict(cfg: ModelConfig) -> dict:
    """The configuration as the benchmark's reference reads it: `ModelConfig`'s field names."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, pm.init_params(cfg, jax.random.PRNGKey(1))


# eager dispatch of the loops and scatters is what takes the time on the CPU: one program per shape
_PREFILL = jax.jit(pm.prefill, static_argnums=1)
_DECODE = jax.jit(pm.decode, static_argnums=1)
_PLAIN = jax.jit(pm.forward_plain, static_argnums=(1, 3))
_EXPERTS = jax.jit(lambda p, x, valid, cfg: moe.routed_experts(p, x, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                                              eps=pm.ROUTING_EPS, first=cfg.first_expert),
                   static_argnums=3)  # as `pangu_moe._forward` calls it


def _plain(params, cfg, toks, experts=None):
    return np.asarray(_PLAIN(params, cfg, jnp.asarray(toks, jnp.int32), experts))


def _reference(params, cfg, toks, q_block=8, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, _model_dict(cfg), jnp.asarray(toks, jnp.int32), q_block=q_block, **kw))


def _tokens(n, vocab=96, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,)).astype(np.int32)


def _prefill(cfg, params, toks, chunks, caches, slot):
    """`toks` into `slot` in chunks of (tokens, bucket); the last chunk's logits."""
    off, last = 0, None
    for n, bucket in chunks:
        pad = np.full((1, bucket), 7, np.int32)
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


def _through_the_cache(cfg, params, toks, chunks, n_prompt):
    """Logits at positions n_prompt - 1 .. len(toks) - 1: the prompt's last from the chunked
    prefill into a slot another request left dirty, the others from decode steps through the cache."""
    last, caches = _prefill(cfg, params, toks[:n_prompt], chunks, _dirty(pm.init_caches(cfg, 3, 64)), slot=1)
    out = [last]
    for at in range(n_prompt, len(toks)):
        logits, caches = _decode(cfg, params, toks[at], caches, 1, at)
        out.append(logits)
    return np.stack(out)


CHUNKS = {
    "whole": ((29, 32),),                                  # one chunk, padded
    "three-chunks": ((16, 16), (8, 8), (5, 8)),            # chunks that split the prompt unevenly
    "short-chunks": ((4, 4), (4, 4), (16, 16), (3, 4), (2, 2)),
}


# -- the cached paths against the two plain references -----------------------------------


def test_the_repos_plain_reference_is_the_benchmarks(model):
    cfg, params = model
    toks = _tokens(41)
    np.testing.assert_allclose(_plain(params, cfg, toks), _reference(params, cfg, toks), atol=ATOL)
    assert np.mean(np.argmax(_plain(params, cfg, toks), axis=-1) == toks) < 0.2  # not all but an argmax at the input


@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_chunked_prefill_then_decode_through_the_cache_matches_the_benchmarks_reference(model, chunks):
    cfg, params = model
    toks = _tokens(37, seed=1)
    got = _through_the_cache(cfg, params, toks, CHUNKS[chunks], 29)
    np.testing.assert_allclose(got, _reference(params, cfg, toks)[28:], atol=ATOL)


LONG_CHUNKS = {
    "three-chunks-of-128": ((128, 128), (128, 128), (44, 128)),   # the last one padded; keys in blocks of 128
    "a-chunk-of-256-and-a-tail": ((256, 256), (44, 64)),          # the tail's bucket is too small for a tile
}


@pytest.mark.parametrize("chunks", sorted(LONG_CHUNKS))
def test_chunked_prefill_through_the_interpreted_chunk_kernel_matches_the_plain_reference(model, monkeypatch, chunks):
    """On the TPU a chunk's attention over each block of keys is the kernel `latent_chunk`; here the
    same trace with the kernel interpreted, over a cache of 512 rows (blocks of 128 keys) into a slot
    left dirty: the prompt's last logits, and decode steps over the rows the chunks wrote."""
    cfg, params = model
    toks, kernel, calls = _tokens(304, seed=6), la.latent_chunk_attention, []

    def interpreted(q_full, lat_rows, kv_b, offset, kb, *a, **kw):
        calls.append((q_full.shape[0], kb, la.chunk_tiles(q_full.shape[0], kb)))
        return kernel(q_full, lat_rows, kv_b, offset, kb, *a, interpret=True, **kw)

    monkeypatch.setattr(la, "latent_chunk_attention", interpreted)
    prefill = jax.jit(pm.prefill, static_argnums=1)  # traced under the patch
    caches, off = _dirty(pm.init_caches(cfg, 3, 512)), 0
    for n, bucket in LONG_CHUNKS[chunks]:
        pad = np.full((1, bucket), 7, np.int32)
        pad[0, :n] = toks[off:off + n]
        last, caches, _ = prefill(params, cfg, jnp.asarray(pad), caches, jnp.int32(1), jnp.int32(off), jnp.int32(300))
        off += n
    assert off == 300 and {c[2] for c in calls} == {(b, 128) if b >= 128 else None for _, b in LONG_CHUNKS[chunks]}
    want = _plain(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(last), want[299], atol=ATOL)
    for at in range(300, 304):
        logits, caches = _decode(cfg, params, toks[at], caches, 1, at)
        np.testing.assert_allclose(logits, want[at], atol=ATOL)


@pytest.mark.parametrize("P", [7, 20, 33])
def test_the_decode_path_gives_the_prefill_paths_logits(model, P):
    """The absorbed form over the slab against the expanded keys and values of a chunk: position P
    reached by a decode step after a prefill of P tokens, and as the last of a prefill of P + 1."""
    cfg, params = model
    toks = _tokens(P + 1, seed=2)
    _, caches = _prefill(cfg, params, toks[:P], ((P, 64),), pm.init_caches(cfg, 3, 64), slot=2)
    by_decode, _ = _decode(cfg, params, toks[P], caches, 2, P)
    by_prefill, _ = _prefill(cfg, params, toks, ((P + 1, 64),), pm.init_caches(cfg, 3, 64), slot=0)
    np.testing.assert_allclose(by_decode, by_prefill, atol=ATOL)


@pytest.mark.parametrize("norm", NORMS)
def test_each_of_a_layers_four_norms_makes_a_difference_the_comparison_sees(model, norm):
    """The reference with one norm left out is another function: the cached paths, which pass the
    comparison above, fail it against that by four orders of magnitude."""
    cfg, params = model
    toks = _tokens(37, seed=1)
    got = _through_the_cache(cfg, params, toks, CHUNKS["three-chunks"], 29)
    without = _reference(params, cfg, toks, drop=norm)[28:]
    assert np.abs(got - without).max() > 1e4 * ATOL
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, without, atol=ATOL)


def test_every_layer_has_the_four_norms_and_is_of_one_kind_with_no_field_to_say_so(model):
    """The block is the one with the post-norms and with layers of one kind: its module says so
    (`LAYER_TYPES`), not a field of `ModelConfig` nor a list of block names in it."""
    cfg, params = model
    for i in range(cfg.n_layers):
        assert {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"} <= set(params[f"layer_{i}"])
    assert "sandwich_norm" not in {f.name for f in dataclasses.fields(ModelConfig)}
    assert pm.LAYER_TYPES == () and cfg.layer_types == () and not models.names_its_layers(cfg)
    with pytest.raises(ValueError, match="block 'dots3' needs one of layer_types per layer: 0 for n_layers=3"):
        dataclasses.replace(cfg, block="dots3")


def test_the_cached_paths_in_bfloat16_fail_the_comparison_float32_passes(model):
    """The configuration says what precision the paths run in; a cached path in a lower one (the
    tree, the activations and the cache in bfloat16) is out of the comparison's limit by far."""
    cfg, params = model
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    toks = _tokens(37, seed=1)
    got = _through_the_cache(low, params, toks, CHUNKS["three-chunks"], 29)
    want = _reference(params, cfg, toks)[28:]
    assert 100 * ATOL < np.abs(got - want).max() < 0.5  # wrong by rounding, not another function


def test_the_latents_must_not_be_rescaled():
    with pytest.raises(ValueError, match="mla_rescale"):
        pm.init_caches(tiny(mla_rescale=True), 1, 8) and pm.dims(tiny(mla_rescale=True))


# -- the slab and the kernel that reads it -------------------------------------------------


def test_the_cache_is_one_latent_slab_a_layer_in_whole_rows_of_128_lanes(model):
    cfg, _ = model
    caches = pm.init_caches(cfg, 3, 64)
    assert [len(c) for c in caches] == [1] * cfg.n_layers
    assert caches[0][0].shape == (3, 64, 128) and la.slab_width(576) == 640 and la.slab_width(512) == 512
    big = dataclasses.replace(cfg, kv_lora_rank=512, qk_rope_head_dim=64)
    assert jax.eval_shape(lambda: pm.init_caches(big, 16, 32768))[0][0].shape == (16, 32768, 640)


@pytest.mark.parametrize("lens", [(0, 511, 512, 2047), (100, 1300, 5, 1024)], ids=["block-edges", "mixed"])
def test_the_kernel_interpreted_is_the_two_products_and_reads_whole_blocks_up_to_each_length(lens):
    B, H, W, T = 4, 8, 256, 2048
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, W))
    slab = jax.random.normal(jax.random.PRNGKey(3), (B, T, W))
    lens = jnp.asarray(lens, jnp.int32)
    got = la.latent_attention(q, slab, lens, scale=0.05, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(la.latent_attention_xla(q, slab, lens, scale=0.05)), atol=2e-6)
    # rows past a slot's length are never read: poison them
    poisoned = jnp.where(jnp.arange(T)[None, :, None] < la.rows_read(lens, T)[:, None, None], slab, jnp.nan)
    np.testing.assert_array_equal(np.asarray(la.latent_attention(q, poisoned, lens, scale=0.05, interpret=True)), np.asarray(got))
    assert la.block_rows(T) == 512 and la.block_rows(64) == 64
    np.testing.assert_array_equal(np.asarray(la.rows_read(lens, T)), (np.asarray(lens) // 512 + 1) * 512)


def test_the_decode_step_through_the_kernel_is_the_step_through_the_products(model, monkeypatch):
    """On the TPU a decode step folds W_kvb into the query and calls the kernel over the slab; here
    the same trace with the kernel interpreted, against the products: the logits, the slab written,
    and the rows counted (whole blocks of the gated slots, one block of an idle one, against every
    row of every slot)."""
    cfg, params = model
    toks = _tokens(30, seed=5)
    _, caches = _prefill(cfg, params, toks, ((30, 32),), _dirty(pm.init_caches(cfg, 3, 64)), slot=1)
    last, lens, gate = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 30, 3], jnp.int32), jnp.asarray([False, True, True])
    want, want_caches, (_, counted) = pm.decode(params, cfg, last, caches, lens, gate)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    kernel = la.latent_attention
    monkeypatch.setattr(la, "latent_attention", lambda q, slab, seen, scale: kernel(q, slab, seen, scale=scale, interpret=True))
    got, got_caches, (_, counted_kernel) = pm.decode(params, cfg, last, caches, lens, gate)
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], atol=ATOL)
    for a, b in zip(got_caches, want_caches):  # a later layer's row is a function of the layers before it
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=ATOL)
    assert counted.tolist() == [0, 31 + 4, 0, 3 * 64] and counted_kernel.tolist() == [0, 31 + 4, 0, 3 * 64]
    # 64 rows are one block here; at the cell's sizes the two differ (the test above)


@pytest.mark.parametrize("program", ["decode", "multi-step"])
def test_a_gated_off_slot_keeps_its_slab_bit_for_bit(model, engine, program):
    """A slot in the middle of a chunked prefill is stepped over by every interleaved decode step."""
    cfg, params = model
    caches = _dirty(pm.init_caches(cfg, 3, 64))
    before = [np.asarray(c[0]) for c in caches]
    last, lens = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 4, 30], jnp.int32)
    gate = jnp.asarray([True, False, True])
    if program == "decode":
        _, after, (experts, latent) = _DECODE(params, cfg, last, caches, lens, gate)
        steps = 1
    else:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=4))
        _, after, _, _, experts, latent = multi(params, None, jnp.zeros((3,), jnp.int32), last, caches, lens, gate,
                                                   jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
        steps = 4
    # two slots routed to 8 experts in each of 2 expert layers a step; the gated-off slot is routed nowhere
    assert experts[0] == experts[1] == 2 * 8 * 2 * steps == int(experts[2:].sum())
    visible = sum(10 + j + 31 + j for j in range(steps))
    assert latent.tolist() == [0, visible, 0, 3 * 64 * steps]
    for b, (a,) in zip(before, after):
        np.testing.assert_array_equal(b[1], np.asarray(a)[1])
        assert not np.array_equal(b[0], np.asarray(a)[0])
        np.testing.assert_array_equal(np.asarray(a)[0, 9, 24:], 0)  # a row is c_kv | k_r | zeros


# -- the expert layer and its shares ---------------------------------------------------------


def test_the_32_shares_of_an_expert_parallel_layer_add_up_to_the_uncut_layer(model):
    """The guide's share test at the deployment's 32 ways: the parts all the shares give, with the
    shared expert (which every chip computes alike) counted once, are the whole layer's output."""
    cfg, params = model
    p = params["layer_2"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.hidden))
    valid = jnp.ones((2, 9), bool)
    whole, counts = _EXPERTS(p, x, valid, cfg)
    shared = moe.swiglu(p["shared"], x.reshape(-1, cfg.hidden)).reshape(x.shape)
    parts, held = 0.0, 0
    for first in range(0, 64, 2):
        share = dataclasses.replace(cfg, n_routed_experts=2, first_expert=first)
        sp = dict(p, experts={k: v[first:first + 2] for k, v in p["experts"].items()})
        y, c = _EXPERTS(sp, x, valid, share)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts)[first:first + 2])
        parts, held = parts + (y - shared), held + int(c.sum())
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole), atol=1e-5)
    assert held == 2 * 9 * cfg.experts_per_token == int(counts.sum())
    # and the model: one share's forward is either reference told to compute that share
    share = dataclasses.replace(cfg, n_routed_experts=2, first_expert=6)
    toks = _tokens(20, seed=9)
    sliced = jax.tree_util.tree_map_with_path(
        lambda path, v: v[6:8] if any(getattr(k, "key", None) == "experts" for k in path) else v, params)
    np.testing.assert_allclose(_plain(sliced, share, toks), _plain(params, cfg, toks, experts=(6, 2)), atol=ATOL)
    np.testing.assert_allclose(_plain(sliced, share, toks), _reference(sliced, share, toks), atol=ATOL)


def test_the_routing_is_sigmoid_scores_over_their_sum_times_the_scaling_with_no_bias(model):
    cfg, params = model
    from ray_tpu.ops.moe import sigmoid_routing

    h = jax.random.normal(jax.random.PRNGKey(6), (11, cfg.hidden))
    kernel = params["layer_1"]["mlp"]["router"]["kernel"]
    assert "bias" not in params["layer_1"]["mlp"]["router"]
    ids, weights = sigmoid_routing(h, kernel, jnp.zeros((64,)), 8, 2.5, eps=pm.ROUTING_EPS)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(h, kernel, precision="highest")))
    np.testing.assert_array_equal(np.sort(np.asarray(ids), axis=-1), np.sort(np.argsort(-scores, axis=-1)[:, :8], axis=-1))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights), 2.5 * np.take_along_axis(scores, np.asarray(ids), -1)
                               / np.take_along_axis(scores, np.asarray(ids), -1).sum(-1, keepdims=True), rtol=1e-5)


# -- through the engine ---------------------------------------------------------------------


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


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "beside-another"])
def test_the_engine_generates_the_plain_references_greedy_ids(engine, model, beside):
    """Chunked by a 12-token budget (8- and 4-token chunks), then the multi-step decode program,
    with another request prefilling and decoding beside it in the second case."""
    cfg, params = model
    prompt = [int(t) for t in _tokens(27, seed=11)]
    want = _greedy_plain(cfg, params, prompt, 10)
    if beside:
        other = threading.Thread(target=_generate, args=(engine, [int(t) for t in _tokens(19, seed=12)]),
                                 kwargs=dict(max_tokens=8))
        other.start()
    got = _generate(engine, prompt, max_tokens=10, temperature=0.0)
    if beside:
        other.join()
    assert got == want
    assert engine._prefix_cache is None


def test_slots_taken_over_from_longer_requests_under_load_give_the_plain_references_ids(engine, model):
    """Seven requests on three slots, sent together: every later one waits, then takes a slot whose
    latent rows another (often longer) request left behind, and prefills in chunks beside slots
    that decode. Each reply is the plain reference's, as if it ran alone."""
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


def test_scheduler_stats_count_the_experts_pairs_and_the_slabs_rows(engine, model):
    cfg, _ = model
    engine.scheduler_stats()
    _generate(engine, [int(t) for t in _tokens(9, seed=13)], max_tokens=3)
    st = engine.scheduler_stats()
    ex, lat = st["experts"], st["latent"]
    # 9 prompt tokens and 2 decoded tokens pass 2 expert layers with 8 experts a token (the third
    # token is sampled from the second's logits and never fed), all 64 experts held here
    assert ex["window"]["pairs_routed"] == (9 + 2) * 2 * 8 == ex["window"]["pairs_held"]
    assert ex["held"] == ex["of"] == 64 and ex["pairs_routed"] >= ex["window"]["pairs_routed"]
    # the two decode steps saw 10 and 11 rows of a layer's slab and ran over all 64 of all 3 slots
    assert lat["window"] == {"rows_visible": 10 + 11, "rows_read": 2 * 3 * 64}
    assert not any(key.startswith("latent") for key in ex["window"])  # the slabs' counts are under `["latent"]` alone
    assert lat["rows_read"] >= 384 and lat["bytes_per_row"] == 128 * 4
    assert st["model"]["block"] == "pangu_moe"


def test_the_rows_counts_do_not_wrap_where_a_count_of_rows_would(model):
    """A step of 16 slots of 32768 rows adds 512 and 0 to a count kept as (1024s, remainder): an
    int32 of rows would wrap in 4096 such steps, a window's worth at the cell's sizes."""
    cfg, _ = model
    total = (np.zeros((2 + 64,), np.int64), np.asarray([3 * 2**21, 1000, 5 * 2**21, 7], np.int64))
    out = pm.report(cfg, total, total)["latent"]
    assert out["rows_visible"] == 3 * 2**31 + 1000 and out["rows_read"] == 5 * 2**31 + 7
    assert pm.split(jnp.int32(16 * 32768)).tolist() == [512, 0]


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
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-pangu", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-pangu", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'pangu_moe'"):
        _refusals()[what]()


def test_load_model_builds_the_blocks_tree_in_param_dtype():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16, n_routed_experts=2, first_expert=8)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-pangu", model_config=cfg, seed=3))
    leaves = jax.tree_util.tree_leaves(params)
    assert got_cfg.block == "pangu_moe" and all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == pm.num_params(cfg)
    assert params["layer_1"]["mlp"]["experts"]["gate"].shape == (2, 64, 24)
    assert params["layer_1"]["mlp"]["router"]["kernel"].shape == (64, 64)
    assert params["layer_0"]["attn"]["q_b"]["kernel"].shape == (24, 4 * 16)  # heads and their [nope | rope] on one axis
    assert set(params["layer_0"]) == {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "attn", "mlp"}
    assert abs(float(jnp.std(params["embedding"].astype(jnp.float32))) - 0.02) < 0.002
