"""The `xing4` block (`ray_tpu/models/xing4.py`) at tiny widths on the CPU, float32: the engine's
cached paths (chunked prefill, then decode steps through the cache, single and multi-step) against
the benchmark's plain reference (`benchmark/lib/reference_xing4.py`, which imports nothing of the
program), always on logits and at a tolerance that a mixing matrix after one Sinkhorn step or a cached
path in bfloat16 fails; the YaRN table against the direct formula; a decode step through the two
kernels (interpreted) against the products; a gated-off slot; the counts; what the block refuses."""

import dataclasses
import importlib.util
import math
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import latent, pangu_moe, xing4
from ray_tpu.models.transformer import ModelConfig, Transformer, _rope, yarn_inv_freq, yarn_mscale
from ray_tpu.ops import attention, hyper_connection as hc, latent_attention as la

# float32 paths agree to rounding (1e-5 of logits whose standard deviation is 1); the controls move
# them by tens of thousands of times that, so the limit needs no tuning
ATOL = 3e-5
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1}


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "lib", "reference_xing4.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_xing4", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="xing4", vocab_size=96, hidden=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=96, max_seq=64,
        rope_theta=1e4, norm_eps=1e-6, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, mla_rescale=False,
        first_k_dense=1, n_routed_experts_total=16, n_routed_experts=16, first_expert=0,
        experts_per_token=4, moe_mlp_dim=24, routed_scaling_factor=2.0,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, rope_scaling=dict(YARN, original_max_position_embeddings=16))
    base.update(kw)
    return ModelConfig(**base)


def _model_dict(cfg: ModelConfig) -> dict:
    """The configuration as the benchmark's reference reads it: `ModelConfig`'s field names."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, xing4.init_params(cfg, jax.random.PRNGKey(1))


_PREFILL = jax.jit(xing4.prefill, static_argnums=1)
_DECODE = jax.jit(xing4.decode, static_argnums=1)
_REFERENCE = jax.jit(lambda p, t, model, kw: reference.forward(p, dict(model), t, q_block=8, **dict(kw)), static_argnums=(2, 3))


def _reference(params, cfg, toks, **kw):
    with jax.default_matmul_precision("highest"):
        model = tuple(sorted((k, v) for k, v in _model_dict(cfg).items() if k not in ("dtype", "param_dtype")))
        return np.asarray(_REFERENCE(params, jnp.asarray(toks, jnp.int32), model, tuple(sorted(kw.items()))))


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
    last, caches = _prefill(cfg, params, toks[:n_prompt], chunks, _dirty(xing4.init_caches(cfg, 3, 64)), slot=1)
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


# -- the cached paths against the benchmark's reference -------------------------------------


@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_chunked_prefill_then_decode_through_the_cache_matches_the_benchmarks_reference(model, chunks):
    cfg, params = model
    toks = _tokens(37, seed=1)
    got = _through_the_cache(cfg, params, toks, CHUNKS[chunks], 29)
    want = _reference(params, cfg, toks)
    np.testing.assert_allclose(got, want[28:], atol=ATOL)
    assert 0.5 < want.std() < 2.0 and np.mean(np.argmax(want, axis=-1) == toks) < 0.2  # logits, and not the input's


LONG_CHUNKS = {
    "three-chunks-of-128": ((128, 128), (128, 128), (44, 128)),   # the last one padded; keys in blocks of 128
    "a-chunk-of-256-and-a-tail": ((256, 256), (44, 64)),          # the tail's bucket is too small for a tile
}


@pytest.mark.parametrize("chunks", sorted(LONG_CHUNKS))
def test_chunked_prefill_through_the_interpreted_chunk_kernel_matches_the_benchmarks_reference(model, monkeypatch, chunks):
    """On the TPU a chunk's attention over each block of keys is the kernel `latent_chunk`; here the
    same trace with the kernel interpreted, over a cache of 512 rows (blocks of 128 keys) into a slot
    left dirty, with `score_scale` (YaRN's) in the kernel's scale: the prompt's last logits, and decode
    steps over the rows the chunks wrote."""
    cfg, params = model
    toks, kernel, calls = _tokens(304, seed=6), la.latent_chunk_attention, []

    def interpreted(q_full, lat_rows, kv_b, offset, kb, *a, **kw):
        calls.append((q_full.shape[0], kb, la.chunk_tiles(q_full.shape[0], kb)))
        return kernel(q_full, lat_rows, kv_b, offset, kb, *a, interpret=True, **kw)

    monkeypatch.setattr(la, "latent_chunk_attention", interpreted)
    prefill = jax.jit(xing4.prefill, static_argnums=1)  # traced under the patch
    caches, off = _dirty(xing4.init_caches(cfg, 3, 512)), 0
    for n, bucket in LONG_CHUNKS[chunks]:
        pad = np.full((1, bucket), 7, np.int32)
        pad[0, :n] = toks[off:off + n]
        last, caches, _ = prefill(params, cfg, jnp.asarray(pad), caches, jnp.int32(1), jnp.int32(off), jnp.int32(300))
        off += n
    assert off == 300 and {c[2] for c in calls} == {(b, 128) if b >= 128 else None for _, b in LONG_CHUNKS[chunks]}
    want = _reference(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(last), want[299], atol=ATOL)
    for at in range(300, 304):
        logits, caches = _decode(cfg, params, toks[at], caches, 1, at)
        np.testing.assert_allclose(logits, want[at], atol=ATOL)


@pytest.mark.parametrize("P", [7, 20, 33])
def test_the_decode_path_gives_the_prefill_paths_logits(model, P):
    """Position P reached by a decode step after a prefill of P tokens, and as the last of a prefill of
    P + 1: 48-token-wide mixes and one-token mixes of the same streams, the absorbed form over the slab
    and the expanded keys and values of a chunk."""
    cfg, params = model
    toks = _tokens(P + 1, seed=2)
    _, caches = _prefill(cfg, params, toks[:P], ((P, 64),), xing4.init_caches(cfg, 3, 64), slot=2)
    by_decode, _ = _decode(cfg, params, toks[P], caches, 2, P)
    by_prefill, _ = _prefill(cfg, params, toks, ((P + 1, 64),), xing4.init_caches(cfg, 3, 64), slot=0)
    np.testing.assert_allclose(by_decode, by_prefill, atol=ATOL)


def test_a_mixing_matrix_after_one_sinkhorn_step_fails_the_comparison_twenty_pass(model):
    """The reference with 1 Sinkhorn step against the program's 20 is another function: the cached
    paths, which pass the comparison above, fail it against that by thousands of times its limit; and a
    program that ran 1 step would fail against the reference's 20 alike."""
    cfg, params = model
    toks = _tokens(37, seed=1)
    got = _through_the_cache(cfg, params, toks, CHUNKS["three-chunks"], 29)
    one_step = _reference(params, cfg, toks, sinkhorn_iters=1)[28:]
    assert np.abs(got - one_step).max() > 3e3 * ATOL
    short = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    np.testing.assert_allclose(_through_the_cache(short, params, toks, CHUNKS["three-chunks"], 29), one_step, atol=ATOL)


@pytest.mark.parametrize("part", ["attn_hc", "mlp_hc"])
def test_the_projection_of_each_hyper_connection_makes_a_difference_the_comparison_sees(model, part):
    """At the paper's initialisation a program that dropped the projection would pass; at these draws
    (gains 1, biases of standard deviation 1) the coefficients' dynamic part moves the logits far."""
    cfg, params = model
    toks = _tokens(37, seed=1)
    want = _reference(params, cfg, toks)[28:]
    static = jax.tree_util.tree_map(lambda a: a, params)
    static["layer_1"] = dict(params["layer_1"], **{part: dict(params["layer_1"][part], phi=jnp.zeros_like(params["layer_1"][part]["phi"]))})
    got = _through_the_cache(cfg, static, toks, CHUNKS["three-chunks"], 29)
    assert np.abs(got - want).max() > 1e3 * ATOL


def test_the_cached_paths_in_bfloat16_fail_the_comparison_float32_passes(model):
    cfg, params = model
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    toks = _tokens(37, seed=1)
    got = _through_the_cache(low, params, toks, CHUNKS["three-chunks"], 29)
    want = _reference(params, cfg, toks)[28:]
    assert 100 * ATOL < np.abs(got - want).max() < 1.0  # wrong by rounding, not another function


# -- the rotary's scaling ------------------------------------------------------------------


def test_the_yarn_table_is_the_direct_formula_at_the_published_sizes():
    """ISSUE 42's numbers: f_i = 1e4^(-2i/64), low = floor(d(32)) = 10, high = ceil(d(1)) = 23, pairs under
    10 keep their frequency, pairs from 23 on turn 64 times slower, a linear blend between; m(1)^2 = 2.005."""
    got = np.asarray(yarn_inv_freq(64, 1e4, YARN), np.float64)
    d = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e4))  # noqa: E731
    assert (math.floor(d(32)), math.ceil(d(1))) == (10, 23)
    f = 1e4 ** (-2 * np.arange(32) / 64)
    t = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, f * (1 - t) + f / 64 * t, rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(got, np.asarray(reference.yarn_inv_freq(64, 1e4, YARN)), rtol=1e-6)
    assert yarn_mscale(YARN, "mscale_all_dim") ** 2 == pytest.approx((0.1 * math.log(64) + 1) ** 2) == pytest.approx(2.005, abs=1e-3)
    big = latent.attn_dims(tiny(qk_rope_head_dim=64, rope_scaling=YARN), True)
    assert big["score_scale"] == pytest.approx(2.005, abs=1e-3) and big["inv_freq"].shape == (32,)
    with pytest.raises(ValueError, match="only yarn with mscale equal to mscale_all_dim"):
        latent.attn_dims(tiny(rope_scaling=dict(YARN, mscale_all_dim=0)), True)


def test_at_factor_one_the_yarn_table_is_the_plain_rotary_and_without_scaling_nothing_changes():
    one = dict(YARN, factor=1)
    plain = 1.0 / (1e4 ** (np.arange(32, dtype=np.float32) / 32))
    np.testing.assert_allclose(np.asarray(yarn_inv_freq(64, 1e4, one)), plain, rtol=1e-6)
    assert yarn_mscale(one, "mscale") == yarn_mscale(one, "mscale_all_dim") == 1.0
    x, pos = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64)), jnp.arange(9)[None] + jnp.asarray([[0], [700]])
    np.testing.assert_allclose(np.asarray(_rope(x, pos, 1e4, yarn_inv_freq(64, 1e4, one))), np.asarray(_rope(x, pos, 1e4)), atol=1e-4)  # a frequency one rounding of float32 apart, 700 positions on
    assert np.abs(np.asarray(_rope(x, pos, 1e4, yarn_inv_freq(64, 1e4, YARN))) - np.asarray(_rope(x, pos, 1e4))).max() > 0.5
    # a block without `rope_scaling` gets the parent's dict, key for key: its lowered programs are the parent's text
    from tests.test_pangu_moe import tiny as pangu_tiny

    assert set(pangu_moe.dims(pangu_tiny())) == {"heads", "q_rank", "kv_rank", "nope", "rope", "v", "theta"}
    text = lambda *a: jax.jit(_rope, static_argnums=2).lower(*a).as_text()  # noqa: E731
    assert text(x, pos, 1e4) == text(x, pos, 1e4, None)


# -- the kernels, the gate and the counts -----------------------------------------------------


def test_the_decode_step_through_the_two_kernels_is_the_step_through_the_products(model, monkeypatch):
    """On the TPU a decode step's coefficients are the kernel `hc_map` and its attention the kernel
    `latent_attn`; here the same trace with both interpreted, against XLA's own."""
    cfg, params = model
    toks = _tokens(30, seed=5)
    _, caches = _prefill(cfg, params, toks, ((30, 32),), _dirty(xing4.init_caches(cfg, 3, 64)), slot=1)
    last, lens, gate = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 30, 3], jnp.int32), jnp.asarray([False, True, True])
    want, want_caches, _ = xing4.decode(params, cfg, last, caches, lens, gate)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    attn_kernel, map_kernel, calls = la.latent_attention, hc.hc_map, []
    monkeypatch.setattr(la, "latent_attention", lambda q, slab, seen, scale: attn_kernel(q, slab, seen, scale=scale, interpret=True))
    monkeypatch.setattr(hc, "hc_map", lambda *a, **kw: calls.append(a[0].shape) or map_kernel(*a, interpret=True, **kw))
    got, got_caches, _ = xing4.decode(params, cfg, last, caches, lens, gate)
    assert calls == [(24, 3)] * 2 * cfg.n_layers  # one call a sub-layer, the slots on the lane axis
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], atol=ATOL)
    for a, b in zip(got_caches, want_caches):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=ATOL)


@pytest.mark.parametrize("program", ["decode", "multi-step"])
def test_a_gated_off_slot_keeps_its_slab_bit_for_bit_and_is_not_counted(model, engine, program):
    """A slot in the middle of a chunked prefill is stepped over by every interleaved decode step."""
    cfg, params = model
    caches = _dirty(xing4.init_caches(cfg, 3, 64))
    before = [np.asarray(c[0]) for c in caches]
    last, lens = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 4, 30], jnp.int32)
    gate = jnp.asarray([True, False, True])
    if program == "decode":
        _, after, (experts, latent, mixed) = _DECODE(params, cfg, last, caches, lens, gate)
        steps = 1
    else:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=4))
        _, after, _, _, experts, latent, mixed = multi(params, None, jnp.zeros((3,), jnp.int32), last, caches, lens, gate,
                                                          jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
        steps = 4
    # two slots routed to 4 experts in each of 2 expert layers a step, through 6 sub-layers; the gated-off slot nowhere
    assert experts[0] == experts[1] == 2 * 4 * 2 * steps == int(experts[4:].sum())
    assert experts[3] == 2 * steps and 2 * steps <= experts[2] <= 2 * 8 * steps  # expert layers run; experts that took a pair
    assert mixed.tolist() == [2 * 6 * steps]
    assert latent.tolist() == [0, sum(10 + j + 31 + j for j in range(steps)), 0, 3 * 64 * steps]
    for b, (a,) in zip(before, after):
        np.testing.assert_array_equal(b[1], np.asarray(a)[1])
        assert not np.array_equal(b[0], np.asarray(a)[0])
        np.testing.assert_array_equal(np.asarray(a)[0, 9, 24:], 0)  # a row is c_kv | k_r | zeros


def test_padding_moves_no_valid_positions_logits_and_is_not_counted(model):
    """A chunk's bucket is padded with whatever token: the prompt's last logits and the rows written for
    the valid positions are the same bit for bit, and the counts are of the valid tokens alone."""
    cfg, params = model
    toks = _tokens(11, seed=8)
    out = []
    for filler in (7, 55):
        pad = np.full((1, 16), filler, np.int32)
        pad[0, :11] = toks
        logits, caches, (experts, _, mixed) = _PREFILL(params, cfg, jnp.asarray(pad), xing4.init_caches(cfg, 3, 64), jnp.int32(0), jnp.int32(0), jnp.int32(11))
        assert mixed.tolist() == [11 * 6] and experts[0] == 11 * 4 * 2 == experts[1] and experts[2] == experts[3] == 0
        out.append((np.asarray(logits), [np.asarray(c[0])[0, :11] for c in caches]))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    for a, b in zip(out[0][1], out[1][1]):
        np.testing.assert_array_equal(a, b)


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


def _greedy_reference(cfg, params, prompt, n):
    ids = list(prompt) + [0] * n  # one shape: a causal model's logits do not see what follows
    for j in range(len(prompt), len(ids)):
        ids[j] = int(np.argmax(_reference(params, cfg, ids)[j - 1]))
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
def test_the_engine_generates_the_benchmarks_references_greedy_ids(engine, model, beside):
    """Chunked by a 12-token budget (8- and 4-token chunks), then the multi-step decode program,
    with another request prefilling and decoding beside it in the second case."""
    cfg, params = model
    prompt = [int(t) for t in _tokens(27, seed=11)]
    want = _greedy_reference(cfg, params, prompt, 10)
    if beside:
        other = threading.Thread(target=_generate, args=(engine, [int(t) for t in _tokens(19, seed=12)]),
                                 kwargs=dict(max_tokens=8))
        other.start()
    got = _generate(engine, prompt, max_tokens=10, temperature=0.0)
    if beside:
        other.join()
    assert got == want
    assert engine._prefix_cache is None


def test_slots_taken_over_under_load_give_the_references_ids(engine, model):
    """Seven requests on three slots, sent together: every later one waits, then takes a slot whose
    latent rows another request left behind, and prefills in chunks beside slots that decode."""
    cfg, params = model
    prompts = [[int(t) for t in _tokens(n, seed=20 + n)] for n in (44, 9, 33, 21, 47, 12, 27)]
    want = [_greedy_reference(cfg, params, p, 9) for p in prompts]
    got = [None] * len(prompts)
    threads = [threading.Thread(target=lambda i=i: got.__setitem__(i, _generate(engine, prompts[i], max_tokens=9))) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def test_scheduler_stats_count_the_mixes_the_experts_and_the_slabs_rows(engine, model):
    cfg, _ = model
    engine.scheduler_stats()
    _generate(engine, [int(t) for t in _tokens(9, seed=13)], max_tokens=3)
    st = engine.scheduler_stats()
    ex, lat, mixed = st["experts"], st["latent"], st["hc"]
    # 9 prompt tokens and 2 decoded tokens (the third is sampled and never fed) pass 6 sub-layers, and
    # 2 expert layers with 4 experts a token, all 16 experts held here
    assert mixed["window"] == {"token_sublayers": (9 + 2) * 6} and mixed["streams"] == 4 and mixed["sinkhorn_iters"] == 20
    assert mixed["token_sublayers"] >= mixed["window"]["token_sublayers"]
    assert ex["window"]["pairs_routed"] == (9 + 2) * 2 * 4 == ex["window"]["pairs_held"] and ex["held"] == ex["of"] == 16
    # two decode steps of one slot: 2 expert layers each, 4 experts hit in each (one token's four)
    assert ex["window"]["decode_layer_steps"] == 4 and ex["window"]["decode_experts_hit"] == 16
    assert {"max_load", "mean_load"} <= set(ex["window"]) and ex["decode_layer_steps"] >= 4
    assert lat["window"] == {"rows_visible": 10 + 11, "rows_read": 2 * 3 * 64} and lat["bytes_per_row"] == 128 * 4
    assert st["model"]["block"] == "xing4"


def test_the_programs_name_the_hyper_connection_beside_the_sub_layers_never_inside_one(engine):
    """`hc` (with `map`, `pre`, `post`) is a sibling of `attn` and `mlp` in every layer: `latent`, `router`,
    `experts` and `shared_expert` are named as `pangu_moe` names them, and no operation under `hc` is
    under one of them, so the readers of those scopes take none of the hyper-connection's time."""
    from tests.test_trace_names import MODEL_SCOPES, _abstract, _parts, _sampler_args

    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    assert len(programs) >= 4
    for prog, args in programs:
        text = prog.lower(*_abstract(args)).as_text(dialect="hlo", debug_info=True)
        paths = [_parts(name) for name in re.findall(r'op_name="([^"]+)"', text)]
        scopes = {part for path in paths for part in path}
        assert set(MODEL_SCOPES) | {"hc", "map", "pre", "post", "latent", "router", "experts", "shared_expert"} <= scopes
        assert {"layer_0", "layer_1", "layer_2"} <= scopes and not {"indexer", "select", "window", "kv_attn"} & scopes
        under = [path for path in paths if "hc" in path]
        assert under and all(path[path.index("hc") - 1].startswith("layer_") for path in under)
        assert not [path for path in under if {"attn", "mlp", "latent", "router", "experts", "shared_expert"} & set(path)]
        assert {path[path.index("hc") + 1] for path in under if len(path) > path.index("hc") + 1} >= {"map", "pre", "post"}


# -- the tree and what the block refuses -------------------------------------------------------


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
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-xing4", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-xing4", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'xing4'"):
        _refusals()[what]()


def test_load_model_builds_the_blocks_tree_in_param_dtype_with_draws_that_make_the_mechanism_matter():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16, hidden=128)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-xing4", model_config=cfg, seed=3))
    leaves = jax.tree_util.tree_leaves(params)
    assert got_cfg.block == "xing4" and all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == xing4.num_params(cfg)
    assert xing4.LAYER_TYPES == () and cfg.layer_types == () and not models.names_its_layers(cfg)
    assert isinstance(cfg.rope_scaling, tuple) and dict(cfg.rope_scaling)["factor"] == 64 and hash(cfg)
    assert set(params["layer_0"]) == {"attn_norm", "attn_hc", "mlp_norm", "mlp_hc", "attn", "mlp"}
    assert set(params["layer_1"]["mlp"]["router"]) == {"kernel", "bias"} and "router" not in params["layer_0"]["mlp"]
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    part = params["layer_2"]["mlp_hc"]
    assert part["phi"].shape == (24, 4 * 128) and abs(f32(part["phi"]).std() * math.sqrt(4 * 128) - 1) < 0.05
    assert (f32(part["alpha"]) == 1).all() and 0.6 < f32(part["bias"]).std() < 1.5
    biases = np.concatenate([f32(params[f"layer_{i}"][s]["bias"]) for i in range(3) for s in ("attn_hc", "mlp_hc")])
    assert abs(biases.std() - 1) < 0.2 and abs(f32(params["layer_1"]["mlp"]["router"]["bias"]).std() - 0.1) < 0.06
    assert abs(float(jnp.std(params["embedding"].astype(jnp.float32))) - 0.02) < 0.003
    caches = jax.eval_shape(lambda: xing4.init_caches(dataclasses.replace(cfg, kv_lora_rank=512, qk_rope_head_dim=64), 48, 8192))
    assert caches[0][0].shape == (48, 8192, 640) and len(caches) == 3 and all(len(c) == 1 for c in caches)
