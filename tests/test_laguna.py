"""The `laguna` block (`ray_tpu/models/laguna.py`) at tiny widths on the CPU, float32: the engine's
cached paths against the benchmark's plain reference (`benchmark/lib/reference_laguna.py`, which
imports nothing of the program), always on logits: prefill in chunks longer and shorter than the
window, then decoding through the slabs and past a wrap of the rings, single-step and multi-step;
the ring a chunk leaves against the ring a row-at-a-time decode leaves; padding and gated-off slots;
the chunk's key-block loop against `cached_attention_xla`; the partial rotary and the YaRN table
against the direct formula; the softmax router against the literal formula; the four shares of an
expert-parallel layer against the uncut layer; the two controls; what the block refuses."""

import dataclasses
import importlib.util
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import laguna as lg
from ray_tpu.models.transformer import ModelConfig, Transformer, _rope, yarn_inv_freq
from ray_tpu.ops import attention, moe

# float32 paths agree to rounding (1e-5 of logits whose standard deviation is 0.6); the controls
# move them by thousands of times that, so the limit needs no tuning
ATOL = 3e-5
W = 8  # the tiny window
LAYERS = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
YARN = {"rope_type": "yarn", "factor": 8.0, "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
        "attention_factor": 1.2}


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "lib", "reference_laguna.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_laguna", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def tiny(**kw) -> ModelConfig:
    base = dict(
        block="laguna", vocab_size=96, hidden=64, n_layers=5, n_heads=4, swa_n_heads=6, n_kv_heads=2, head_width=16,
        mlp_dim=96, max_seq=64, rope_theta=5e5, swa_rope_theta=1e4, partial_rotary_factor=0.5, rope_scaling=YARN,
        norm_eps=1e-6, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False, remat=False, sliding_window=W,
        layer_types=LAYERS, first_k_dense=1, n_routed_experts_total=16, n_routed_experts=16, first_expert=0,
        n_shared_experts=1, experts_per_token=3, moe_mlp_dim=24, routed_scaling_factor=2.5, router_score="softmax")
    base.update(kw)
    return ModelConfig(**base)


def _model_dict(cfg: ModelConfig) -> dict:
    """The configuration as the benchmark's reference reads it: `ModelConfig`'s field names, the
    rotary group a dict again (a configuration file's `model` holds one)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["rope_scaling"] = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    return out


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, lg.init_params(cfg, jax.random.PRNGKey(1))


# eager dispatch of the loops and scatters is what takes the time on the CPU: one program per shape
_PREFILL = jax.jit(lg.prefill, static_argnums=1)
_DECODE = jax.jit(lg.decode, static_argnums=1)
_EXPERTS = jax.jit(lambda p, x, valid, cfg: moe.routed_experts(p, x, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                                              first=cfg.first_expert, score="softmax"),
                   static_argnums=3)  # as `laguna._forward` calls it


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
    last, caches = _prefill(cfg, params, toks[:n_prompt], chunks, _dirty(lg.init_caches(cfg, 3, 64)), slot=1)
    out = [last]
    for at in range(n_prompt, len(toks)):
        logits, caches = _decode(cfg, params, toks[at], caches, 1, at)
        out.append(logits)
    return np.stack(out), caches


CHUNKS = {
    "whole": ((29, 32),),                                        # one chunk, four rings long, padded
    "chunks-longer-than-the-ring": ((16, 16), (8, 8), (5, 8)),   # two rings, one ring, a padded tail
    "chunks-shorter-than-the-ring": ((4, 4), (4, 4), (16, 16), (3, 4), (2, 2)),
    "under-one-ring": ((5, 8),),                                 # the ring has not wrapped when decoding starts
}


# -- the cached paths against the plain reference ------------------------------------------


@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_chunked_prefill_then_decode_past_a_wrap_of_the_ring_matches_the_benchmarks_reference(model, chunks):
    cfg, params = model
    n_prompt = sum(n for n, _ in CHUNKS[chunks])
    toks = _tokens(n_prompt + 2 * W + 3, seed=1)  # decoding wraps every ring twice over
    got, _ = _through_the_cache(cfg, params, toks, CHUNKS[chunks], n_prompt)
    want = _reference(params, cfg, toks)
    np.testing.assert_allclose(got, want[n_prompt - 1:], atol=ATOL)
    assert np.mean(np.argmax(want, axis=-1) == toks) < 0.2  # not all but an argmax at the input


def test_the_ring_after_a_chunk_holds_the_rows_a_row_at_a_time_decode_would_have_left(model):
    """Position p in row p mod window, whatever wrote it: a padded chunk longer than the ring, chunks
    shorter than it, or decode steps from the first token on."""
    cfg, params = model
    toks = _tokens(29, seed=2)
    _, by_chunk = _prefill(cfg, params, toks, CHUNKS["whole"], lg.init_caches(cfg, 3, 64), slot=1)
    _, by_short = _prefill(cfg, params, toks, CHUNKS["chunks-shorter-than-the-ring"], lg.init_caches(cfg, 3, 64), slot=1)
    _, by_row = _prefill(cfg, params, toks[:1], ((1, 4),), lg.init_caches(cfg, 3, 64), slot=1)
    for at in range(1, 29):
        _, by_row = _decode(cfg, params, toks[at], by_row, 1, at)
    for i, kind in enumerate(LAYERS):
        rows = 29 if kind == "full_attention" else W
        assert by_chunk[i][0].shape == (3, 64 if kind == "full_attention" else W, 2, 16)
        for a, b, c in zip(by_chunk[i], by_short[i], by_row[i]):
            np.testing.assert_allclose(np.asarray(a)[1, :rows], np.asarray(c)[1, :rows], atol=1e-5)
            np.testing.assert_allclose(np.asarray(b)[1, :rows], np.asarray(c)[1, :rows], atol=1e-5)


def test_padding_leaves_the_rings_and_the_other_slots_untouched(model):
    cfg, params = model
    caches = _dirty(lg.init_caches(cfg, 3, 64))
    before = [[np.asarray(a) for a in c] for c in caches]
    _, after = _prefill(cfg, params, _tokens(3, seed=4), ((3, 16),), caches, slot=1)  # 13 rows of padding, a ring and a half
    for i, kind in enumerate(LAYERS):
        for b, a in zip(before[i], after[i]):
            a = np.asarray(a)
            np.testing.assert_array_equal(a[0], b[0])
            np.testing.assert_array_equal(a[2], b[2])
            if kind == "sliding_attention":  # rows 0 to 2 written, the ring's other five as they were
                np.testing.assert_array_equal(a[1, 3:], b[1, 3:])
                assert not np.array_equal(a[1, :3], b[1, :3])


@pytest.mark.parametrize("program", ["decode", "multi-step"])
def test_a_gated_off_slot_keeps_its_slabs_and_rings_bit_for_bit(model, engine, program):
    """A slot in the middle of a chunked prefill is stepped over by every interleaved decode step."""
    cfg, params = model
    caches = _dirty(lg.init_caches(cfg, 3, 64))
    before = [[np.asarray(a) for a in c] for c in caches]
    last, lens = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 4, 30], jnp.int32)
    gate = jnp.asarray([True, False, True])
    if program == "decode":
        _, after, (experts, attn) = _DECODE(params, cfg, last, caches, lens, gate)
        steps = 1
    else:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=4))
        _, after, _, _, experts, attn = multi(params, None, jnp.zeros((3,), jnp.int32), last, caches, lens, gate,
                                                 jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
        steps = 4
    # two slots routed to 3 experts in each of 4 expert layers a step; the gated-off slot is routed nowhere
    n = len(lg.EXPERT_COUNTS)
    assert experts[0] == experts[1] == 2 * 3 * 4 * steps == int(experts[n:].sum())
    assert experts[3] == 4 * steps and 0 < experts[2] <= 2 * 3 * 4 * steps
    seen = sum(10 + j + 31 + j for j in range(steps))
    assert attn.tolist() == [0, 2 * seen, 0, 3 * 2 * W * steps, 0, 0]  # two full layers; three windows, both slots past them
    for b, a in zip(before, after):
        for x, y in zip(b, a):
            np.testing.assert_array_equal(x[1], np.asarray(y)[1])
            assert not np.array_equal(x[0], np.asarray(y)[0])


def test_the_chunks_key_block_loop_is_the_two_products_over_the_whole_slab():
    """At a size where both fit: the online softmax over the blocks up to the chunk's last row
    against `cached_attention_xla` over every row, at offsets inside a block, on an edge and past one."""
    key = jax.random.PRNGKey(7)
    T, S, Hkv, G, D = 64, 8, 2, 3, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (S, Hkv, G, D))
    ck, cv = (jax.random.normal(jax.random.fold_in(key, j), (T, Hkv, D)) for j in (1, 2))
    loop = jax.jit(lg.chunk_attention, static_argnums=(4, 5))
    for offset in (0, 5, 16, 40, 56):
        want = attention.cached_attention_xla(q[None], ck[None], cv[None], jnp.asarray([offset]), scale=0.25)[0]
        for kb in (8, 16):
            got = loop(q, ck, cv, jnp.int32(offset), kb, 0.25)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # rows past the chunk's last are not read: poison there changes nothing
    poisoned = ck.at[24:].set(jnp.nan)
    np.testing.assert_array_equal(np.asarray(loop(q, poisoned, cv.at[24:].set(jnp.nan), jnp.int32(16), 8, 0.25)),
                                  np.asarray(loop(q, ck, cv, jnp.int32(16), 8, 0.25)))


def test_the_decode_step_through_the_kernel_is_the_step_through_the_products(monkeypatch):
    """On the TPU a decode step's attention of both kinds is the kernel `cached_attn`, over the slabs up
    to each slot's length and over the rings told at most window - 1; here the same trace with the
    kernel interpreted, at heads of 128 (whole rows of lanes, as the cell's) with 3 and 5 queries a KV
    head, against the products: a ring not yet wrapped, one wrapped, and an idle slot."""
    cfg = tiny(head_width=128, n_heads=6, swa_n_heads=10, sliding_window=16, n_layers=3, layer_types=LAYERS[:3], max_seq=32)
    params = lg.init_params(cfg, jax.random.PRNGKey(2))
    caches = lg.init_caches(cfg, 3, 32)
    toks = _tokens(27, seed=5)
    _, caches = _prefill(cfg, params, toks, ((27, 32),), caches, slot=1)
    _, caches = _prefill(cfg, params, toks[:5], ((5, 8),), caches, slot=2)
    last, lens, gate = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([9, 27, 5], jnp.int32), jnp.asarray([False, True, True])
    want, want_caches, counted = lg.decode(params, cfg, last, caches, lens, gate)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    kernel, calls = attention.cached_attention, []

    def interpreted(q, ck, cv, seen, scale, new_k, new_v, write_at, gate):
        calls.append((ck.shape[1], q.shape[3], np.asarray(seen).tolist(), np.asarray(write_at).tolist()))
        assert np.asarray(gate).tolist() == [False, True, True] and new_k.shape == (3, 1) + ck.shape[2:]
        return kernel(q, ck, cv, seen, scale=scale, new_k=new_k, new_v=new_v, write_at=write_at, gate=gate, interpret=True)

    monkeypatch.setattr(attention, "cached_attention", interpreted)
    got, got_caches, counted_kernel = lg.decode(params, cfg, last, caches, lens, gate)
    # a ring is told at most window - 1 and writes at the position mod the window; the kernel writes the row itself
    assert calls == [(32, 3, [0, 27, 5], [9, 27, 5]), (16, 5, [0, 15, 5], [9, 11, 5]), (16, 5, [0, 15, 5], [9, 11, 5])]
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], atol=ATOL)
    for a, b in zip(got_caches, want_caches):  # a later layer's rows are a function of the layers before it
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=ATOL)
    for a, b in zip(counted, counted_kernel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the two rotary tables ---------------------------------------------------------------------


def test_the_full_layers_rotary_is_yarn_over_half_a_head_times_the_attention_factor():
    """The direct formula at the published sizes: r = 64 of 128, low 9, high 18 of 32 pairs, factor 128
    from 8192, cos and sin times 1.4852; the 64 values that do not rotate pass through."""
    published = {"rope_type": "yarn", "factor": 128, "original_max_position_embeddings": 8192, "beta_slow": 1,
                 "beta_fast": 32, "attention_factor": 1.4852030263919618}
    cfg = tiny(head_width=128, hidden=256, rope_scaling=published)
    r, theta, inv, factor = lg.rotary(cfg, True)
    assert (r, theta, factor) == (64, 5e5, 1.4852030263919618) and abs(0.1 * math.log(128) + 1 - factor) < 1e-12
    d = lambda n: 64 * math.log(8192 / (2 * math.pi * n)) / (2 * math.log(5e5))  # noqa: E731
    assert (math.floor(d(32)), math.ceil(d(1))) == (9, 18)
    f = 5e5 ** (-np.arange(32) / 32.0)
    t = np.clip((np.arange(32) - 9) / 9.0, 0, 1)
    np.testing.assert_allclose(inv, f * (1 - t) + f / 128 * t, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.rotary_table(_model_dict(cfg), True)[1]), inv, rtol=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 3, 128))
    pos = jnp.asarray([[0, 1, 700, 9000, 31000]])
    got = np.asarray(lg._rotated(x, pos, lg.rotary(cfg, True)))
    ang = np.asarray(pos, np.float32)[0][:, None] * np.asarray(inv, np.float32)[None, :]  # float32 angles, as the program's
    cos, sin = (np.cos(ang) * factor)[:, None, :], (np.sin(ang) * factor)[:, None, :]
    x1, x2 = np.asarray(x)[0, ..., :32], np.asarray(x)[0, ..., 32:64]
    np.testing.assert_allclose(got[0, ..., :32], x1 * cos - x2 * sin, atol=2e-4)
    np.testing.assert_allclose(got[0, ..., 32:64], x2 * cos + x1 * sin, atol=2e-4)
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    # the sliding layers: every value of a head, theta 1e4, plain
    assert lg.rotary(cfg, False) == (128, 1e4, None, 1.0)
    np.testing.assert_array_equal(np.asarray(lg._rotated(x, pos, lg.rotary(cfg, False))), np.asarray(_rope(x, pos, 1e4)))


def test_at_factor_1_and_a_rotating_share_of_1_the_rotary_is_the_plain_one():
    plain = tiny(partial_rotary_factor=1.0, rope_scaling=None)
    one = tiny(partial_rotary_factor=1.0, rope_scaling=dict(YARN, factor=1.0, attention_factor=1.0))
    x, pos = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 4, 16)), jnp.arange(12).reshape(2, 6) * 7
    want = np.asarray(_rope(x, pos, 5e5))
    np.testing.assert_array_equal(np.asarray(lg._rotated(x, pos, lg.rotary(plain, True))), want)
    np.testing.assert_allclose(np.asarray(lg._rotated(x, pos, lg.rotary(one, True))), want, atol=1e-5)
    np.testing.assert_allclose(yarn_inv_freq(16, 5e5, dict(YARN, factor=1.0)), 5e5 ** (-np.arange(8) / 8.0), rtol=1e-6)
    assert ModelConfig(hidden=64, n_heads=8).head_dim == 8 and tiny().head_dim == 16  # `hidden // n_heads` where no width is stated


# -- the controls --------------------------------------------------------------------------------


def test_a_window_of_twice_the_size_and_operands_under_bfloat16_both_fail_the_comparison(model):
    """The reference run with a window of 16 for 8 (a ring that kept or showed the wrong rows reads like
    this), and with every matrix product's operands rounded to float8: both lie thousands of
    tolerances from the cached paths; bfloat16 operands, the stated precision, lie between."""
    cfg, params = model
    toks = _tokens(40, seed=5)
    got, _ = _through_the_cache(cfg, params, toks, CHUNKS["chunks-longer-than-the-ring"], 29)
    want = _reference(params, cfg, toks)[28:]
    np.testing.assert_allclose(got, want, atol=ATOL)
    rms = lambda other: float(np.sqrt(np.mean((other[28:] - want) ** 2)))  # noqa: E731

    def fp8(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)) / 448.0, 1e-30)
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    wide = rms(_reference(params, cfg, toks, window=2 * W))
    low = rms(_reference(params, cfg, toks, operand=fp8))
    stated = rms(_reference(params, cfg, toks, operand=lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)))
    assert wide > 1000 * ATOL and low > 1000 * ATOL and low > 2 * stated > 0
    # a window of 16 over 8 positions is no other function: the control bites only past the window
    np.testing.assert_allclose(_reference(params, cfg, toks[:W], window=2 * W), _reference(params, cfg, toks[:W]), atol=ATOL)


def test_the_gate_spreads_and_makes_a_difference_the_comparison_sees(model):
    cfg, params = model
    toks = _tokens(24, seed=6)
    h = jax.random.normal(jax.random.PRNGKey(2), (200, cfg.hidden))
    g = np.asarray(jax.nn.sigmoid(h @ params["layer_1"]["attn"]["g"]["kernel"]))
    assert params["layer_1"]["attn"]["g"]["kernel"].shape == (64, 6) and params["layer_0"]["attn"]["g"]["kernel"].shape == (64, 4)
    assert np.mean((g < 0.3) | (g > 0.7)) > 0.3  # logits of unit variance: not every gate is a half
    shut = jax.tree_util.tree_map_with_path(
        lambda path, v: v * 0 if any(getattr(k, "key", None) == "g" for k in path) else v, params)
    assert np.sqrt(np.mean((_reference(shut, cfg, toks) - _reference(params, cfg, toks)) ** 2)) > 1000 * ATOL


# -- the expert layer, its router and its shares -------------------------------------------------


def test_the_router_is_a_softmax_over_every_expert_the_largest_renormalised_to_the_scaling(model):
    cfg, params = model
    h = jax.random.normal(jax.random.PRNGKey(6), (11, cfg.hidden))
    kernel = params["layer_1"]["mlp"]["router"]["kernel"]
    assert set(params["layer_1"]["mlp"]["router"]) == {"kernel"} and kernel.shape == (64, 16)
    ids, weights = moe.softmax_routing(h, kernel, 3, 2.5)
    logits = np.asarray(jnp.dot(h, kernel, precision="highest"), np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), order)
    chosen = np.take_along_axis(p, order, -1)
    np.testing.assert_allclose(np.asarray(weights), 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    # with the chosen renormalised: a softmax over the largest logits alone
    top = np.take_along_axis(logits, order, -1)
    np.testing.assert_allclose(np.asarray(weights), 2.5 * np.exp(top) / np.exp(top).sum(-1, keepdims=True), rtol=1e-5)
    r_ids, r_weights = reference.routing(h, kernel, 3, 2.5)
    np.testing.assert_array_equal(np.asarray(r_ids), order)
    np.testing.assert_allclose(np.asarray(r_weights), np.asarray(weights), rtol=1e-5)
    assert np.asarray(weights).max() / np.asarray(weights).min() > 1.5  # logits of unit variance: unequal weights


def test_routed_experts_routes_by_sigmoid_unless_told_and_the_block_asks_for_the_softmax(model):
    cfg, params = model
    p = params["layer_2"]["mlp"]
    x, valid = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.hidden)), jnp.ones((2, 9), bool)
    soft, _ = _EXPERTS(p, x, valid, cfg)
    named, _ = moe.routed_experts(p, x, valid, 3, 2.5, score="sigmoid")
    default, _ = moe.routed_experts(p, x, valid, 3, 2.5)
    np.testing.assert_array_equal(np.asarray(named), np.asarray(default))
    text = lambda **kw: jax.jit(lambda a: moe.routed_experts(p, a, valid, 3, 2.5, **kw)).lower(x).as_text()  # noqa: E731
    assert text() == text(score="sigmoid") != text(score="softmax")  # every older caller's program is the program it was
    assert float(jnp.max(jnp.abs(soft - default))) > 1e-3
    with pytest.raises(ValueError, match="routes by a softmax"):
        lg.param_shapes(tiny(router_score="sigmoid"))
    with pytest.raises(ValueError, match="layers of"):
        lg.param_shapes(tiny(layer_types=("full_attention", "conv", "sliding_attention", "sliding_attention", "full_attention")))


def test_the_four_shares_of_an_expert_parallel_layer_add_up_to_the_uncut_layer(model):
    """The guide's share test at the deployment's four ways (experts 0-3, 4-7, 8-11, 12-15 of 16 here
    for 0-63, 64-127, 128-191, 192-255 of 256): the routed parts the shares give, with the shared
    expert (which every chip computes alike) counted once, are the whole layer's output; and one
    share's forward pass is the reference's told to sum that share."""
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
    # the uncut reference's layer: the four shares' routed parts and the shared expert once
    m = jax.random.normal(jax.random.PRNGKey(11), (13, cfg.hidden))
    md = _model_dict(cfg)
    with jax.default_matmul_precision("highest"):
        uncut = reference._experts(p, m, md, lambda a: a)
        only_shared = moe.swiglu(p["shared"], m)
        routed = sum(reference._experts(p, m, md, lambda a: a, held=(first, 4)) - only_shared for first in range(0, 16, 4))
    np.testing.assert_allclose(np.asarray(routed + only_shared), np.asarray(uncut), atol=1e-5)
    # and the model: one share's cached paths are the reference given that share's tree
    share = dataclasses.replace(cfg, n_routed_experts=4, first_expert=8)
    toks = _tokens(30, seed=9)
    sliced = jax.tree_util.tree_map_with_path(
        lambda path, v: v[8:12] if any(getattr(k, "key", None) == "experts" for k in path) else v, params)
    got, _ = _through_the_cache(share, sliced, toks, ((16, 16), (4, 4)), 20)
    np.testing.assert_allclose(got, _reference(sliced, share, toks)[19:], atol=ATOL)
    np.testing.assert_allclose(_reference(sliced, share, toks), _reference(params, cfg, toks, held=(8, 4)), atol=ATOL)


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


_SCORE = jax.jit(lambda p, ids, cfg: reference.forward(p, _model_dict(cfg), ids, q_block=8), static_argnums=2)


def _greedy_reference(cfg, params, prompt, n):
    ids = list(prompt) + [0] * n  # one shape: a causal model's logits do not see what follows
    for j in range(len(prompt), len(ids)):
        with jax.default_matmul_precision("highest"):
            ids[j] = int(np.argmax(np.asarray(_SCORE(params, jnp.asarray(ids, jnp.int32), cfg))[j - 1]))
    return ids[len(prompt):]


@pytest.fixture(scope="module")
def engine(model):
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine

    cfg, params = model
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    eng = DecodeEngine(cfg, params, num_slots=3, max_seq=64, multi_step=4, token_budget=20)
    try:
        yield eng
    finally:
        eng.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "beside-another"])
def test_the_engine_generates_the_plain_references_greedy_ids(engine, model, beside):
    """Chunked by a 20-token budget (16-token chunks, two rings long, and shorter tails), then the
    multi-step decode program past a wrap of the rings, with another request prefilling and decoding
    beside it in the second case."""
    cfg, params = model
    prompt = [int(t) for t in _tokens(27, seed=11)]
    want = _greedy_reference(cfg, params, prompt, 12)
    if beside:
        other = threading.Thread(target=_generate, args=(engine, [int(t) for t in _tokens(19, seed=12)]),
                                 kwargs=dict(max_tokens=8))
        other.start()
    got = _generate(engine, prompt, max_tokens=12, temperature=0.0)
    if beside:
        other.join()
    assert got == want
    assert engine._prefix_cache is None


def test_slots_taken_over_from_longer_requests_under_load_give_the_plain_references_ids(engine, model):
    """Seven requests on three slots, sent together: every later one waits, then takes a slot whose
    slabs and rings another (often longer) request left behind, and prefills in chunks beside slots
    that decode. Each reply is the plain reference's, as if it ran alone."""
    cfg, params = model
    prompts = [[int(t) for t in _tokens(n, seed=20 + n)] for n in (44, 5, 33, 21, 47, 12, 27)]
    want = [_greedy_reference(cfg, params, p, 9) for p in prompts]
    got = [None] * len(prompts)

    def one(i):
        got[i] = _generate(engine, prompts[i], max_tokens=9)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def test_scheduler_stats_count_the_experts_pairs_and_the_rows_a_step_could_see(engine, model):
    cfg, _ = model
    engine.scheduler_stats()
    _generate(engine, [int(t) for t in _tokens(9, seed=13)], max_tokens=3)
    st = engine.scheduler_stats()
    ex, at = st["experts"], st["attn"]
    # 9 prompt tokens and 2 decoded tokens pass 4 expert layers with 3 experts a token (the third
    # token is sampled from the second's logits and never fed), all 16 experts held here
    assert ex["window"]["pairs_routed"] == (9 + 2) * 4 * 3 == ex["window"]["pairs_held"]
    assert ex["window"]["decode_layer_steps"] == 2 * 4 and 2 * 4 <= ex["window"]["decode_experts_hit"] <= 2 * 4 * 3
    assert ex["held"] == ex["of"] == 16 and ex["pairs_routed"] >= ex["window"]["pairs_routed"]
    # the two decode steps saw 10 and 11 rows in each of 2 full layers, and a full ring in each of 3
    # sliding ones; the one chunk's two full layers scored 9 x 10 / 2 pairs each
    assert at["window"] == {"full_rows_visible": 2 * (10 + 11), "window_rows_visible": 3 * 2 * W, "chunk_pairs_full": 2 * 45}
    assert at["slab_bytes_per_token"] == 2 * 2 * 2 * 16 * 4 and at["ring_bytes_per_slot"] == 3 * W * 2 * 2 * 16 * 4
    assert st["model"]["block"] == "laguna"


def test_the_programs_name_each_layer_its_cache_and_its_gate_as_the_readers_look_for_them(engine):
    """`layer_<i>` round each layer, `kv_attn` (the dense block's name) round a layer's cache write and
    its attention in layers of both kinds, `gate` beside it under `attn`, `router`, `experts`,
    `shared_expert` as `routed_experts` names them: what `lib/kinds_trace.py` and the readers that
    were there find in a trace of this block."""
    import re

    from tests.test_trace_names import MODEL_SCOPES, _abstract, _parts, _sampler_args

    _generate(engine, [int(t) for t in _tokens(27, seed=14)], max_tokens=6)  # builds the programs a request of its size runs
    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    assert len(programs) >= 3  # the single step, a multi-step size, a prefill bucket or more
    for prog, args in programs:
        text = prog.lower(*_abstract(args)).as_text(dialect="hlo", debug_info=True)
        paths = [_parts(name) for name in re.findall(r'op_name="([^"]+)"', text)]
        scopes = {part for path in paths for part in path}
        assert set(MODEL_SCOPES) | {"kv_attn", "gate", "router", "experts", "shared_expert"} <= scopes
        assert {f"layer_{i}" for i in range(5)} <= scopes and not {"indexer", "select", "window", "latent"} & scopes
        for i in range(5):  # a layer of either kind writes and reads its cache under `layer_<i>/attn/kv_attn`
            assert [path for path in paths if f"layer_{i}" in path and "kv_attn" in path and path[path.index("kv_attn") - 1] == "attn"], i
        assert [path for path in paths if "gate" in path and path[path.index("gate") - 1] == "attn"]  # beside `kv_attn`, under `attn`


def test_the_rows_counts_do_not_wrap_where_a_count_of_rows_would(model):
    """A step of 24 slots of 32768 rows in two full layers adds 1536 and 0 to a count kept as (1024s,
    remainder): an int32 of rows would wrap in 1400 such steps, a window's worth at the cell's sizes."""
    cfg, _ = model
    total = (np.zeros((4 + 16,), np.int64), np.asarray([3 * 2**21, 1000, 5 * 2**21, 7, 9 * 2**21, 1], np.int64))
    out = lg.report(cfg, total, total)["attn"]
    assert out["full_rows_visible"] == 3 * 2**31 + 1000 and out["window_rows_visible"] == 5 * 2**31 + 7
    assert out["chunk_pairs_full"] == 9 * 2**31 + 1


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
        "pd-prefill-server": lambda: PrefillServer(LLMConfig(model_id="tiny-laguna", model_config=cfg)),
        "pd-decode-server": lambda: DecodeServer(LLMConfig(model_id="tiny-laguna", model_config=cfg)),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
    }


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "train-step"])
def test_what_the_block_cannot_do_yet_is_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=r"block 'laguna'"):
        _refusals()[what]()


def test_load_model_builds_the_blocks_tree_with_two_shapes_of_layer():
    from ray_tpu.llm import LLMConfig, load_model

    cfg = tiny(param_dtype=jnp.bfloat16, n_routed_experts=4, first_expert=8)
    got_cfg, params = load_model(LLMConfig(model_id="tiny-laguna", model_config=cfg, seed=3))
    leaves = jax.tree_util.tree_leaves(params)
    assert got_cfg.block == "laguna" and all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == lg.num_params(cfg)
    assert params["layer_1"]["mlp"]["experts"]["gate"].shape == (4, 64, 24)
    assert params["layer_1"]["mlp"]["router"]["kernel"].shape == (64, 16)
    assert params["layer_0"]["attn"]["q"]["kernel"].shape == (64, 4 * 16) and params["layer_0"]["attn"]["o"]["kernel"].shape == (64, 64)
    assert params["layer_1"]["attn"]["q"]["kernel"].shape == (64, 6 * 16) and params["layer_1"]["attn"]["o"]["kernel"].shape == (96, 64)
    assert params["layer_1"]["attn"]["k"]["kernel"].shape == params["layer_0"]["attn"]["k"]["kernel"].shape == (64, 2 * 16)
    assert set(params["layer_0"]["mlp"]) == {"gate", "up", "down"} and "lm_head" in params
    assert abs(float(jnp.std(params["embedding"].astype(jnp.float32))) - 0.02) < 0.002
    assert models.names_its_layers(cfg) and lg.LAYER_TYPES == ("full_attention", "sliding_attention")
    with pytest.raises(ValueError, match="one of layer_types per layer"):
        tiny(layer_types=LAYERS[:3])
