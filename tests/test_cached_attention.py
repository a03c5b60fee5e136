"""The length-aware cached-attention kernel (`ops/attention.py:cached_attention`, the TPU's path
of `models/llama.py:_cached_products`) in interpret mode against the XLA products it replaces
there, which stay the plain form off the TPU: every visible row attended, none past it read
into a result, at the slab shapes the engine's blocks keep."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.ops import attention

T = 1024  # rows a slot: eight blocks of the kernel where a cache row is 8 rows of lanes, four where it is 4


# (head_dim, G, the slab's axes after the rows): the dense block's [Hkv, 128]; heads of 64 as the
# hybrid blocks keep them, two to a row of 128 lanes, and as a dense model keeps them; and cache rows
# that no tile of 16 flattened rows holds a whole number of: six heads of 128, six of 64 two to a row
SLABS = {
    "d128g2": (128, 2, (8, 128)),
    "d64g4_two_heads_a_row": (64, 4, (4, 128)),
    "d64g4_a_head_a_row": (64, 4, (8, 64)),
    "d128g4": (128, 4, (8, 128)),
    "d128g2_six_rows": (128, 2, (6, 128)),
    "d64g4_three_rows": (64, 4, (3, 128)),
}
# a cache row of three or six flattened rows divides no block size, so those slabs are one block of T rows
BLOCKED = sorted(name for name, (_, _, minor) in SLABS.items() if attention._cached_block_rows(T, minor[0]) < T)


def _case(slab, S, lens, *, scale=None, slab_dtype=jnp.float32, T=T):
    D, G, minor = SLABS[slab]
    Hkv = math.prod(minor) // D
    B = len(lens)
    keys = jax.random.split(jax.random.PRNGKey(D + G + S), 3)
    q = jax.random.normal(keys[0], (B, S, Hkv, G, D), jnp.float32)
    cache_k = jax.random.normal(keys[1], (B, T) + minor, jnp.float32).astype(slab_dtype)
    cache_v = jax.random.normal(keys[2], (B, T) + minor, jnp.float32).astype(slab_dtype)
    scale = 1.0 / D ** 0.5 if scale is None else scale
    return q, cache_k, cache_v, jnp.asarray(lens, jnp.int32), scale


def _both(q, cache_k, cache_v, lens, scale):
    got, same_k, same_v = attention.cached_attention(q, cache_k, cache_v, lens, scale=scale, interpret=True)
    want = attention.cached_attention_xla(q, cache_k, cache_v, lens, scale=scale)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    # handed no new rows it writes none: the slabs as they came
    assert np.array_equal(same_k, cache_k, equal_nan=True) and np.array_equal(same_v, cache_v, equal_nan=True)
    return np.asarray(got), np.asarray(want)


def _edges(slab, S):
    """Lengths 0, 1, a block's edge on either side, and the last that fits."""
    minor = SLABS[slab][2]
    block = attention._cached_block_rows(T, minor[0])
    assert T % block == 0 and block < T, block  # more than one block, or the edges test nothing
    return [0, 1, block - S, block - S + 1, block, T - S]


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
@pytest.mark.parametrize("slab", BLOCKED)
def test_kernel_equals_the_products_at_every_edge_of_a_block(slab, S):
    """One slot a length: 0 (the new row alone), 1, the last row of a block, the first of the
    next, and `T - S`. The kernel reads no block past a slot's last visible row, so a wrong
    bound drops a row or lets a hidden one in, and either moves the result."""
    got, want = _both(*_case(slab, S, _edges(slab, S)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slab", ["d128g2", "d64g4_two_heads_a_row"])
def test_kernel_reads_no_block_past_a_slots_last_visible_row(slab):
    """The blocks wholly past a slot's last visible row hold NaN: a result that read one, even
    at a weight of zero, is NaN (as the products over the whole slab are, which therefore get
    zeros there). Inside the last live block the hidden rows are read and masked, like theirs."""
    minor = SLABS[slab][2]
    block = attention._cached_block_rows(T, minor[0])
    q, cache_k, cache_v, lens, scale = _case(slab, 1, [0, 5, block - 1, block, 2 * block + 3])
    dead = (jnp.arange(T)[None, :] // block > lens[:, None] // block).reshape((len(lens), T) + (1,) * len(minor))
    got, _, _ = attention.cached_attention(q, jnp.where(dead, jnp.nan, cache_k), jnp.where(dead, jnp.nan, cache_v),
                                           lens, scale=scale, interpret=True)
    want = attention.cached_attention_xla(q, jnp.where(dead, 0.0, cache_k), jnp.where(dead, 0.0, cache_v),
                                          lens, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slab", ["d128g2", "d64g4_two_heads_a_row"])
def test_kernel_takes_a_chunk_of_a_one_slot_view(slab):
    """A 128-row chunk of one slot at an offset inside a block: query i sees rows up to offset + i."""
    got, want = _both(*_case(slab, 128, [400]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slab", ["d128g2", "d64g4_two_heads_a_row"])
def test_kernel_takes_the_scale_it_is_handed_and_a_bfloat16_slab(slab):
    """`granite_hybrid`'s score scale is its own, and the engine's slabs are bfloat16 under
    queries of the model's type: the slab is cast to the queries' type, as the products cast it."""
    got, want = _both(*_case(slab, 1, [7, 333], scale=0.015625, slab_dtype=jnp.bfloat16))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    other, _ = _both(*_case(slab, 1, [7, 333], slab_dtype=jnp.bfloat16))
    assert np.abs(other - got).max() > 1e-3  # the scale reached the scores


TAKEN = [name for name in BLOCKED if attention.cached_attention_takes(SLABS[name][2][-1])]


def _written(slab, S, lens, gate, *, write_at=None, told=None, slab_dtype=jnp.float32, run=None, T=T):
    """The kernel handed the step's rows against the parent's path, XLA's gated write and then the
    kernel over the written slabs: the same `out` and the same slabs to the last bit; and near the
    products over them. `run`: another way to the kernel taking (q, k, v, new_k, new_v, lens, gate)."""
    q, cache_k, cache_v, lens, scale = _case(slab, S, lens, slab_dtype=slab_dtype, T=T)
    keys = jax.random.split(jax.random.PRNGKey(S + len(lens)), 2)
    new_k, new_v = (jax.random.normal(k, cache_k.shape[:1] + (S,) + cache_k.shape[2:]).astype(slab_dtype) for k in keys)
    at = lens if write_at is None else jnp.asarray(write_at, jnp.int32)
    told = lens if told is None else jnp.asarray(told, jnp.int32)
    gate = jnp.asarray(gate)
    want_k, want_v = attention.put_gated(cache_k, new_k, at, gate), attention.put_gated(cache_v, new_v, at, gate)
    want, _, _ = attention.cached_attention(q, want_k, want_v, told, scale=scale, interpret=True)
    if run is None:
        got = attention.cached_attention(q, cache_k, cache_v, told, scale=scale, new_k=new_k, new_v=new_v, write_at=at,
                                         gate=gate, interpret=True)
    else:
        got = run(q, cache_k, cache_v, new_k, new_v, told, gate, scale)
    for g, w, what in zip(got, (want, want_k, want_v), ("out", "cache_k", "cache_v")):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        if run is None or what != "out":  # another split of the heads is other products: near, not equal
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=what)
    products = attention.cached_attention_xla(q, want_k, want_v, told, scale=scale)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(products), rtol=2e-5, atol=2e-5)
    off = ~np.asarray(gate)
    np.testing.assert_array_equal(np.asarray(got[1])[off], np.asarray(cache_k)[off])  # a gated-off slot: untouched
    np.testing.assert_array_equal(np.asarray(got[2])[off], np.asarray(cache_v)[off])
    return np.asarray(got[1]), np.asarray(cache_k)


# what a slot's gate is over the lengths of `_edges` and the last row of a block: on everywhere, off
# in the slot at a block's edge, off in every slot
GATES = {"every_slot_writes": lambda n: [True] * n, "gate_off_in_one_slot": lambda n: [i != 3 for i in range(n)],
         "gate_off_in_every_slot": lambda n: [False] * n}


@pytest.mark.parametrize("gates", sorted(GATES))
@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
@pytest.mark.parametrize("slab", TAKEN)
def test_kernel_writes_the_steps_rows_itself_at_every_edge_of_a_block(slab, S, gates):
    """A slot a write row: 0, 1, the rows that end a block, straddle its edge and start the next,
    the last row of a block, and `T - S`; the gate on everywhere, off in one slot and off in all.
    The slabs come back as XLA's gated write left them and `out` as the kernel's over those, bit
    for bit: the step's queries see the step's rows, and a gated-off slot's slab is untouched."""
    block = attention._cached_block_rows(T, SLABS[slab][2][0])
    lens = _edges(slab, S) + [block - 1]
    after, before = _written(slab, S, lens, GATES[gates](len(lens)))
    assert (after != before).any() == (gates != "gate_off_in_every_slot")


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
@pytest.mark.parametrize("slab", TAKEN)
def test_kernel_clamps_a_write_past_the_caches_end_as_the_gated_write_does(slab, S):
    """Slots at `T - S` (the last write that fits), one row past it, at the last row and past the
    cache: XLA's `dynamic_update_slice` lands those at `T - S`, and so does the kernel, which
    clamps the row before its copy (a copy out of bounds is not clamped for it); the last slot's
    gate is off there, and its last rows are untouched."""
    _written(slab, S, [T - S, T - S + 1, T - 1, T + 5, T + 5], [True, True, True, True, False])


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
@pytest.mark.parametrize("slab", TAKEN)
def test_kernel_writes_a_bfloat16_slab(slab, S):
    """The engine's slabs: bfloat16 rows under float32 queries, two rows to a 32-bit word."""
    block = attention._cached_block_rows(T, SLABS[slab][2][0])
    _written(slab, S, [0, 7, block - 1, block, 333, T - S, T + 5], [True, True, True, True, False, True, True],
             slab_dtype=jnp.bfloat16)


@pytest.mark.parametrize("slab", TAKEN)
def test_kernel_writes_a_ring_at_its_own_row_under_a_length_told_shorter(slab):
    """`laguna`'s window layers: a ring of W rows written at `lens % W` and told `min(lens, W - 1)`,
    so the write row is its own operand: before the wrap the two agree; after it the row lies
    anywhere in the ring, in the first block, at an edge and in the last, under a length that
    reads every block. An idle slot is told 0 and writes nothing."""
    W = T
    block = attention._cached_block_rows(W, SLABS[slab][2][0])
    lens = np.asarray([5, W - 1, W, W + 5, 3 * W + block - 1, 2 * W + block, 7 * W - 1, 4 * W + 77])
    gate = np.asarray([True] * 7 + [False])
    _written(slab, 1, np.where(gate, np.minimum(lens, W - 1), 0), gate, write_at=lens % W)


@pytest.mark.parametrize("slab_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
@pytest.mark.parametrize("slab", sorted(set(SLABS) - set(BLOCKED)))
def test_kernel_writes_a_cache_row_that_starts_anywhere_in_a_tile(slab, S, slab_dtype):
    """A cache row of six flattened rows starts at any even row of a tile of 16 and one of three at
    any row, so the window of whole tiles that carries a write has to hold `16 - gcd(groups, 16)`
    rows before it (a window sized for two or four rows a cache row, which divide 16, dropped the
    rows past it and nothing said so): every start in a tile, the cache's end and past it."""
    rows = 128  # one block either way, so a short slab tests the same
    lens = list(range(17)) + [77, rows - S - 1, rows - S, rows + 5, 40]
    _written(slab, S, lens, [True] * (len(lens) - 1) + [False], slab_dtype=slab_dtype, T=rows)


@pytest.mark.parametrize("S,rows", [(1, 10), (4, 4)], ids=["rows_no_whole_tiles", "a_ring_under_a_window"])
def test_a_slab_that_holds_no_window_is_written_before_the_kernel(S, rows):
    """Forty flattened rows are no whole tiles, and sixteen are fewer than the 32 a window for four
    cache rows of four takes: no window of whole tiles lies in such a slab, so `put_gated` writes it
    and the kernel reads it, with the same results."""
    assert attention._write_window(0, S, 4, rows) is None
    _written("d64g4_two_heads_a_row", S, [0, 3, rows - S, rows + 2, 1], [True, True, True, True, False], T=rows)


def test_kernel_lands_rows_written_past_the_blocks_it_reads():
    """The two numbers a slot are independent: a write row in a block past the last the length
    makes live is written all the same, before the kernel's end."""
    after, before = _written("d128g2", 1, [3, 3], [True, True], write_at=[900, 5])
    assert (after[0, 900] != before[0, 900]).all() and (after[1, 5] != before[1, 5]).all()


def _layer(cfg, key):
    M, H, Hkv, D = cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 4)
    return {name: {"kernel": jax.random.normal(k, shape) / M ** 0.5} for k, (name, shape) in zip(
        keys, [("q", (M, H, D)), ("k", (M, Hkv, D)), ("v", (M, Hkv, D)), ("o", (H, D, M))])}


@pytest.mark.parametrize("gate_off", [False, True], ids=["every_slot_writes", "gate_off_in_one_slot"])
@pytest.mark.parametrize("packed", [False, True], ids=["a_head_a_row", "two_heads_a_row"])
def test_attn_cached_through_the_kernel_is_attn_cached_through_the_products(monkeypatch, packed, gate_off):
    """`_attn_cached` as the TPU runs a decode or verify program (gated: the kernel, here
    interpreted) against the same layer on the products, heads of 64 over both slab shapes (a
    head a row, and `kv_slab_shape`'s): the output, and the slabs written alike, a gated-off
    slot's rows kept; the two shapes hold the same elements."""
    cfg = ModelConfig(hidden=256, n_heads=4, n_kv_heads=2, dtype=jnp.float32, rope_theta=10000.0)
    assert cfg.head_dim == 64
    B, S, rows = 3, 2, 64
    assert llama.kv_slab_shape(cfg, B, rows) == (B, rows, 1, 128)
    assert llama.init_caches(cfg, B, rows)[0][0].shape == (B, rows, 2, 64)
    shape = llama.kv_slab_shape(cfg, B, rows) if packed else (B, rows, 2, 64)
    keys = jax.random.split(jax.random.PRNGKey(2 * packed + gate_off), 4)
    layer, x = _layer(cfg, keys[0]), jax.random.normal(keys[1], (B, S, cfg.hidden))
    cache_k, cache_v = jax.random.normal(keys[2], shape), jax.random.normal(keys[3], shape)
    lens = jnp.asarray([0, 9, rows - S], jnp.int32)
    gate = jnp.asarray([True, not gate_off, True])
    args = (layer, x, lens[:, None] + jnp.arange(S)[None], cache_k, cache_v, lens, cfg)
    want = llama._attn_cached(*args, write_gate=gate)

    calls = []
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "cached_attention",
                        lambda *a, kernel=attention.cached_attention, **kw: (calls.append(1), kernel(*a, **{**kw, "interpret": True}))[1])
    got = llama._attn_cached(*args, write_gate=gate)
    assert len(calls) == int(packed)  # a head a row of 64 lanes is not row-major on the chip: the products
    for g, w, what in zip(got, want, ("out", "cache_k", "cache_v")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5, err_msg=what)
    if gate_off:
        np.testing.assert_array_equal(np.asarray(got[1][1]), np.asarray(cache_k[1]))
    if packed:  # the other shape of the same slab gives the same layer
        flat = (layer, x, args[2], cache_k.reshape(B, rows, 2, 64), cache_v.reshape(B, rows, 2, 64), lens, cfg)
        again = llama._attn_cached(*flat, write_gate=gate)
        np.testing.assert_allclose(np.asarray(again[0]), np.asarray(got[0]), rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(again[1]).reshape(shape), np.asarray(got[1]))


# (query rows, slots, gated): a decode step and a verify block of every slot; the draft's own step,
# the smallest prefill bucket at InternLM2-1.8B's 16 heads and a chunk
@pytest.mark.parametrize("S,B,gated", [(1, 3, True), (5, 3, True), (1, 1, False), (16, 1, False), (128, 1, False)],
                         ids=["decode", "verify_k4", "draft_step", "prefill_b16", "prefill_b128"])
def test_the_gated_programs_take_the_kernel_and_a_one_slot_view_never_does(monkeypatch, S, B, gated):
    """Routed by the caller: the programs that step every slot at once gate their writes and,
    on the TPU, read the slab through the kernel; a one-slot view (no gate: a prefill chunk
    of any bucket, a detached prefill, the draft's steps) keeps the products, whatever its
    shapes. Those programs are traced outside the TP engine's mesh, where a kernel cannot be
    lowered (`_engine.py:_traced_on` wraps decode and verify only). Either way the layer's output
    is the products'."""
    cfg = ModelConfig(hidden=2048, n_heads=16, n_kv_heads=8, dtype=jnp.float32, rope_theta=1e6)
    assert cfg.head_dim == 128 and attention.cached_attention_takes(128) and attention.cached_attention_takes(256)
    assert not attention.cached_attention_takes(64)
    rows = 256
    keys = jax.random.split(jax.random.PRNGKey(S), 4)
    layer, x = _layer(cfg, keys[0]), jax.random.normal(keys[1], (B, S, cfg.hidden))
    shape = (B, rows, cfg.n_kv_heads, cfg.head_dim)
    lens = jnp.asarray([0, 9, rows - S][:B], jnp.int32)
    args = (layer, x, lens[:, None] + jnp.arange(S)[None], jax.random.normal(keys[2], shape),
            jax.random.normal(keys[3], shape), lens, cfg)
    gate = jnp.ones((B,), bool) if gated else None
    want = llama._attn_cached(*args, write_gate=gate)

    calls = []
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "cached_attention",
                        lambda *a, kernel=attention.cached_attention, **kw: (calls.append(1), kernel(*a, **{**kw, "interpret": True}))[1])
    got = llama._attn_cached(*args, write_gate=gate)
    assert len(calls) == int(gated)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify_s4"])
def test_the_kernel_runs_inside_a_shard_map_over_the_meshs_tp_axis(S):
    """Under the TP engine's mesh (`with mesh:` round the trace) the call is split over the KV
    heads, each device's heads and new rows against its own part of the slabs (four heads of
    128 a device: a cache row of half a tile), and gives the one-device result: both slabs
    written bit for bit, a gated-off slot's kept, and `out` the products' over them."""
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    from ray_tpu.llm import tp as tp_plan

    mesh = tp_plan.build_tp_mesh(2, devices=devices[:2])
    slab = tp_plan.kv_cache_sharding(mesh, 8)
    texts = []

    def on_mesh(q, k, v, new_k, new_v, lens, gate, scale):
        with mesh:
            run = jax.jit(lambda *a: llama._cached_attention_on_mesh(*a[:3], a[5], scale, a[3], a[4], a[6], interpret=True))
            args = (q, *(jax.device_put(x, slab) for x in (k, v, new_k, new_v)), lens, gate)
            texts.append(run.lower(*args).as_text())
            return run(*args)

    _written("d128g2", S, [3, 100, 127, T - S, 500], [True, True, True, True, False], run=on_mesh)
    assert "shard_map" in texts[0] or "manual" in texts[0]


# -- the benchmark's reader of the mechanism: `kv_attn_roofline.serve` -------------------------


@pytest.fixture
def roofline_reader(monkeypatch):
    """`benchmark/metrics/kv_attn_roofline.serve.py`, loaded as `benchmark/run.py` loads it."""
    import importlib.util
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    for name in [n for n in sys.modules if n == "lib" or n.startswith("lib.")]:
        monkeypatch.delitem(sys.modules, name)
    spec = importlib.util.spec_from_file_location("kv_attn_roofline_serve", os.path.join(bench, "metrics", "kv_attn_roofline.serve.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    yield module
    for name in [n for n in sys.modules if n == "lib" or n.startswith("lib.")]:
        sys.modules.pop(name)


@pytest.mark.parametrize("case", ["kernel", "no_scope_in_the_trace", "four_chips", "untraced"])
def test_the_kv_attn_roofline_reads_live_bytes_over_the_scopes_time(roofline_reader, monkeypatch, case):
    """A decode step whose 12 slots hold 5000 rows of InternLM2-1.8B's 98,304 bytes a row needs
    0.6 ms of a 819 GB/s memory; under `kv_attn` for 1.0 ms a step that is 60%. A trace whose
    programs wrote no such scope (a parent of PR 32), four chips or no trace: nothing, no raise."""
    model = {"n_layers": 24, "n_kv_heads": 8, "n_heads": 16, "hidden": 2048}
    record = {"cell": "c", "chips": 4 if case == "four_chips" else 1, "model": model, "trace": {},
              "peaks": {"hbm_bytes_per_s": 819e9}}
    scope = "attn" if case == "no_scope_in_the_trace" else "attn/kv_attn"
    ms = 1_000_000
    events = {
        "window": [0, 100 * ms],
        "modules": [("jit_rt_decode", 10 * ms, 10 * ms), ("jit_rt_decode_multi_n2", 30 * ms, 20 * ms)],
        "ops": [("cached_attn.1", f"jit(rt_decode)/layer_0/{scope}/cached_attn", 11 * ms, 1 * ms),
                ("fusion.7", "jit(rt_decode)/layer_0/mlp/dot_general", 13 * ms, 5 * ms),
                ("cached_attn.1", f"jit(rt_decode_multi)/while/body/layer_0/{scope}/cached_attn", 31 * ms, 2 * ms)],
        "spans": [("rt.engine.dispatch", 9 * ms, 1 * ms, {"rows": 4000, "steps": 1, "slots": 12}, 7),
                  ("rt.engine.dispatch", 29 * ms, 1 * ms, {"rows": 6000, "steps": 2, "slots": 12}, 7)],
    }
    from lib import scope_trace

    monkeypatch.setattr(scope_trace, "for_record", lambda record: None if case == "untraced" else events)
    got = roofline_reader.read(record)
    if case == "kernel":
        assert got == pytest.approx(100.0 * (5000 * 98304 / 819e9) / 1e-3)
        assert roofline_reader.NAME == "kv_attn_roofline.serve" and roofline_reader.DRIVERS == ("serve_closed", "serve_open")
    else:
        assert got is None
