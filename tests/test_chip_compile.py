"""Compile the Pallas flash kernels, one layer of the serve engine's cached attention
and the fused cross-entropy on four chips, for a described TPU v5e, without the chip.

The TPU's compiler is installed where the tests run, and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2). It refuses
what interpret mode lets through: a slice off the tiling, too much fast memory, a
kernel it cannot place. Kernels and one layer only, at the shapes the repo really runs;
whole-step compiles build a 152M-parameter model and stay scratch scripts.

Everything that touches the topology lives in the module-scoped fixtures below:
nothing here describes it at import, in a `skipif` or in `parametrize`, because the
process that loads the TPU's library keeps it, and every xdist worker imports every
test file. One file only, for the same reason.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.llama import _attn_cached
from ray_tpu.models.transformer import ModelConfig, fused_cross_entropy_loss
from ray_tpu.ops.attention import _flash_backward, _flash_blocks, _flash_forward

# (batch, heads, seq, head_dim) as the repo's configurations run the kernel, bf16.
SHAPES = {
    "gpt2-125m": (8, 12, 1024, 64),
    "llama3-1b": (1, 32, 8192, 64),
    "llama3-8b": (2, 32, 2048, 128),
    "mistral-7b-train-seq4k": (2, 32, 4096, 128),  # the two train cells' calls (PERF.md §4)
    "internlm2-1.8b-train-fsdp4": (2, 16, 4096, 128),
}


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2. The persistent compilation cache is off while this module
    runs: a compile for a described chip is written to it but cannot be read back
    without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises where there is no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """A sharding on one device of the described v5e:2x2."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(autouse=True)
def as_on_the_chip(monkeypatch):
    """`ops.attention._use_pallas()` asks for the backend and sees the CPU here: every program of
    this file is compiled as the chip's process would trace it, the cached attention through its
    kernel (on-chip-measurement guide, section 2: steer such code in the test)."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)


def _operand(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shaped(tree, sharding):
    """The tree's arrays as operands on that device."""
    return jax.tree_util.tree_map(lambda a: _operand(a.shape, sharding, a.dtype), tree)


def _laid_out(model: str, layout: str):
    b, h, s, d = SHAPES[model]
    return ((b, h, s, d) if layout == "bhsd" else (b, s, h, d)), (b, h, s), d


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
@pytest.mark.parametrize("model", sorted(SHAPES))
def test_flash_forward_compiles_for_v5e(one_chip, model, layout):
    shape, (_, _, s), d = _laid_out(model, layout)
    x = _operand(shape, one_chip)
    block_q, block_k = _flash_blocks(s, s, d, x.dtype)  # as the two call sites choose them

    def fwd(q, k, v):
        return _flash_forward(q, k, v, causal=True, scale=1.0 / math.sqrt(d),
                              block_q=block_q, block_k=block_k, interpret=False, layout=layout)

    text = jax.jit(fwd).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name, in both layouts: a device trace shows the instruction's
    # name, and the reduction finds the kernel by it (PERF.md §3)
    assert re.search(r"%flash_fwd(\.\d+)? = ", text) and 'flash_fwd/pallas_call"' in text


@pytest.mark.parametrize("shape", [*sorted(SHAPES), (1024, 4096, 128), (4096, 32768, 128), (16384, 16384, 64), (200, 200, 64)])
def test_the_forward_blocks_come_from_the_calls_shapes_and_not_from_the_environment(shape, monkeypatch):
    """`_flash_blocks` gives blocks that divide S and T or cover them and a key block that fits the
    kernel's fast memory two buffers deep; and nothing of `ops/attention.py` reads the variables
    the blocks once came from."""
    import inspect

    from ray_tpu.ops import attention

    s, t, d = (SHAPES[shape][2], SHAPES[shape][2], SHAPES[shape][3]) if shape in SHAPES else shape
    monkeypatch.setenv("RAY_TPU_FLASH_BQ", "128")
    monkeypatch.setenv("RAY_TPU_FLASH_BK", "128")
    block_q, block_k = _flash_blocks(s, t, d, jnp.bfloat16)
    assert s % block_q == 0 and t % block_k == 0, (block_q, block_k)  # a block that covers is the size itself
    assert block_q in (s, 512, 256, 128)
    assert block_k == t or 4 * block_k * max(d, 128) * 2 <= 8 << 20
    source = inspect.getsource(attention)
    assert "RAY_TPU_FLASH" not in source and "environ" not in source


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
@pytest.mark.parametrize("model", sorted(SHAPES))
def test_flash_backward_compiles_for_v5e(one_chip, model, layout):
    shape, lse_shape, d = _laid_out(model, layout)
    x = _operand(shape, one_chip)
    lse = _operand(lse_shape, one_chip, jnp.float32)

    def bwd(q, k, v, out, lse, g):
        return _flash_backward(q, k, v, out, lse, g, causal=True, scale=1.0 / math.sqrt(d),
                               block_q=512, block_k=1024, interpret=False, layout=layout)

    text = jax.jit(bwd).lower(x, x, x, x, lse, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%flash_bwd(\.\d+)? = ", text) and 'flash_bwd/pallas_call"' in text


# InternLM2-1.8B's widths and the dense serve cells' 2048 rows a slot (PERF.md §4).
_SERVE_CFG = ModelConfig(vocab_size=92544, hidden=2048, n_layers=24, n_heads=16, n_kv_heads=8,
                         mlp_dim=8192, max_seq=2048, rope_theta=1e6)


def _granite_cfg():
    return ModelConfig(block="granite_hybrid", vocab_size=100352, hidden=2048, n_layers=2, n_heads=32, n_kv_heads=8,
                       mlp_dim=8192, max_seq=4096, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, scan_layers=False,
                       remat=False, tie_embeddings=True, layer_types=("mamba", "attention"), mamba_n_heads=64,
                       mamba_d_head=64, mamba_d_state=128, embedding_multiplier=12.0, residual_multiplier=0.22,
                       attention_multiplier=0.015625, logits_scaling=8.0, position_embedding_type="nope")


def _lfm2_cfg():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "lfm2-24b-a2b.json")) as f:
        model = json.load(f)["model"]
    return ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})


# (slots, query rows): those cells' decode step over all 12 slots, and a one-slot chunk of 128 tokens
@pytest.mark.parametrize("slots,rows", [(12, 1), (1, 128)], ids=["decode_b12", "prefill_b128"])
def test_cached_attention_copies_no_slab_for_v5e(one_chip, slots, rows):
    """A decode step's attention (the kernel) and a chunk's (the grouped-query products) read K
    and V where they lie: the compiled layer holds no array of a slab repeated to all query
    heads. With `jnp.repeat` it held two stand-alone `broadcast_in_dim` of that size, a third
    of a decode step (PERF.md §6, PR 29)."""
    cfg, T = _SERVE_CFG, _SERVE_CFG.max_seq
    H, Hkv, D, M = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden
    layer = {
        name: {"kernel": _operand(shape, one_chip, jnp.float32)}
        for name, shape in [("q", (M, H, D)), ("k", (M, Hkv, D)), ("v", (M, Hkv, D)),
                            ("o", (H, D, M))]
    }
    slab = _operand((slots, T, Hkv, D), one_chip)

    def attn(layer, x, positions, cache_k, cache_v, write_at, gate):
        # the decode step gates its writes; the one-slot chunk has no gate
        return _attn_cached(layer, x, positions, cache_k, cache_v, write_at, cfg, write_gate=gate if rows == 1 else None)

    text = jax.jit(attn).lower(
        layer, _operand((slots, rows, M), one_chip),
        _operand((slots, rows), one_chip, jnp.int32), slab, slab,
        _operand((slots,), one_chip, jnp.int32), _operand((slots,), one_chip, jnp.bool_),
    ).compile().as_text()
    assert f"bf16[{slots},{T},{Hkv},{D}]" in text  # the slabs themselves are there to find
    assert ("cached_attn" in text) == (rows == 1)  # a one-slot view keeps the products (PERF.md §6, PR 35)
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", text)}
    G = H // Hkv
    repeated = {(slots, T, H, D), (slots, T, Hkv, G, D)}
    if slots == 1:
        # without the unit axis; not (T, H, D), which at these widths is the q kernel too
        repeated.add((T, Hkv, G, D))
    else:
        # and in any order of the axes ([B, Hkv, G, T, D], ...): with one query row no
        # other array of the layer has a slab's rows and H heads' worth of elements
        repeated |= {s for s in shapes if T in s and math.prod(s) == slots * T * H * D}
    assert not repeated & shapes, sorted(repeated & shapes)


@pytest.mark.parametrize("steps", [1, 4], ids=["decode", "multi-step"])
def test_a_decode_step_updates_the_recurrent_state_in_place_for_v5e(one_chip, steps):
    """`granite-4.0-h-micro.serve-sessions48`'s decode programs at the published widths and 48
    slots, cut to one mamba and one attention layer: every array of the donated cache is aliased
    to an output (48 slots of state are 3.6 GB at full depth, which a copy would double), and no
    copy of a recurrent state is in the compiled text: the update reads it once and writes it once."""
    from ray_tpu.models import granite_hybrid as gh

    cfg, slots = _granite_cfg(), 48

    params = _shaped(jax.eval_shape(lambda k: gh.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: gh.init_caches(cfg, slots, cfg.max_seq)), one_chip)
    vec = _operand((slots,), one_chip, jnp.int32)

    def run(params, last, caches, lens, gate):
        def step(carry, _):
            last, caches, lens = carry
            logits, caches, _ = gh.decode(params, cfg, last, caches, lens, gate)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), None

        return jax.lax.scan(step, (last, caches, lens), None, length=steps)[0]

    compiled = jax.jit(run, donate_argnums=(2,)).lower(
        params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_)).compile()
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes == held
    state = f"f32[{slots},{cfg.mamba_n_heads},{cfg.mamba_d_head},{cfg.mamba_d_state}]"
    text = compiled.as_text()
    assert state in text and not re.search(re.escape(state) + r"\S* copy\(", text)


def _dense_params(cfg, sharding, served=True):
    """The dense block's tree at `cfg`'s widths as operands on that device: as the engine holds it
    (`llama.serving_params` over the float32 tree `init_params` gives), or that float32 tree."""
    import functools

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import unbox

    tree = unbox(jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    assert {a.dtype for a in jax.tree_util.tree_leaves(tree)} == {jnp.dtype(jnp.float32)}
    if served:
        tree = jax.eval_shape(functools.partial(llama.serving_params, cfg), tree)
    return _shaped(tree, sharding)


def _weight_converts(text, params):
    """The compiled text's lines that convert an array of a kernel's or the table's size to bf16:
    what a program over float32 weights does to each before (or fused into) its product."""
    shapes = set()
    for a in jax.tree_util.tree_leaves(params):
        if len(a.shape) >= 2:  # as the tree holds it, and as a product reads it: [in, heads x dim], [heads x dim, out]
            shapes |= {tuple(a.shape), (a.shape[0], math.prod(a.shape[1:])), (math.prod(a.shape[:-1]), a.shape[-1])}
    return [line.strip()[:140] for line in text.splitlines()
            if (m := re.search(r"= bf16\[([\d,]+)\]\S* convert\(", line)) and tuple(int(n) for n in m.group(1).split(",")) in shapes]


def _slab_updates(text, *slabs):
    """The compiled text's `dynamic-update-slice`s into an array of one of these shapes: XLA's
    write of rows into a K or V slab. A decode program holds none since the kernel writes a
    step's rows itself (PERF.md §6, PR 47: the gated write was one a slot a slab a layer, run one
    after another); a chunk keeps its one a slab."""
    shapes = {tuple(shape) for shape in slabs}
    return [line.strip()[:160] for line in text.splitlines()
            if (m := re.search(r"= \w+\[([\d,]+)\]\S* dynamic-update-slice\(", line))
            and tuple(int(n) for n in m.group(1).split(",")) in shapes]


def _sampler_operands(slots, sharding):
    """What `rt_decode` and `rt_decode_multi_n<n>` take after the gate: the slots' temperatures and the sampler's key."""
    return (_operand((slots,), sharding, jnp.float32), _operand((2,), sharding, jnp.uint32))


def _dense_program(program, sharding, served=True):
    """One of the dense serve cells' three programs (`DecodeEngine`'s own bodies over
    `models/llama.py`) at InternLM2-1.8B's widths and 12 slots of 2048 rows, cut to two layers,
    compiled over `_dense_params` with the caches donated as the engine donates them.
    -> (compiled, the tree's operands, the caches', cfg, slots)."""
    import dataclasses
    import functools
    import types

    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.models import llama

    cfg = dataclasses.replace(_SERVE_CFG, n_layers=2, scan_layers=False, remat=False)
    slots, T = 12, cfg.max_seq
    engine = types.SimpleNamespace(cfg=cfg, _block=llama, _mesh=None)  # all that the three bodies read of an engine
    engine._decode_step = functools.partial(DecodeEngine._decode_step, engine)

    params = _dense_params(cfg, sharding, served)
    caches = _shaped(jax.eval_shape(lambda: llama.init_caches(cfg, slots, T)), sharding)
    vec, i32 = _operand((slots,), sharding, jnp.int32), _operand((), sharding, jnp.int32)
    step = (params, None, vec, vec, caches, vec, _operand((slots,), sharding, jnp.bool_))
    body, donated, args = {
        "rt_decode": (functools.partial(DecodeEngine._decode_sample, engine), 4, step + _sampler_operands(slots, sharding)),
        "rt_decode_multi_n8": (functools.partial(DecodeEngine._decode_multi, engine, n=8), 4,
                               step + _sampler_operands(slots, sharding)),
        "rt_prefill_b128": (functools.partial(DecodeEngine._prefill_at, engine), 3,
                            (params, None, _operand((1, 128), sharding, jnp.int32), caches, i32, i32, i32, i32)),
    }[program]
    return jax.jit(body, donate_argnums=(donated,)).lower(*args).compile(), params, caches, cfg, slots


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b128"])
def test_the_dense_programs_write_the_kv_slab_in_place_for_v5e(one_chip, program):
    """The dense serve cells' three programs over the tree as the engine holds it
    (`llama.serving_params` of a float32 tree: kernels and table in bf16), the caches donated:
    every slab is aliased to its output, and the compiled text holds no copy of one into its own
    layout. Undonated, each program first copied all 48 slabs of the whole depth
    (`copy(%caches_...)`, four here), 6.9 ms of a 25.5 ms decode step and 6.7 of a 17.3 ms chunk
    (PERF.md §6, PR 33). The decode programs no longer hold a slab in any second layout
    (`test_the_decode_programs_read_the_slabs_through_the_kernel_for_v5e`); the chunk's two
    products read theirs as fused operands, and its rows go into each slab by one
    `dynamic-update-slice`, where a decode program holds none: the kernel writes a step's rows
    (PERF.md §6, PR 47). And the weights are read as they are multiplied: no
    `convert` of a kernel's or the table's size is left in any of the three (over the float32
    tree a single step read 7.56 GB for 3.78 GB of products and the 8-step program kept a
    converted copy of every kernel, 3.6 GiB of temporaries at the whole depth: PERF.md §6, PR
    40), and the plan's arguments beside the caches are two bytes a parameter."""
    compiled, params, caches, cfg, slots = _dense_program(program, one_chip)
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    plan = compiled.memory_analysis()
    assert plan.alias_size_in_bytes == held
    slab = f"bf16[{slots},{cfg.max_seq},{cfg.n_kv_heads},{cfg.head_dim}]"
    text = compiled.as_text()
    assert slab + "{3,2,1,0" in text  # the slab as the engine holds it: row-major
    assert not re.search(re.escape(slab) + r"\{3,2,1,0[^}]*\} copy\(", text)
    written = _slab_updates(text, (slots, cfg.max_seq, cfg.n_kv_heads, cfg.head_dim))
    assert len(written) == (2 * cfg.n_layers if program == "rt_prefill_b128" else 0), written  # a chunk's own write; a step's is the kernel's
    assert not _weight_converts(text, params)
    leaves = jax.tree_util.tree_leaves(params)
    assert {a.dtype for a in leaves if len(a.shape) >= 2} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in leaves if len(a.shape) == 1} == {jnp.dtype(jnp.float32)}  # the norms' scales
    weights = plan.argument_size_in_bytes - held
    count = sum(math.prod(a.shape) for a in leaves)
    assert 2 * count <= weights < 2.002 * count, (weights, count)  # the scales' float32 and the step's vectors over it


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b128"])
def test_the_dense_programs_over_a_float32_tree_still_convert_the_kernels_for_v5e(one_chip, program):
    """The control of the case above: the same three programs over the float32 tree a train step
    or a checkpoint holds convert the kernels (`_dense` casts inside the program; the 8-step
    program hoists the casts out of its scan and keeps the copies), and their arguments are four
    bytes a parameter. So the reading above is the tree's doing, and a `serving_params` that stops
    casting would show."""
    compiled, params, caches, cfg, _ = _dense_program(program, one_chip, served=False)
    converts = _weight_converts(compiled.as_text(), params)
    # the MLP's three a layer and the head at the least (13 to 15 of the 15 kernels here, by the program:
    # the compiler folds some of the attention's into other fusions; the 8-step program converts the table too)
    assert len(converts) >= 3 * cfg.n_layers + 1, converts
    assert any(f"bf16[{cfg.hidden},{cfg.vocab_size}]" in line for line in converts)
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    count = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes - held >= 4 * count


@pytest.mark.parametrize("steps", [1, 8], ids=["rt_decode", "rt_decode_multi_n8"])
@pytest.mark.parametrize("block", ["llama", "granite_hybrid", "lfm2"])
def test_the_decode_programs_read_the_slabs_through_the_kernel_for_v5e(one_chip, block, steps):
    """The four serve cells' decode programs (the dense block at InternLM2-1.8B's widths and 12
    slots of 2048 rows, cut to two layers; `granite_hybrid` at 48 slots of 4096 rows, one mamba and
    one attention layer; `lfm2`'s cut of 9 layers at 64 slots), the caches donated as the engine
    donates them: the text holds the `cached_attn` call, every cache array is aliased to its
    output, no operation copies an array of a slab's size (the parent's programs copied every
    head-64 slab into another layout each step, `jit_rt_decode:copy.24` and its like, a quarter of
    a `granite_hybrid` step: PERF.md §6, PR 35), no `dynamic-update-slice` writes into a slab
    (the kernel writes the step's rows: PERF.md §6, PR 47), and no `[B, Hkv, G, S, T]` array of
    scores over every row of every slot is left."""
    from ray_tpu import models
    from ray_tpu.parallel.mesh import unbox

    import dataclasses

    cfg, slots = {"llama": (dataclasses.replace(_SERVE_CFG, n_layers=2, scan_layers=False, remat=False), 12),
                  "granite_hybrid": (_granite_cfg(), 48), "lfm2": (_lfm2_cfg(), 64)}[block]
    module, T = models.block_module(cfg), cfg.max_seq
    tree = unbox(jax.eval_shape(lambda k: module.init_params(cfg, k), jax.random.PRNGKey(0)))
    params = _shaped(jax.eval_shape(lambda tree: module.serving_params(cfg, tree), tree), one_chip)  # as the engine holds it
    caches = _shaped(jax.eval_shape(lambda: module.init_caches(cfg, slots, T)), one_chip)
    vec = _operand((slots,), one_chip, jnp.int32)

    def run(params, last, caches, lens, gate):
        def step(carry, _):
            last, caches, lens = carry
            logits, caches, stats = module.decode(params, cfg, last, caches, lens, gate, None, None)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), stats

        return jax.lax.scan(step, (last, caches, lens), None, length=steps)

    compiled = jax.jit(run, donate_argnums=(2,)).lower(params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_)).compile()
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes == held
    text = compiled.as_text()
    assert re.search(r"%cached_attn(\.\d+)? = ", text) and re.search(r'kv_attn/[^"]*cached_attn/pallas_call"', text)
    slab = math.prod(module.init_caches(cfg, 1, 8)[-1][0].shape[2:]) * slots * T  # elements of one K or V slab
    sized = [line.strip()[:160] for line in text.splitlines()
             if (m := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)) and math.prod(int(n) for n in m.group(1).split(",")) >= slab]
    assert not sized, sized
    kv = module.init_caches(cfg, slots, 8)[-1][0].shape  # the last layer is an attention layer in all three
    assert not _slab_updates(text, (slots, T) + tuple(kv[2:])), "XLA writes a step's rows"
    G = cfg.n_heads // cfg.n_kv_heads
    shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\bf32\[([\d,]+)\]", text)}
    assert not {s for s in shapes if T in s and math.prod(s) == slots * cfg.n_kv_heads * G * T}, "scores over every row"


def test_the_decode_program_ends_in_a_sampler_that_sorts_nothing_for_v5e(one_chip):
    """`rt_decode` at the chat cell's widths (12 slots, 92544 tokens of vocabulary, two layers):
    the compiled text holds operations under the scope `sample`, both branches of its `cond`
    among them (a round of greedy rows generates no noise), and no `sort` there or anywhere
    else in the program (a top-k filter at a temperature stays the host's: `_host_drawn`); the
    logits leave the program beside the tokens, `f32[12,92544]` and `s32[12]`; and the block's
    own prefetches keep their place in the schedule."""
    import dataclasses
    import functools
    import types

    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.models import llama

    cfg = dataclasses.replace(_SERVE_CFG, n_layers=2, scan_layers=False, remat=False)
    slots, T = 12, cfg.max_seq
    engine = types.SimpleNamespace(cfg=cfg, _block=llama, _mesh=None)
    engine._decode_step = functools.partial(DecodeEngine._decode_step, engine)
    params = _dense_params(cfg, one_chip)
    caches = _shaped(jax.eval_shape(lambda: llama.init_caches(cfg, slots, T)), one_chip)
    vec = _operand((slots,), one_chip, jnp.int32)
    args = (params, None, vec, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_)) + _sampler_operands(slots, one_chip)
    compiled = jax.jit(functools.partial(DecodeEngine._decode_sample, engine), donate_argnums=(4,)).lower(*args).compile()
    text = compiled.as_text()
    sampled = [line for line in text.splitlines() if re.search(r'op_name="jit\([^"]*/sample/', line)]
    assert sampled and any("branch_0_fun" in line for line in sampled) and any("branch_1_fun" in line for line in sampled)
    assert not re.search(r"\bsort\(", text)
    assert any("random_bits" in line or "threefry" in line for line in sampled)  # the noise is the sampler's, in its scope
    # The sampler leaves the block's schedule alone: every layer's norm scales are fetched behind a
    # product, as without it. Drawn as one `[B, V]` fusion the compiler started those fetches after
    # the product before them and each `copy-done` waited: 0.3 ms a step on the chip (PERF.md §6, PR 37).
    # Since the kernel writes a step's rows (PERF.md §6, PR 47) XLA's gated write is gone from between a
    # layer's first fetch and its first product, and in a program this shallow (every kernel prefetched at
    # its start) an `attn_norm` scale's 8 KB come behind the copy that lays that layer's k or v kernel out
    # for its product, 4 MB, and nothing else; the cell's 24 layers keep 47 of 48 fetches behind a product.
    entry = text[text.index("ENTRY "):].splitlines()
    at = {m.group(1): i for i, line in enumerate(entry) if (m := re.match(r"\s*%([\w.\-]+) = ", line))}
    kv_kernel = rf"= bf16\[{cfg.n_kv_heads * cfg.head_dim},{cfg.hidden}\]\S* copy\(.*op_name=\"[^\"]*/layer_%s/attn/dot_general\""
    fetched, behind_a_copy = 0, 0
    for i, line in enumerate(entry):
        done = re.match(r"\s*%copy-done[\w.\-]* = .*copy-done\(%([\w.\-]+)\)", line)
        if done and (scale := re.search(r"copy-start\(%params__layer_(\d+)____(\w+_norm)", entry[at[done.group(1)]])):
            fetched += 1
            between = entry[at[done.group(1)]:i]
            if not any(" fusion(" in op and "dot_general" in op and "kind=kOutput" in op for op in between):
                layer, norm = scale.groups()
                assert norm == "attn_norm" and any(re.search(kv_kernel % layer, op) for op in between), line.strip()[:120]
                behind_a_copy += 1
    assert behind_a_copy <= cfg.n_layers
    assert fetched == 2 * cfg.n_layers
    out = jax.eval_shape(functools.partial(DecodeEngine._decode_sample, engine), *args)
    assert (out[0].shape, out[0].dtype, out[1].shape, out[1].dtype) == ((slots,), jnp.int32, (slots, cfg.vocab_size), jnp.float32)


def test_the_eight_step_program_draws_every_steps_token_whatever_the_temperatures_for_v5e(one_chip):
    """`rt_decode_multi_n8` at the chat cell's widths (12 slots, 92544 tokens of vocabulary, two
    layers), the one program a plan of eight steps runs whether its slots are greedy or at a
    temperature: the temperatures and the sampler's key are operands (`f32[12]`, `u32[2]`), so
    nothing about them is in the lowering; the scan's body holds the sampler's noise under
    `sample` and no control flow of the sampler's: no `cond` a step (a conditional, or a loop
    over the rows, in the scan's body cost the step 0.55 to 0.95 ms on the chip in either
    branch: PERF.md §6, PR 44) and no `sort`; and what comes back is `[8, 12]` int32 ids, the
    caches, the lengths and the next key: no `[B, V]` float32 leaves the program."""
    compiled, _, caches, cfg, slots = _dense_program("rt_decode_multi_n8", one_chip)
    text = compiled.as_text()
    sampled = [line for line in text.splitlines() if re.search(r'op_name="jit\([^"]*/while/body/(?:closed_call/)?sample/', line)]
    assert any("random_bits" in line or "threefry" in line for line in sampled)
    assert "conditional(" not in text and not re.search(r"\bsort\(", text)
    entry = next(line for line in text.splitlines() if line.startswith("ENTRY "))
    assert re.search(r"temps[\w.]*: f32\[%d\]" % slots, entry) and re.search(r"key[\w.]*: u32\[2\]", entry), entry[-400:]
    toks, held, lens, key = compiled.out_info
    assert (toks.shape, toks.dtype, lens.shape, key.shape, key.dtype) == ((8, slots), jnp.int32, (slots,), (2,), jnp.uint32)
    results = [(tuple(a.shape), a.dtype) for a in jax.tree_util.tree_leaves(compiled.out_info)]
    assert len(results) == 3 + len(jax.tree_util.tree_leaves(caches))
    assert not [r for r in results if r[0][-1:] == (cfg.vocab_size,)], results
    root = text[text.index("ENTRY "):]
    assert f"f32[{slots},{cfg.vocab_size}]" not in next(line for line in root.splitlines() if " ROOT " in line)


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_spec_verify_k4", "rt_prefill_b16",
                                     "rt_prefill_b128", "prefill_detached_b16", "draft_propose"])
def test_the_tp_engines_programs_compile_for_v5e_2x2(v5e_2x2, program):
    """The TP=4 engine's programs over the dense block at InternLM2-1.8B's widths (cut to two
    layers), params and slabs split over `tp` as `llm/tp.py` splits them, each traced as the
    engine traces it: decode, the eight-step scan with the sampler in its body and verify under
    the mesh (`_engine.py:_traced_on`), where the kernel runs inside a `shard_map` over the KV
    heads, two calls and the row-parallel all-reduces; a prefill chunk (the smallest bucket: 16 tokens x 16 heads), a detached prefill
    and the draft's own steps outside any mesh, where a `pallas_call` cannot be lowered
    (`Mosaic kernels cannot be automatically partitioned`): those hold no kernel and compile."""
    import dataclasses
    import functools
    import types

    from jax.sharding import NamedSharding

    from ray_tpu.llm import tp as tp_plan
    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.llm.scheduler.spec import ModelDraft
    from ray_tpu.models import llama

    cfg = dataclasses.replace(_SERVE_CFG, n_layers=2, scan_layers=False, remat=False)
    slots, T = 12, cfg.max_seq
    mesh = tp_plan.build_tp_mesh(4, devices=list(v5e_2x2.devices))
    whole = tp_plan.replicated(mesh)
    engine = types.SimpleNamespace(cfg=cfg, _block=llama, _mesh=mesh)
    engine._decode_step = functools.partial(DecodeEngine._decode_step, engine)

    def on_mesh(tree, path=()):
        if isinstance(tree, dict):
            return {k: on_mesh(v, path + (k,)) for k, v in tree.items()}
        return _operand(tree.shape, NamedSharding(mesh, tp_plan.param_spec(path, tuple(tree.shape), mesh)), tree.dtype)

    params = on_mesh(_dense_params(cfg, whole))  # the tree as the engine holds it, then `shard_decode_params`' layout
    caches = _shaped(jax.eval_shape(lambda: llama.init_caches(cfg, slots, T)), tp_plan.kv_cache_sharding(mesh, cfg.n_kv_heads))
    vec, i32 = _operand((slots,), whole, jnp.int32), _operand((), whole, jnp.int32)
    gate = _operand((slots,), whole, jnp.bool_)
    draft = types.SimpleNamespace(cfg=cfg, T=T)  # all that the draft's program reads of its provider
    body, args = {
        "rt_decode": (functools.partial(DecodeEngine._decode_sample, engine),
                      (params, None, vec, vec, caches, vec, gate) + _sampler_operands(slots, whole)),
        "rt_decode_multi_n8": (functools.partial(DecodeEngine._decode_multi, engine, n=8),
                               (params, None, vec, vec, caches, vec, gate) + _sampler_operands(slots, whole)),
        "rt_spec_verify_k4": (functools.partial(DecodeEngine._spec_verify_batched, engine),
                              (params, None, vec, _operand((slots, 5), whole, jnp.int32), caches, vec, gate,
                               _operand((slots, 5, cfg.vocab_size), whole, jnp.float32))),
        "rt_prefill_b16": (functools.partial(DecodeEngine._prefill_at, engine),
                           (params, None, _operand((1, 16), whole, jnp.int32), caches, i32, i32, i32, i32)),
        "rt_prefill_b128": (functools.partial(DecodeEngine._prefill_at, engine),
                            (params, None, _operand((1, 128), whole, jnp.int32), caches, i32, i32, i32, i32)),
        "prefill_detached_b16": (lambda params, tokens, adapter: llama.prefill_detached(params, cfg, tokens, None, adapter),
                                 (params, _operand((1, 16), whole, jnp.int32), i32)),
        "draft_propose": (functools.partial(ModelDraft._propose_prog, draft, k=4, catchup=False),
                          (params, caches, i32, i32, i32, i32)),
    }[program]
    text = jax.jit(body).lower(*args).compile().as_text()
    kernels = len(re.findall(r"%cached_attn(\.\d+)? = ", text))
    if program in ("rt_decode", "rt_decode_multi_n8", "rt_spec_verify_k4"):
        assert kernels == cfg.n_layers and " all-reduce" in text
        assert f"bf16[{slots},{T},{cfg.n_kv_heads // 4},{cfg.head_dim}]" in text  # a device's heads of the slab
        # two heads a device: a cache row of a quarter of a tile, written through its window of whole tiles
        assert not _slab_updates(text, (slots, T, cfg.n_kv_heads // 4, cfg.head_dim))
    else:
        assert kernels == 0 and "tpu_custom_call" not in text


def test_fused_loss_moves_the_head_once_a_step_on_v5e_2x2(v5e_2x2):
    """`internlm2-1.8b.train-fsdp4`'s loss (8 x 4096 tokens, a 2048 x 92544 head split four
    ways on `embed`, 16 chunks): value_and_grad compiled for the four described chips
    holds one gather of the cast head and one reduction of its gradient, neither inside
    a loop. Left to the compiler the two loops held two gathers and an all-reduce of the
    whole head in every chunk, a quarter of the step (PERF.md §6, PR 31)."""
    import flax.linen as nn
    from jax.sharding import NamedSharding

    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import _rules_list
    from tests.test_fused_loss_mesh import _collectives

    B, S, E, V = 8, 4096, _SERVE_CFG.hidden, _SERVE_CFG.vocab_size
    mesh = mesh_lib.create_mesh({"fsdp": 4}, devices=v5e_2x2.devices)

    def placed(shape, dtype, names):
        return _operand(shape, NamedSharding(mesh, mesh_lib.logical_to_spec(names)), dtype)

    def loss(hidden, table, targets):
        with nn.logical_axis_rules(_rules_list(None)):
            return fused_cross_entropy_loss(hidden, table, targets, contract_dim=0)

    with mesh:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            placed((B, S, E), jnp.bfloat16, ("batch", "seq", None)),
            placed((E, V), jnp.float32, ("embed", "vocab")),
            placed((B, S), jnp.int32, ("batch", "seq")),
        ).compile().as_text()
    assert len(re.findall(r"body=", text)) >= 2  # the forward and the backward chunk loop
    whole_head = sorted((kind, inside) for kind, size, inside in _collectives(text) if size == E * V)
    assert whole_head in ([("all-gather", False), ("all-reduce", False)],
                          [("all-gather", False), ("reduce-scatter", False)]), whole_head


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b512"])
def test_the_lfm2_cells_programs_fit_the_chip_and_read_each_expert_in_place_for_v5e(one_chip, program):
    """`lfm2-24b-a2b.serve-decode64`'s three programs at the published widths, all 9 layers of the
    cut and 64 slots of 4096 rows, the caches donated as the engine donates them: the plan
    (arguments + outputs + temporaries - aliased) stays under the chip's 15.75 GiB with 10.36 GB of
    weights held, every cache array is aliased to its output, and the loop over the experts' tiles
    reads a tile's expert where it lies: its `[2048, 1536]` matrices are a `dynamic-slice` of the
    stack fused into the product, and no operation of the compiled text copies one out first
    (that would read every expert twice and write it once: PERF.md §6, PR 34)."""
    from ray_tpu.models import lfm2

    cfg, slots = _lfm2_cfg(), 64
    params = _shaped(jax.eval_shape(lambda k: lfm2.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: lfm2.init_caches(cfg, slots, cfg.max_seq)), one_chip)
    vec, scalar = _operand((slots,), one_chip, jnp.int32), _operand((), one_chip, jnp.int32)

    def steps(n):
        def run(params, last, caches, lens, gate):
            def step(carry, _):
                last, caches, lens = carry
                logits, caches, stats = lfm2.decode(params, cfg, last, caches, lens, gate)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), stats

            return jax.lax.scan(step, (last, caches, lens), None, length=n)
        return jax.jit(run, donate_argnums=(2,)).lower(params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_))

    if program == "rt_prefill_b512":
        lowered = jax.jit(lambda p, t, c, s, o, n: lfm2.prefill(p, cfg, t, c, s, o, n), donate_argnums=(2,)).lower(
            params, _operand((1, 512), one_chip, jnp.int32), caches, scalar, scalar, scalar)
    else:
        lowered = steps(8 if program.endswith("n8") else 1)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    plan = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert m.alias_size_in_bytes == held == 64 * (57344 + 4096 * 4096)
    assert 2 * lfm2.num_params(cfg) + held < plan < 15.75 * 2**30, plan / 2**30
    text = compiled.as_text()
    assert "bf16[64,2048,1536]" in text and re.search(r"bf16\[1,2048,1536\]\S* dynamic-slice\(", text)
    for matrix in ("bf16[2048,1536]", "bf16[1536,2048]", "bf16[1,2048,1536]", "bf16[1,1536,2048]", "bf16[64,2048,1536]"):
        assert not re.search(re.escape(matrix) + r"\S* copy\(", text), matrix


def _cell_cfg(name: str):
    """The `model` of `benchmark/configs/<name>.json` as the program's `ModelConfig`."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        model = json.load(f)["model"]
    return ModelConfig(**{k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()})


def _chunk_kernel_holds_the_scores(text: str, layers: int, heads: int, queries: int = 1024, kb: int = 1024):
    """A prefill program's latent attention (`ops/latent_attention.py:latent_chunk_attention`): one call of
    the kernel `latent_chunk` a layer, inside that layer's loop over key blocks and under the scope
    `latent`, the carry's three arrays written where they were read; and no float32 array of a block's
    scores, `[heads, queries, kb]` (537 MB at 128 heads), which XLA's three fusions wrote and read."""
    calls = [line for line in text.splitlines() if re.search(r"%latent_chunk(\.\d+)? = ", line)]
    assert len(calls) == layers, len(calls)
    for line in calls:
        assert re.search(r'layer_\d+/attn/latent/while/body/[^"]*latent_chunk/pallas_call"', line), line[:300]
        assert "output_to_operand_aliasing={{0}: (5, {}), {1}: (6, {}), {2}: (7, {})}" in line or \
            "output_to_operand_aliasing={{0}: (6, {}), {1}: (7, {}), {2}: (8, {})}" in line, line[:300]
        assert f"f32[{heads},128,{queries}]" in line  # the accumulator, queries on the lanes
    shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\bf32\[([\d,]+)\]", text)}
    assert not {sh for sh in shapes if math.prod(sh) >= heads * queries * kb and kb in sh and heads in sh}, "a block's scores in memory"


def _pangu_cfg():
    return _cell_cfg("openpangu-ultra-moe-718b")


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b1024"])
def test_the_pangu_moe_cells_programs_fit_the_chip_and_copy_no_latent_slab_for_v5e(one_chip, program):
    """`openpangu-ultra-moe-718b.serve-longctx-mla`'s programs at the published widths, all 6 layers
    of the cut and 16 slots of 32768 rows, the caches donated as the engine donates them: the latent
    slab is `bf16[16,32768,640]` row-major in the program's own layout (576 values in five whole rows
    of 128 lanes), every slab is aliased to its output, no operation copies an array of a slab's size
    (a 576-wide slab was copied whole four times a decode step: PERF.md §7, PR 35), the plan stays
    under the chip's 15.75 GiB with 8.07 GB of weights and 4.03 GB of cache held, a decode step reads
    the slab through the kernel `latent_attn` and holds no array of scores over every row of every
    slot, and W_qb is multiplied where it lies (kept `[1536, 128, 192]` it was copied into the
    product's shape every step: a last axis of 192 is a row and a half of lanes). A chunk's attention
    over a block of keys is one call of the kernel `latent_chunk` a layer and no array of a block's scores
    (`_chunk_kernel_holds_the_scores`)."""
    from ray_tpu.models import pangu_moe

    cfg, slots = _pangu_cfg(), 16
    T = cfg.max_seq
    params = _shaped(jax.eval_shape(lambda k: pangu_moe.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: pangu_moe.init_caches(cfg, slots, T)), one_chip)
    vec, scalar = _operand((slots,), one_chip, jnp.int32), _operand((), one_chip, jnp.int32)

    def steps(n):
        def run(params, last, caches, lens, gate):
            def step(carry, _):
                last, caches, lens = carry
                logits, caches, stats = pangu_moe.decode(params, cfg, last, caches, lens, gate)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), stats

            return jax.lax.scan(step, (last, caches, lens), None, length=n)
        return jax.jit(run, donate_argnums=(2,)).lower(params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_))

    if program == "rt_prefill_b1024":
        lowered = jax.jit(lambda p, t, c, s, o, n: pangu_moe.prefill(p, cfg, t, c, s, o, n), donate_argnums=(2,)).lower(
            params, _operand((1, 1024), one_chip, jnp.int32), caches, scalar, scalar, scalar)
    else:
        lowered = steps(8 if program.endswith("n8") else 1)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    plan = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert m.alias_size_in_bytes == held == 6 * 16 * 32768 * 640 * 2
    assert 2 * pangu_moe.num_params(cfg) + held < plan < 15.75 * 2**30, plan / 2**30
    text = compiled.as_text()
    layout = next(line for line in text.splitlines() if "entry_computation_layout" in line)
    assert "bf16[16,32768,640]{2,1,0:T(8,128)(2,1)}" in layout and "bf16[16,32768,576]" not in text
    copied = [tuple(int(n) for n in mm.group(1).split(",")) for line in text.splitlines()
              if (mm := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))]
    # no copy of a slab, nor of one slot's rows of it (a chunk's view)
    assert not [s for s in copied if T in s or math.prod(s) >= slots * T * 640], copied
    if program != "rt_prefill_b1024":
        assert re.search(r"%latent_attn(\.\d+)? = ", text) and re.search(r'latent/[^"]*latent_attn/pallas_call"', text)
        shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\bf32\[([\d,]+)\]", text)}
        assert not {s for s in shapes if T in s and math.prod(s) >= slots * cfg.n_heads * T}, "scores over every row"
        assert "latent_chunk" not in text
    else:
        _chunk_kernel_holds_the_scores(text, cfg.n_layers, cfg.n_heads)
        assert not re.search(r"%latent_attn(\.\d+)? = ", text)


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b1024"])
def test_the_xing4_cells_programs_fit_the_chip_keep_the_streams_whole_and_copy_no_latent_slab_for_v5e(one_chip, program):
    """`xing4.0-29b-a4b.serve-sessions-mhc48`'s programs at the published widths, all 6 layers of the cut
    and 48 slots of 8192 rows, the caches donated as the engine donates them: the plan stays under the
    chip's 15.75 GiB with 9.59 GB of weights and 3.02 GB of cache held; every slab is `bf16[48,8192,640]`
    row-major, aliased to its output and copied by no operation; a token's four streams are one row of
    14336 lanes (`[tokens, 14336]`: no array has an axis of 4 beside the lanes, which the chip would pad
    to a tile of 8 or 16) and Phi lies `bf16[24,14336]`; the coefficients are computed with the tokens on
    the lane axis by one call of the kernel `hc_map` a sub-layer; a decode step reads the slab through
    the kernel `latent_attn`, and a chunk's attention over a block of keys is one call of the kernel
    `latent_chunk` a layer with no array of a block's scores (32 heads: 134 MB)."""
    from ray_tpu.models import xing4

    cfg, slots = _cell_cfg("xing4.0-29b-a4b"), 48
    T, tokens = cfg.max_seq, 1024 if program == "rt_prefill_b1024" else 48
    params = _shaped(jax.eval_shape(lambda k: xing4.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: xing4.init_caches(cfg, slots, T)), one_chip)
    vec, scalar = _operand((slots,), one_chip, jnp.int32), _operand((), one_chip, jnp.int32)

    def steps(n):
        def run(params, last, caches, lens, gate):
            def step(carry, _):
                last, caches, lens = carry
                logits, caches, stats = xing4.decode(params, cfg, last, caches, lens, gate)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), stats

            return jax.lax.scan(step, (last, caches, lens), None, length=n)
        return jax.jit(run, donate_argnums=(2,)).lower(params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_))

    if program == "rt_prefill_b1024":
        lowered = jax.jit(lambda p, t, c, s, o, n: xing4.prefill(p, cfg, t, c, s, o, n), donate_argnums=(2,)).lower(
            params, _operand((1, 1024), one_chip, jnp.int32), caches, scalar, scalar, scalar)
    else:
        lowered = steps(8 if program.endswith("n8") else 1)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    plan = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert m.alias_size_in_bytes == held == 6 * 48 * 8192 * 640 * 2
    assert 2 * xing4.num_params(cfg) == 9_585_339_656 and 2 * xing4.num_params(cfg) + held < plan < 15.75 * 2**30, plan / 2**30
    text = compiled.as_text()
    layout = next(line for line in text.splitlines() if "entry_computation_layout" in line)
    assert "bf16[48,8192,640]{2,1,0:T(8,128)(2,1)}" in layout and "bf16[24,14336]{1,0:T(8,128)(2,1)}" in layout
    copied = [tuple(int(n) for n in mm.group(1).split(",")) for line in text.splitlines()
              if (mm := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))]
    assert not [s for s in copied if T in s or math.prod(s) >= slots * T * 640], copied
    # the streams: rows of 14336 lanes and nothing with the four streams as an axis of their own
    assert re.search(rf"bf16\[{tokens},14336\]\{{1,0:T\(8,128\)\(2,1\)", text)
    under_hc = [line for line in text.splitlines() if re.search(r'op_name="[^"]*/hc/', line)]
    assert under_hc and not [line for line in under_hc if re.search(r"\[(\d+,)*4,3584\]|\[4,\d+,3584\]", line)]
    # the coefficients: tokens on the lane axis into and out of one kernel call a sub-layer
    calls = re.findall(r"= f32\[24,(\d+)\]\S* custom-call\([^\n]*hc_map", text)
    assert len(calls) == 2 * cfg.n_layers and set(calls) == {str(tokens)}, calls
    assert re.search(r'hc/map/[^"]*hc_map/pallas_call"', text)
    if program != "rt_prefill_b1024":
        assert re.search(r"%latent_attn(\.\d+)? = ", text) and re.search(r'latent/[^"]*latent_attn/pallas_call"', text)
        assert "latent_chunk" not in text
    else:
        _chunk_kernel_holds_the_scores(text, cfg.n_layers, cfg.n_heads)



def test_the_dots3_cells_chunk_keeps_its_scores_in_the_kernel_and_fits_the_chip_for_v5e(one_chip):
    """`dots3-note-prev.serve-longctx`'s `rt_prefill_b1024` at the published widths, all 5 layers of the
    cut and 16 slots of 32768 rows, the caches donated: the two full layers' attention over a block of
    keys is one call of the kernel `latent_chunk` a layer, the selection's block going in as an int8
    operand `[keys, queries]`, with no float32 array of a block's scores; the kernel takes the block's
    expanded keys and values and never the slab, so the 576-wide latent slab (`bf16[16,32768,576]`, not
    whole rows of lanes: PERF.md section 7) is aliased to its output and copied by no operation; the
    three sliding layers run no kernel; the plan stays under the chip's 15.75 GiB."""
    from ray_tpu.models import dots3

    cfg, slots = _cell_cfg("dots3-note-prev"), 16
    T = cfg.max_seq
    params = _shaped(jax.eval_shape(lambda k: dots3.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: dots3.init_caches(cfg, slots, T)), one_chip)
    scalar = _operand((), one_chip, jnp.int32)
    compiled = jax.jit(lambda p, t, c, s, o, n: dots3.prefill(p, cfg, t, c, s, o, n), donate_argnums=(2,)).lower(
        params, _operand((1, 1024), one_chip, jnp.int32), caches, scalar, scalar, scalar).compile()
    m = compiled.memory_analysis()
    plan = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    # every cache aliased (the rings' 513 rows are padded to whole tiles in the chip's layout: 0.2% more than their shapes)
    assert held <= m.alias_size_in_bytes < 1.01 * held and 2 * dots3.num_params(cfg) + held < plan < 15.75 * 2**30, plan / 2**30
    text = compiled.as_text()
    full = sum(t == "full_attention" for t in cfg.layer_types)
    assert full == 2 and "bf16[16,32768,576]" in text
    _chunk_kernel_holds_the_scores(text, full, cfg.n_heads)
    calls = [line for line in text.splitlines() if re.search(r"%latent_chunk(\.\d+)? = ", line)]
    assert all("s8[1024,1024]" in line and "32768" not in line.split("custom_call_target")[0] for line in calls)
    copied = [tuple(int(n) for n in mm.group(1).split(",")) for line in text.splitlines()
              if (mm := re.search(r"= bf16\[([\d,]+)\]\S* copy\(", line))]
    # no copy of the slab; of one slot's view of it the one a full layer that the parent had too (38 MB where the
    # chunk's rows are written into a slab that is not whole rows of lanes: S10), none for the kernel's sake
    assert not [sh for sh in copied if math.prod(sh) >= slots * T * 576], copied
    assert len([sh for sh in copied if sh == (1, T, 576)]) <= full, copied



@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n8", "rt_prefill_b1024"])
def test_the_laguna_cells_programs_fit_the_chip_and_copy_no_slab_and_score_no_row_past_the_live_ones_for_v5e(one_chip, program):
    """`laguna-s-2.1.serve-mixedlen24`'s programs at the published widths, all 5 layers of the cut and
    24 slots of 32768 rows, the caches donated as the engine donates them: a full layer's slabs are
    `bf16[24,32768,8,128]` and a sliding layer's rings `bf16[24,512,8,128]`, row-major in the program's
    own layout (a head is a whole row of 128 lanes), every one aliased to its output; no operation
    copies an array of 32768 rows (a chunk's loop that took its key blocks of `[T, Hkv, D]` had the whole
    slot's slab laid out head-major before the loop, 67 MB a slab a chunk: PERF.md §6, PR 46); the plan
    stays under the chip's 15.75 GiB with 6.00 GB of weights and 6.59 GB of cache held; a decode step
    reads slabs and rings through the kernel `cached_attn`, one call a layer; and a chunk's full layers
    hold scores over one block of 1024 keys, never over every row of the slab."""
    from ray_tpu.models import laguna

    cfg, slots = _cell_cfg("laguna-s-2.1"), 24
    params = _shaped(jax.eval_shape(lambda k: laguna.init_params(cfg, k), jax.random.PRNGKey(0)), one_chip)
    caches = _shaped(jax.eval_shape(lambda: laguna.init_caches(cfg, slots, cfg.max_seq)), one_chip)
    vec, scalar = _operand((slots,), one_chip, jnp.int32), _operand((), one_chip, jnp.int32)

    def steps(n):
        def run(params, last, caches, lens, gate):
            def step(carry, _):
                last, caches, lens = carry
                logits, caches, stats = laguna.decode(params, cfg, last, caches, lens, gate)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32), caches, lens + 1), stats

            return jax.lax.scan(step, (last, caches, lens), None, length=n)
        return jax.jit(run, donate_argnums=(2,)).lower(params, vec, caches, vec, _operand((slots,), one_chip, jnp.bool_))

    if program == "rt_prefill_b1024":
        lowered = jax.jit(lambda p, t, c, s, o, n: laguna.prefill(p, cfg, t, c, s, o, n), donate_argnums=(2,)).lower(
            params, _operand((1, 1024), one_chip, jnp.int32), caches, scalar, scalar, scalar)
    else:
        lowered = steps(8 if program.endswith("n8") else 1)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    plan = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(caches))
    assert m.alias_size_in_bytes == held == 24 * (32768 * 8192 + 3 * 512 * 4096)
    assert 2 * laguna.num_params(cfg) + held < plan < 15.75 * 2**30, plan / 2**30
    text = compiled.as_text()
    layout = next(line for line in text.splitlines() if "entry_computation_layout" in line)
    assert "bf16[24,32768,8,128]{3,2,1,0:T(8,128)(2,1)}" in layout and "bf16[24,512,8,128]{3,2,1,0:T(8,128)(2,1)}" in layout
    copied = [tuple(int(n) for n in mm.group(1).split(",")) for line in text.splitlines()
              if (mm := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))]
    assert not [sh for sh in copied if 32768 in sh or 32768 * 8 in sh], "a slab's rows copied"
    shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\bf32\[([\d,]+)\]", text)}
    assert not {sh for sh in shapes if 32768 in sh and math.prod(sh) >= 32768 * 1024}, "scores over every row of a slab"
    kernel_calls = [line for line in text.splitlines() if re.search(r"%cached_attn(\.\d+)? = ", line)]
    written = _slab_updates(text, (24, 32768, 8, 128), (24, 512, 8, 128))
    if program == "rt_prefill_b1024":
        assert not kernel_calls and (8, 6, 1024, 1024) in shapes  # one block of keys' scores, 48 heads: XLA's fusions hold them (PERF.md §7)
        assert written  # the chunk's own rows
    else:
        assert len(kernel_calls) == 5 and all("kv_attn" in line for line in kernel_calls)
        assert not written, written  # the kernel writes a step's row into slab and ring (PERF.md §6, PR 47)
