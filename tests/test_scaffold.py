"""What holds the served blocks' shape (`ray_tpu/models/scaffold.py`, `models/latent.py`, `ops/moe.py`):
sibling modules are reused through public names only, the seeded trees are the ones the cells' routing
and `correct` were measured on, and the scaffold's statements do what the five blocks' prefills ask."""

import ast
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import scaffold

from tests import test_dots3, test_granite_hybrid, test_laguna, test_lfm2, test_pangu_moe, test_xing4

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_MODULES = {path.rsplit(".", 1)[1] for path in models.BLOCKS.values()}

# The dense block is outside the scaffold until its block table rewrites it (ROADMAP D2, D22): these reads
# of its private names are the debt as it stands, listed so that it cannot grow unseen.
DENSE_BLOCK_DEBT = {
    "ray_tpu/models/granite_hybrid.py": {"llama._attn_cached"},
    "ray_tpu/models/lfm2.py": {"llama._attn_cached", "llama._mlp"},
    "ray_tpu/llm/scheduler/spec.py": {"llama._forward_cached", "llama._scatter_slot_caches"},
}


def _private_reads(path: str) -> set:
    """`<block>._name` for every underscore name of a block's module (`models.BLOCKS`) that the file
    imports or reads through an alias of the module."""
    tree = ast.parse(open(os.path.join(ROOT, path)).read())
    own = os.path.basename(path)[:-3] if os.path.dirname(path).endswith("models") else None
    alias, found = {}, set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "ray_tpu.models":
                alias.update({a.asname or a.name: a.name for a in node.names if a.name in BLOCK_MODULES})
            elif node.module.startswith("ray_tpu.models.") and node.module.rsplit(".", 1)[1] in BLOCK_MODULES:
                block = node.module.rsplit(".", 1)[1]
                found.update(f"{block}.{a.name}" for a in node.names if a.name.startswith("_"))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("ray_tpu.models.") and a.name.rsplit(".", 1)[1] in BLOCK_MODULES and a.asname:
                    alias[a.asname] = a.name.rsplit(".", 1)[1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr.startswith("_") and not node.attr.startswith("__"):
            value = node.value
            if isinstance(value, ast.Name) and value.id in alias:
                found.add(f"{alias[value.id]}.{node.attr}")
            elif isinstance(value, ast.Attribute) and value.attr in BLOCK_MODULES and isinstance(value.value, ast.Name) \
                    and value.value.id == "models":
                found.add(f"{value.attr}.{node.attr}")
    return {name for name in found if name.split(".")[0] != own}


def _modules(*dirs: str) -> list:
    return sorted(os.path.relpath(os.path.join(at, name), ROOT) for d in dirs for at, _, names in os.walk(os.path.join(ROOT, d))
                  for name in names if name.endswith(".py"))


LAYERS = _modules("ray_tpu/models", "ray_tpu/ops")


@pytest.mark.parametrize("path", LAYERS + ["the rest of ray_tpu/"])
def test_no_module_reads_a_sibling_blocks_private_names(path):
    """A block's module offers its siblings public names or nothing: what `xing4` runs of `pangu_moe`
    (`attn_prefill`, `attn_decode`, `param_shapes`, `init_caches`, `split`) is that module's surface, and what
    three blocks share lives in `models/latent.py`, `models/scaffold.py` and `ops/moe.py`."""
    paths = [p for p in _modules("ray_tpu") if p not in LAYERS] if path.startswith("the rest") else [path]
    assert {p: reads for p in paths if (reads := _private_reads(p))} == {p: DENSE_BLOCK_DEBT[p] for p in paths if p in DENSE_BLOCK_DEBT}


def _tree_digest(tree: dict) -> str:
    """One hash over every leaf's path, type, shape and bits, in the tree's own order of paths sorted."""
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    h = hashlib.sha256()
    for path in sorted(flat):
        h.update(f"{path} {flat[path].dtype} {flat[path].shape} ".encode() + flat[path].tobytes())
    return h.hexdigest()[:16]


# `init_params(tiny(), PRNGKey(3))` and the same in bfloat16, recorded from PR 44's tree (each block's own copy of
# the builder): the cells' routing, expert hits and `correct` hang on the drawn weights, leaf for leaf.
SEEDED = {
    "dots3": (test_dots3.tiny, "b8d4a948c0476007", "4ba44d2936c7ee3d"),
    "granite_hybrid": (test_granite_hybrid.tiny, "77cb09e7321748de", "4b87edc547bdf66e"),
    "lfm2": (test_lfm2.tiny, "091ce23a1a9ef64c", "f879cf98e2e87100"),
    "pangu_moe": (test_pangu_moe.tiny, "e1c10c060cc7c018", "2f0c1608a565c1ed"),
    "xing4": (test_xing4.tiny, "0cd57067916bcf4e", "f453e21355bf4903"),
    "laguna": (test_laguna.tiny, "3e6055ec60509bb0", "133baa95e05de8e5"),  # recorded from PR 46's tree, the block's first
}


@pytest.mark.parametrize("block", sorted(SEEDED))
def test_a_seeded_tree_is_the_tree_the_cells_were_measured_on(block):
    tiny, f32, bf16 = SEEDED[block]
    cfg = tiny()
    module = models.block_module(cfg)
    assert _tree_digest(module.init_params(cfg, jax.random.PRNGKey(3))) == f32
    cfg = tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    tree = module.init_params(cfg, jax.random.PRNGKey(3))
    assert _tree_digest(tree) == bf16
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree)) == module.num_params(cfg)
    assert module.serving_params(cfg, tree) is tree  # held as drawn


def test_a_large_leaf_is_drawn_in_its_own_type_and_a_small_one_in_float32():
    key, big, small = jax.random.PRNGKey(1), (1 << 12, 1 << 12), (1 << 11, 1 << 12)
    want = (jax.random.normal(key, big, jnp.bfloat16) * 0.5).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(scaffold.normal(key, big, 0.5, jnp.bfloat16), np.float32), np.asarray(want, np.float32))
    want = (jax.random.normal(key, small, jnp.float32) * 0.5).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(scaffold.normal(key, small, 0.5, jnp.bfloat16), np.float32), np.asarray(want, np.float32))


def test_groups_and_leaves_are_keyed_by_their_place_and_drawn_as_the_block_says():
    shapes = {("embedding",): ((4, 3), "a"), ("layer_0", "x", "kernel"): ((3, 2), "b"), ("layer_0", "y"): ((2,), "c"),
              ("head", "kernel"): ((3, 4), "d")}
    seen = []

    def draw(key, shape, spec, dtype):
        seen.append(spec)
        return jax.random.normal(key, shape, dtype)

    key = jax.random.PRNGKey(9)
    tree = scaffold.tree_from_shapes(shapes, key, jnp.float32, draw)
    assert seen == ["a", "b", "c", "d"] and set(tree) == {"embedding", "layer_0", "head"} and set(tree["layer_0"]) == {"x", "y"}
    group = jax.random.fold_in(key, 1)  # `layer_0`, the second group; `y`, its second leaf
    np.testing.assert_array_equal(np.asarray(tree["layer_0"]["y"]), np.asarray(jax.random.normal(jax.random.fold_in(group, 1), (2,))))
    np.testing.assert_array_equal(np.asarray(tree["embedding"]),
                                  np.asarray(jax.random.normal(jax.random.fold_in(jax.random.fold_in(key, 0), 0), (4, 3))))
    assert scaffold.num_params(shapes) == 12 + 6 + 2 + 12
    ones = scaffold.tree_from_shapes({("n", "scale"): ((5,), 0), ("n", "bias"): ((5,), -1), ("n", "kernel"): ((400, 5), 400)}, key, jnp.float32)
    assert np.asarray(ones["n"]["scale"]).tolist() == [1.0] * 5
    assert 0.03 < float(jnp.std(ones["n"]["bias"])) < 0.3 and 0.04 < float(jnp.std(ones["n"]["kernel"])) < 0.06


@pytest.mark.parametrize("slot", [0, 2])
def test_a_chunk_works_on_one_slots_view_and_is_written_back_there(slot):
    caches = [(jnp.arange(3 * 4 * 2, dtype=jnp.float32).reshape(3, 4, 2), jnp.arange(3 * 5, dtype=jnp.bfloat16).reshape(3, 5)),
              (jnp.arange(3 * 2, dtype=jnp.float32).reshape(3, 2),)]
    view = jax.jit(scaffold.slot_view)(caches, jnp.int32(slot))
    assert [tuple(a.shape for a in c) for c in view] == [((1, 4, 2), (1, 5)), ((1, 2),)]
    np.testing.assert_array_equal(np.asarray(view[0][0][0]), np.asarray(caches[0][0][slot]))
    new = [tuple(a.astype(jnp.float32) + 100 for a in c) for c in view]  # a layer may leave another type: the cache's is kept
    out = jax.jit(scaffold.write_back)(caches, new, jnp.int32(slot))
    for c, o in zip(caches, out):
        for a, b in zip(c, o):
            assert b.dtype == a.dtype and b.shape == a.shape
            want = np.asarray(a, np.float32).copy()
            want[slot] += 100
            np.testing.assert_array_equal(np.asarray(b, np.float32), want)


@pytest.mark.parametrize("offset,total,row", [(0, 5, 4), (0, 8, 7), (8, 11, 2), (0, 20, 7), (16, 9, 0)],
                         ids=["inside", "the-last", "second-chunk", "not-yet", "already-past"])
def test_the_prompts_last_row_in_a_chunk(offset, total, row):
    x = jnp.arange(8 * 3, dtype=jnp.float32).reshape(1, 8, 3)
    got = jax.jit(scaffold.last_row)(x, jnp.int32(offset), jnp.int32(total))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x[0, row:row + 1]))


def test_counts_are_one_array_in_the_order_of_their_names_and_the_head_is_float32():
    got = scaffold.counts(("a", "b", "c"), c=jnp.int32(7), a=True)
    assert got.dtype == jnp.int32 and got.tolist() == [1, 0, 7]
    params = {"lm_head": {"kernel": jnp.ones((4, 6), jnp.bfloat16)}}
    logits = scaffold.head(params, jnp.ones((2, 4), jnp.bfloat16))
    assert logits.dtype == jnp.float32 and logits.shape == (2, 6) and float(logits[0, 0]) == 4.0
