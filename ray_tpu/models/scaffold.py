"""What a served block needs that is not its layers, written once: the seeded tree, a prefill
chunk's meeting with the slots' caches, a program's counts, the untied head
(`models/__init__.py` lists them beside the seam). The gated expert and the routed-expert layer
are `ops/moe.py`'s (`swiglu`, `routed_experts`), the latent sub-layer's row `models/latent.py`'s.
The dense block (`models/llama.py`) serves the flax Transformer's own tree and meets its slots
by a gather and `_scatter_slot_caches`: it uses nothing of this (ROADMAP D22), and no block has
to (`tests/test_block_seam.py` serves one that does not).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import ModelConfig, _dense

# -- the tree ------------------------------------------------------------------------


def normal(key, shape, std: float, dtype):
    """A kernel: normal(0, std) in `dtype`."""
    # large leaves are drawn in their own type: a float32 draw of an expert stack is 1 GB
    draw = dtype if math.prod(shape) >= (1 << 24) else jnp.float32
    return (jax.random.normal(key, shape, draw) * std).astype(dtype)


def draw_by_fan_in(key, shape, fan_in, dtype):
    """One leaf of a tree whose `param_shapes` gives fan-ins: 0 marks a norm scale (ones), a
    negative one a bias drawn small (0.1), and a kernel is normal(0, 1 / sqrt(fan_in)), so that
    a product of a unit-variance input has unit variance."""
    if fan_in == 0:
        return jnp.ones(shape, dtype)
    return normal(key, shape, 0.1 if fan_in < 0 else 1.0 / math.sqrt(fan_in), dtype)


def tree_from_shapes(shapes: dict, key, dtype, draw=draw_by_fan_in):
    """A tree of seeded random leaves from {path tuple: (shape, spec)} (`param_shapes`), each
    `draw(key, shape, spec, dtype)`, made on the device one top-level group (a layer, the
    embedding, the head) a program, so that layers of one kind share theirs and no second copy
    of a layer's experts is ever alive. A group's key is the tree's folded with the group's
    place, a leaf's the group's folded with the leaf's: a cell's weights hang on both orders."""
    groups: dict = {}
    for path, spec in shapes.items():
        groups.setdefault(path[0], {})[path[1:]] = spec
    tree = {}
    for n, (name, leaves) in enumerate(groups.items()):
        made = _init_group(jax.random.fold_in(key, n), tuple(leaves.items()), dtype, draw)
        for path, leaf in zip(leaves, made):
            node = tree
            for part in (name,) + path[:-1]:
                node = node.setdefault(part, {})
            if path:
                node[path[-1]] = leaf
            else:
                tree[name] = leaf
    return tree


def _init_leaves(key, leaves: tuple, dtype, draw):
    return [draw(jax.random.fold_in(key, n), shape, spec, dtype) for n, (_, (shape, spec)) in enumerate(leaves)]


_init_group = jax.jit(_init_leaves, static_argnums=(1, 2, 3))


def num_params(shapes: dict) -> int:
    return sum(math.prod(shape) for shape, _ in shapes.values())


def as_drawn(cfg: ModelConfig, params):
    """`serving_params` of a block configured with `param_dtype` the served type: the tree the
    engine holds (`models/__init__.py`) is the tree as drawn."""
    return params


def num_expert_layers(cfg: ModelConfig) -> int:
    return cfg.n_layers - cfg.first_k_dense


# -- a prefill chunk and the slots ---------------------------------------------------


def slot_view(caches: list, slot) -> list:
    """Slot `slot`'s `[1, ...]` view of every cache array: what a prefill chunk works on."""
    return [tuple(jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0) for a in c) for c in caches]


def write_back(caches: list, new: list, slot) -> list:
    """The caches with the chunk's views, as the layers left them, in slot `slot`."""
    return [tuple(jax.lax.dynamic_update_slice_in_dim(a, b.astype(a.dtype), slot, axis=0)
                  for a, b in zip(c, n)) for c, n in zip(caches, new)]


def last_row(x, offset, total_len):
    """x: [1, S, D], a chunk at positions offset + [0, S) -> [1, D]: the prompt's last token's
    row where it is in this chunk (some row of it where it is not: nobody reads those logits)."""
    S = x.shape[1]
    return jax.lax.dynamic_slice_in_dim(x[0], jnp.clip(total_len - 1 - offset, 0, S - 1), 1, axis=0)


# -- counts and the head -------------------------------------------------------------


def counts(names: tuple, **named):
    """One int32 array in the order of `names`, 0 where a program counts nothing under a name."""
    return jnp.stack([jnp.asarray(named.get(name, 0), jnp.int32) for name in names])


def head(params, x):
    """x: [..., D] -> logits [..., V] float32 through the untied `lm_head`."""
    with jax.named_scope("lm_head"):
        return _dense(x, params["lm_head"]["kernel"]).astype(jnp.float32)
