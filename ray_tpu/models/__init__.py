"""Which module runs a model's block, and what that block can take.

A block's module is a set of pure functions over its parameter tree, and it is all the
serve engine knows of a model (`llm/_engine.py` calls nothing else):

    SUPPORTS          frozenset of FEATURES the block can take
    init_params(cfg, key)                  the tree served at random weights
    serving_params(cfg, params)            the tree the engine holds: `params` with every leaf that the
                                           block's programs read only through a cast to `cfg.dtype` held
                                           in `cfg.dtype` (`cast_leaves`), so that no program reads or
                                           converts the wider bytes; the tree it was given where
                                           `init_params` already draws in the served type. The dicts
                                           of `params` are the engine's own and may be written into
    init_caches(cfg, slots, max_seq)       per layer a tuple of [slots, ...] arrays
    prefill(params, cfg, tokens, caches, slot, offset, total_len, lora, adapter_id)
                                           one chunk of one slot -> (last logits [V], caches, stats)
    decode(params, cfg, last_token, caches, lens, gate, lora, adapter_ids)
                                           one token for every slot -> (logits [B, V], caches, stats)
    init_stats(cfg)                        zeros shaped like `stats`, a tuple of int32 arrays
                                           (empty where the block counts nothing)
    report(cfg, total, window)             what scheduler_stats() says of those counts
                                           since the start and since the last report

    LAYER_TYPES       () where every layer is of one kind; a block without it names each
                      layer's kind in `ModelConfig.layer_types`, one per layer

and, with the feature that needs them: `verify` ("speculation"), `gather_rows` and
`attach_rows` ("prefix_cache", "pd"), `prefill_detached` and `prefill_detached_suffix`
("pd"); `models/llama.py` has them all. `lora` and the adapter ids are None and zeros
for a block that does not list "lora".

A new block writes its own layers, its cache and its counts, and takes the rest by public name
(a block reads no underscore name of another: `tests/test_scaffold.py`):

    models/scaffold.py   tree_from_shapes(param_shapes(cfg), key, dtype[, draw]) for `init_params`
                         (the block says how one leaf is drawn), num_params, as_drawn (`serving_params`);
                         slot_view, write_back, last_row for `prefill`; counts for its stats; head
    models/latent.py     the latent sub-layer's row c_kv | k_r: attn_dims, latents, put_row, key_block
    ops/moe.py           swiglu, and routed_experts: router, grouped experts, shared expert
    ops/                 what reads a cache on the chip: attention.py (KV slabs, through
                         `llama._attn_cached`, and `laguna`'s slabs and rings through `cached_attention`,
                         which writes a step's rows too; `put_gated`, the write where no kernel runs),
                         latent_attention.py, ssd.py, hyper_connection.py
"""

from __future__ import annotations

import importlib

# block name (`ModelConfig.block`) -> the module that runs it, imported when asked for
# (`models/transformer.py` asks too, and the modules import it).
BLOCKS = {
    "llama": "ray_tpu.models.llama",
    "dots3": "ray_tpu.models.dots3",
    "granite_hybrid": "ray_tpu.models.granite_hybrid",
    "lfm2": "ray_tpu.models.lfm2",
    "pangu_moe": "ray_tpu.models.pangu_moe",
    "xing4": "ray_tpu.models.xing4",
    "laguna": "ray_tpu.models.laguna",
}

# What a caller may ask of a block, and how the refusal names the caller.
FEATURES = {
    "lora": "LoRA (lora_config)",
    "speculation": "speculative decoding (spec_config)",
    "tp": "tensor parallelism (llm/tp.py)",
    "prefix_cache": "the prefix cache (llm/kvcache/)",
    "pd": "PD disaggregation (llm/pd_disagg.py)",
    "train": "the flax Transformer (the train step)",
    "checkpoint": "loading a checkpoint (checkpoint_path)",
}


def cast_leaves(tree: dict, dtype, cast_on_read) -> dict:
    """`tree` (nested dicts of arrays, the engine's own: `parallel.mesh.unbox` builds them) with
    every leaf whose path `cast_on_read(path)` names held in `dtype`: what a block's
    `serving_params` is made of. Written into `tree`'s own dicts, one leaf at a time and each
    waited for, so that where nothing else holds the wider tree each wide leaf is free before the
    next is cast and start-up never holds both trees whole. A leaf already in `dtype` stays the
    very array."""
    import jax

    def walk(node, path):
        for key, leaf in node.items():
            if isinstance(leaf, dict):
                walk(leaf, path + (key,))
            elif leaf.dtype != dtype and cast_on_read(path + (key,)):
                node[key] = jax.block_until_ready(leaf.astype(dtype))  # raylint: disable=RL603 (start-up, a wait a leaf: the wide leaf must be free before the next cast)

    walk(tree, ())
    return tree


def block_module(cfg):
    """The module that runs `cfg.block`."""
    if cfg.block not in BLOCKS:
        raise ValueError(f"unknown block {cfg.block!r}; known: {sorted(BLOCKS)}")
    return importlib.import_module(BLOCKS[cfg.block])


def names_its_layers(cfg) -> bool:
    """Whether `cfg.layer_types` has to name each layer's kind: every block's does, but one
    whose module says its layers are all of one kind (`LAYER_TYPES = ()`)."""
    return getattr(block_module(cfg), "LAYER_TYPES", None) != ()


def require(cfg, feature: str) -> None:
    """Refuse, by the block's name, a feature its module does not list, rather than run
    one block's code over another's tree (PERF.md §7: what the system cannot run yet)."""
    if feature not in block_module(cfg).SUPPORTS:
        raise NotImplementedError(
            f"{FEATURES[feature]} does not support block {cfg.block!r} yet: it runs the dense llama-family "
            f"block only; block {cfg.block!r} is served by LLMServer / DecodeEngine on one device")
