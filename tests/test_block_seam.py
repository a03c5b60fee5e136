"""The seam between the serve engine and a model block (`ray_tpu/models/__init__.py`).

This file is itself a block the engine has never seen: an embedding, one cached row a
slot (the sum of the embeddings of every token the slot has taken), a head. It is
registered under a name of its own and served by `DecodeEngine` as the two real blocks
are, with no line of `llm/_engine.py` knowing of it; and what a block does not list in
`SUPPORTS` is refused by one function, by the block's name.
"""

import importlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models.transformer import ModelConfig, Transformer, get_config

# -- the block: what `models/__init__.py` says a block's module offers -----------------

SUPPORTS = frozenset()


def init_params(cfg, key):
    k_e, k_h = jax.random.split(key)
    return {"embedding": jax.random.normal(k_e, (cfg.vocab_size, cfg.hidden), jnp.float32),
            "head": jax.random.normal(k_h, (cfg.hidden, cfg.vocab_size), jnp.float32)}


def serving_params(cfg, params):
    return params  # drawn in the type its two products read


def init_caches(cfg, slots, max_seq):
    return [(jnp.zeros((slots, cfg.hidden), jnp.float32),)]


def init_stats(cfg):
    return (jnp.zeros((), jnp.int32),)  # tokens taken


def report(cfg, total, window):
    return {"onerow": {"tokens": int(total[0]), "window": int(window[0])}}


def prefill(params, cfg, tokens, caches, slot, offset, total_len, lora, adapter_id):
    ((rows,),) = caches
    valid = jnp.arange(tokens.shape[1]) < total_len - offset
    taken = jnp.sum(jnp.where(valid[:, None], params["embedding"][tokens[0]], 0.0), axis=0)
    row = jnp.where(offset == 0, 0.0, rows[slot]) + taken  # a new prompt starts the slot's row anew
    return row @ params["head"], [(rows.at[slot].set(row),)], (jnp.sum(valid, dtype=jnp.int32),)


def decode(params, cfg, last_token, caches, lens, gate, lora, adapter_ids):
    ((rows,),) = caches
    rows = rows + jnp.where(gate[:, None], params["embedding"][last_token], 0.0)
    return rows @ params["head"], [(rows,)], (jnp.sum(gate, dtype=jnp.int32),)


# -- served through the seam -----------------------------------------------------------


def _cfg(block="onerow", **kw):
    return ModelConfig(block=block, vocab_size=50, hidden=16, n_layers=1, layer_types=("row",), max_seq=64,
                       dtype=jnp.float32, scan_layers=False, remat=False, **kw)


@pytest.fixture
def onerow(monkeypatch):
    monkeypatch.setitem(models.BLOCKS, "onerow", __name__)
    cfg = _cfg()
    assert models.block_module(cfg) is importlib.import_module(__name__)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _greedy_plain(params, prompt, n):
    emb, head = np.asarray(params["embedding"]), np.asarray(params["head"])
    row, out = emb[prompt].sum(axis=0), []
    for _ in range(n):
        out.append(int(np.argmax(row @ head)))
        row = row + emb[out[-1]]
    return out


def _generate(engine, prompt, n):
    from ray_tpu.llm import SamplingParams

    out, done = [], threading.Event()
    engine.submit(prompt, SamplingParams(max_tokens=n), lambda tok, fin: (out.append(tok), fin and done.set()))
    assert done.wait(120)
    return out


@pytest.mark.parametrize("multi_step", [1, 8], ids=["single-steps", "multi-step"])
def test_a_block_the_engine_has_never_seen_is_served(onerow, multi_step):
    from ray_tpu.llm import DecodeEngine

    cfg, params = onerow
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(0, 50, size=(n,))] for n in (5, 40, 23, 17, 31)]
    # 16-token chunks, so three of the prompts are prefilled in two or three of them; five
    # requests over two slots, so a slot takes over a longer prompt's row
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, token_budget=16, multi_step=multi_step)
    try:
        got = [None] * len(prompts)
        threads = [threading.Thread(target=lambda i=i: got.__setitem__(i, _generate(engine, prompts[i], 9)))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == [_greedy_plain(params, p, 9) for p in prompts]
        stats = engine.scheduler_stats()
        assert stats["model"]["block"] == "onerow" and "experts" not in stats
        # every prompt token once, and every generated token but a request's last, which is never fed
        assert stats["onerow"]["tokens"] == sum(len(p) for p in prompts) + len(prompts) * 8
        programs = {str(row["key"]) for row in stats["programs"]["programs"]}
        assert any("decode_multi" in k for k in programs) == (multi_step > 1)
    finally:
        engine.shutdown()


def _asked(cfg, what):
    from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams, load_model
    from ray_tpu.llm.kvcache import PrefixCacheManager
    from ray_tpu.llm.pd_disagg import DecodeServer, PrefillServer

    build = lambda **kw: DecodeEngine(cfg, {}, num_slots=1, max_seq=64, decode_loop=False, **kw)  # noqa: E731
    config = lambda **kw: LLMConfig(model_id="onerow", model_config=cfg, **kw)  # noqa: E731
    return {
        "lora": lambda: build(lora_config={"max_loras": 2, "rank": 4}),
        "speculation": lambda: build(spec_config={"method": "ngram"}),
        "tensor-parallel": lambda: build(tp=2),
        "prefix-cache": lambda: build(prefix_cache=PrefixCacheManager(4, 1 << 20, name="refused")),
        "pd-prefill-server": lambda: PrefillServer(config()),
        "pd-decode-server": lambda: DecodeServer(config()),
        "pd-submit-prefilled": lambda: build().submit_prefilled(
            np.zeros((1, 2, 4, 4, 2), np.float32), 4, np.zeros((50,), np.float32), SamplingParams(), lambda *_: None),
        "pd-prefill-detached": lambda: build().prefill_detached([1, 2, 3]),
        "train-step": lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
        "checkpoint": lambda: load_model(config(checkpoint_path="/nonexistent")),
    }[what]


@pytest.mark.parametrize("what", ["lora", "speculation", "tensor-parallel", "prefix-cache", "pd-prefill-server",
                                  "pd-decode-server", "pd-submit-prefilled", "pd-prefill-detached", "train-step",
                                  "checkpoint"])
def test_what_the_unseen_block_does_not_list_is_refused_by_its_name(onerow, what):
    with pytest.raises(NotImplementedError, match=r"block 'onerow'"):
        _asked(onerow[0], what)()


@pytest.mark.parametrize("feature", sorted(models.FEATURES))
@pytest.mark.parametrize("block", ["llama", "dots3", "granite_hybrid", "lfm2", "pangu_moe", "xing4", "laguna", "onerow"])
def test_one_function_refuses_what_a_block_does_not_list(onerow, block, feature):
    cfg = get_config("test-tiny") if block == "llama" else _cfg(block)
    if feature in models.block_module(cfg).SUPPORTS:
        models.require(cfg, feature)
    else:
        with pytest.raises(NotImplementedError, match=re.escape(models.FEATURES[feature]) + rf".* block '{block}'"):
            models.require(cfg, feature)


@pytest.mark.parametrize("block", ["llama", "dots3", "granite_hybrid", "lfm2", "pangu_moe", "xing4", "laguna", "onerow"])
def test_every_block_offers_what_the_seam_names(onerow, block):
    """Each function the seam's docstring lists for every block is in the block's module, the
    engine's `serving_params` among them; and a tree that is already in the served type comes
    back from it as the tree it was, leaf for leaf (no copy, no second tree)."""
    cfg = get_config("test-tiny") if block == "llama" else _cfg(block)
    module = models.block_module(cfg)
    for name in ("init_params", "serving_params", "init_caches", "prefill", "decode", "init_stats", "report"):
        assert re.search(rf"^    {name}\(", models.__doc__, re.M), name
        assert callable(getattr(module, name)), name
    assert isinstance(module.SUPPORTS, frozenset)
    leaves = {"kernel": jnp.ones((4, 4), cfg.dtype), "scale": jnp.ones((4,), jnp.float32)}
    tree = {"embedding": jnp.ones((8, 4), cfg.dtype), "layer_0": {"mlp": {"up": dict(leaves)}}}
    served = module.serving_params(cfg, tree)
    assert served is tree
    assert served["embedding"] is tree["embedding"] and served["layer_0"]["mlp"]["up"]["kernel"] is leaves["kernel"]


def test_an_unknown_block_is_named_with_those_known():
    with pytest.raises(ValueError, match=r"unknown block 'nosuch'; known: \['dots3', 'granite_hybrid', 'laguna', 'lfm2', 'llama', 'pangu_moe', 'xing4'\]"):
        models.block_module(_cfg("nosuch"))
