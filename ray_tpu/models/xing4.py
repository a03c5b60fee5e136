"""The `xing4` block on the serve path: a residual of `hc_mult` streams mixed by
manifold-constrained hyper-connections round every sub-layer, dense latent attention under a
YaRN-scaled rotary, sigmoid-routed experts chosen with a selection bias beside a shared one
(`ModelConfig(block="xing4")`; XingChen-AGI/Xing4.0-29B-A4B, `model_type` xing4_0).

One set of pure functions over one parameter tree, behind the seam every block is served
through (`models/__init__.py`). Per token, with X the `hc_mult` streams of width D, kept as
one row of hc_mult x D values (`ops/hyper_connection.py` has the equations of the three `hc`
functions and says why the layout):

    X      = the token's embedding in every stream
    in each layer, for F = attention and then the MLP or expert layer, each with its own Phi, alpha, b:
      coef = mapping(X)                             H_pre | H_post | H_res, 20 Sinkhorn steps
      h    = norm_F(mix_in(X, coef))                H_pre X, then the sub-layer's RMSNorm
      X    = mix_out(X, F(h), coef)                 H_res X + H_post^T F(h)
    logits = norm_final(sum of the streams) W_head

Attention is `pangu_moe`'s, function for function (`attn_prefill`, `attn_decode`: the latents, the
slab `[slots, max_seq, 640]`, the chunk loop, the decode step over the slab through the kernel
`latent_attn`), with the rotary's frequencies and the scores' scale read from `rope_scaling`
(`models/latent.py:attn_dims`). A cached row is a function of the sub-layer's input, the mixture,
so the cache knows nothing of the streams. The expert layer is `pangu_moe`'s call of
`ops/moe.py:routed_experts` with a selection bias in the tree (`router/bias`: `noaux_tc`, the bias
chooses and does not weigh) and every expert of a layer held. Every layer
is of one kind, so the block takes no `layer_types` (`LAYER_TYPES`). The multi-token-prediction
module is not loaded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import pangu_moe, scaffold
from ray_tpu.models.pangu_moe import LATENT_COUNTS, split
from ray_tpu.models.transformer import ModelConfig, _rmsnorm
from ray_tpu.ops import hyper_connection as hc
from ray_tpu.ops.moe import routed_experts, swiglu

# Served by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7): a prefix
# hit would attach latent rows, a draft needs the multi-token-prediction module and a program
# that returns the last hidden state, the train step a backward pass through the streams, latent
# attention and the expert layer, several chips an exchange of tokens between the experts' holders.
SUPPORTS = frozenset()
LAYER_TYPES = ()  # every layer is of one kind: `ModelConfig.layer_types` names none (`models/__init__.py`)

SUBLAYERS = ("attn", "mlp")  # a layer's two, each with a hyper-connection (`<name>_hc`) and a norm (`<name>_norm`)
# What a program counts of the expert layers before the pairs each expert took (`init_stats`): `pangu_moe`'s
# two, then the experts that took a pair and the expert layers run, in the decode programs alone (`lfm2`'s names).
EXPERT_COUNTS = ("pairs_routed", "pairs_held", "decode_experts_hit", "decode_layer_steps")


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, fan_in)}: `pangu_moe.param_shapes` (whose attention and
    expert layer this block runs; fan_in 0 ones, -1 normal at 0.1, else normal at 1 / sqrt(fan_in))
    without the two post-norms, with a selection bias beside every router and a hyper-connection
    for each of a layer's sub-layers. Its projection is drawn at 1 / sqrt(hc_mult x hidden), its
    gains are 1 and its biases standard normal, so that the part of a coefficient that depends on
    the input is as large as the part that does not and the mixing matrix is far from the identity
    and from the uniform one: a program that dropped the projection would not pass for this one."""
    n, out = cfg.hc_mult, {}
    for path, spec in pangu_moe.param_shapes(cfg).items():
        if path[1:2] in (("attn_post_norm",), ("mlp_post_norm",)):
            continue  # a sub-layer's output joins the streams through its hyper-connection, unnormed
        out[path] = spec
        if path[-2:] == ("router", "kernel"):
            out[path[:-1] + ("bias",)] = ((cfg.n_routed_experts_total,), -1)
    for i in range(cfg.n_layers):
        for sub in SUBLAYERS:
            part = (f"layer_{i}", sub + "_hc")
            out[part + ("phi",)] = ((hc.coefficients(n), n * cfg.hidden), n * cfg.hidden)
            out[part + ("alpha",)] = ((3,), 0)
            out[part + ("bias",)] = ((hc.coefficients(n),), 1)
    return out


def num_params(cfg: ModelConfig) -> int:
    return scaffold.num_params(param_shapes(cfg))


serving_params = scaffold.as_drawn


def init_params(cfg: ModelConfig, key):
    """The tree at seeded random weights in `cfg.param_dtype` (`scaffold.tree_from_shapes`)."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype)


# -- the cache and the counts --------------------------------------------------------

init_caches = pangu_moe.init_caches  # one latent slab a layer, `[slots, max_seq, 640]`: the streams keep nothing


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: the expert layers' (`EXPERT_COUNTS`, then the pairs each
    expert took), the slabs' (`pangu_moe.LATENT_COUNTS`, split) and the hyper-connections' (valid
    tokens times the sub-layers that mixed them; padding and gated-off slots are not counted)."""
    return (jnp.zeros((len(EXPERT_COUNTS) + cfg.n_routed_experts,), jnp.int32),
            jnp.zeros((2 * len(LATENT_COUNTS),), jnp.int32), jnp.zeros((1,), jnp.int32))


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["experts"]` and `["latent"]` as `pangu_moe.report` gives them, the decode
    programs' experts hit and expert layers run beside the pairs, and `["hc"]`: token-sub-layers
    mixed since the engine started and, under `window`, since the last report."""
    n = len(EXPERT_COUNTS)
    as_pangu = lambda counts: (np.concatenate([counts[0][:2], counts[0][n:]]), counts[1])  # noqa: E731
    out = pangu_moe.report(cfg, as_pangu(total), as_pangu(window))
    for into, counts in ((out["experts"], total), (out["experts"]["window"], window)):
        into.update({name: int(counts[0][j]) for j, name in enumerate(EXPERT_COUNTS) if j >= 2})
    out["hc"] = {"streams": cfg.hc_mult, "sinkhorn_iters": cfg.hc_sinkhorn_iters,
                 "token_sublayers": int(total[2][0]), "window": {"token_sublayers": int(window[2][0])}}
    return out


# -- the layers ------------------------------------------------------------------------


def _hyper(layer, sub: str, x, cfg: ModelConfig, f):
    """One sub-layer round the streams. x: [B, S, hc_mult x D]; `f(h) -> (y, aux)` over the
    normed mixture h [B, S, D]. Returns (the streams after the write-back, aux). The three `hc`
    scopes are siblings of the sub-layer's own, never inside one."""
    p, n, rows = layer[sub + "_hc"], cfg.hc_mult, x.reshape(-1, x.shape[-1])
    with jax.named_scope("hc"):
        with jax.named_scope("map"):
            coef = hc.mapping(rows, p["phi"], p["alpha"], p["bias"], n=n, iters=cfg.hc_sinkhorn_iters,
                              eps=cfg.hc_eps, clamp=(cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
        with jax.named_scope("pre"):
            h = hc.mix_in(rows, coef, n=n).reshape(x.shape[:2] + (-1,))
    with jax.named_scope(sub + "_norm"):
        h = _rmsnorm(h, layer[sub + "_norm"]["scale"], cfg.norm_eps)
    with jax.named_scope(sub):
        y, aux = f(h)
    with jax.named_scope("hc"), jax.named_scope("post"):
        return hc.mix_out(rows, y.reshape(-1, y.shape[-1]), coef, n=n).reshape(x.shape), aux


def _forward(params, cfg: ModelConfig, tokens, valid, attend, decoding: bool):
    """The layers round `attend(i, layer_params, normed) -> (out, cache_i)`. Returns (hidden after
    the final norm, caches, the expert layers' counts (`EXPERT_COUNTS`, then pairs by expert), the
    hyper-connections' count [1])."""
    n = cfg.hc_mult
    with jax.named_scope("embedding"):
        e = params["embedding"][tokens].astype(cfg.dtype)
        x = jnp.concatenate([e] * n, axis=-1)
    caches, counts, hit = [], jnp.zeros((cfg.n_routed_experts,), jnp.int32), jnp.zeros((), jnp.int32)
    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            x, cache = _hyper(layer, "attn", x, cfg, lambda h, i=i, layer=layer: attend(i, layer["attn"], h))
            caches.append(cache)
            if i < cfg.first_k_dense:
                x, _ = _hyper(layer, "mlp", x, cfg, lambda h, layer=layer: (swiglu(layer["mlp"], h), None))
            else:
                x, c = _hyper(layer, "mlp", x, cfg, lambda h, layer=layer: routed_experts(
                    layer["mlp"], h, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                    eps=pangu_moe.ROUTING_EPS, first=cfg.first_expert))
                counts, hit = counts + c, hit + jnp.sum(c > 0, dtype=jnp.int32)
    with jax.named_scope("final_norm"):
        D = cfg.hidden
        x = sum(x[..., i * D:(i + 1) * D].astype(jnp.float32) for i in range(n)).astype(cfg.dtype)
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    n_moe, n_valid = scaffold.num_expert_layers(cfg), jnp.sum(valid, dtype=jnp.int32)
    named = [n_valid * (cfg.experts_per_token * n_moe), jnp.sum(counts), hit * decoding, jnp.int32(n_moe * decoding)]
    mixed = n_valid * (len(SUBLAYERS) * cfg.n_layers)
    return x, caches, jnp.concatenate([jnp.stack(named).astype(jnp.int32), counts]), mixed[None]


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len, lora=None, adapter_id=None):
    """The engine's prefill program for this block (`pangu_moe.prefill` round four streams).
    tokens: [1, S] right-padded, the chunk at positions offset + [0, S) of a prompt of `total_len`
    tokens, into slot `slot`. Returns (logits of the prompt's last token if it is in this chunk,
    caches, stats)."""
    S = tokens.shape[1]
    n_valid = jnp.minimum(S, total_len - offset)
    view = scaffold.slot_view(caches, slot)
    x, new, experts, mixed = _forward(params, cfg, tokens, jnp.arange(S)[None, :] < n_valid,
                                      lambda i, p, normed: pangu_moe.attn_prefill(p, normed, view[i], offset, cfg),
                                      decoding=False)
    caches = scaffold.write_back(caches, new, slot)
    logits = scaffold.head(params, scaffold.last_row(x, offset, total_len))[0]
    return logits, caches, (experts, jnp.zeros((2 * len(LATENT_COUNTS),), jnp.int32), mixed)


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots with
    `gate` write their rows, are routed and are counted. Returns (logits [B, V], caches, stats)."""
    read = []

    def attend(i, p, normed):
        out, cache, rows = pangu_moe.attn_decode(p, normed, caches[i], lens, gate, cfg)
        read.append(rows)
        return out, cache

    x, new, experts, mixed = _forward(params, cfg, last_token[:, None], gate[:, None], attend, decoding=True)
    visible = jnp.sum(jnp.where(gate, lens + 1, 0))
    return scaffold.head(params, x[:, 0]), new, (experts, jnp.concatenate([split(visible), split(read[0])]), mixed)
