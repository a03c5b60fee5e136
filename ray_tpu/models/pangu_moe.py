"""The `pangu_moe` block on the serve path: dense latent attention over the whole cache,
a norm before and after every sub-layer, sigmoid-routed experts beside a shared one
(`ModelConfig(block="pangu_moe")`; FreedomIntelligence/openPangu-Ultra-MoE-718B,
`model_type` pangu_ultra_moe).

One set of pure functions over one parameter tree, behind the seam every block is served
through (`models/__init__.py`). `forward_plain` is the repo's plain reference (whole
sequence, float32, no cache, no blocks) that the tests hold the cached paths to.

Per layer, every norm an RMSNorm with its own gain:

    a      = norm_in(x)
    c_q    = norm_qa(a W_qa);  q = c_q W_qb -> H x [nope | rope], rotary on rope
    [c_kv | k_r] = a W_kva;  c_kv = norm_kva(c_kv);  k_r rotated, one for all heads
    [k_nope | v] = c_kv W_kvb
    o_head[t] = softmax_{s <= t}((q_nope[t] . k_nope[s] + q_rope[t] . k_r[s]) / sqrt(nope + rope)) v[s]
    x      = x + norm_post_attn(concat_heads(o_head) W_o)          every s: no selection, no window
    m      = norm_pre_mlp(x)
    f      = SwiGLU(m) at `mlp_dim`                                layers under `first_k_dense`
    f      = sum_k w_k E_{i_k}(m) + E_shared(m)                    every other layer (`ops/moe.py`):
             s = sigmoid(m W_r) in float32; i = top-k of s (no selection bias, one group);
             w = s_i / (sum_i s_i + 1e-20) x `routed_scaling_factor`; the experts held here only
    x      = x + norm_post_mlp(f)
    logits = norm_final(x) W_head

The two post-norms are the published `sandwich_norm`: this block is the one that has them.
Every layer is of one kind, so the block takes no `layer_types` (`LAYER_TYPES`). The latent
projections, the chunk loop's block size and the gated row write are `models/latent.py`'s (the
row is `dots3`'s, with no rescale of the latents: `mla_rescale` must be off).

The cache, one array a layer: `[slots, max_seq, 640]` in `cfg.dtype`, a token's row
c_kv (after its norm) | k_r (after rotary) | zeros: 576 values kept in five whole rows of
128 lanes (`ops/latent_attention.py:slab_width`), so that the slab is row-major on the chip
and no program copies it into another layout. A prefill chunk expands keys and values from
the rows block of keys by block of keys under the causal mask; a decode step folds W_kvb
into the query and the output and runs over the slab itself: on the TPU the Pallas kernel
`latent_attn`, which reads of each slot the row blocks up to its length; elsewhere two
products over every row and a mask.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models import scaffold
from ray_tpu.models.latent import attn_dims, key_block, latents, put_row
from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm, _rope
from ray_tpu.ops import attention, latent_attention as la
from ray_tpu.ops.moe import routed_experts, sigmoid_routing, swiglu

# Served by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7): a
# prefix hit would attach latent rows, a draft needs the multi-token-prediction module and a
# program that returns the last hidden state, the train step latent attention's and the
# expert layer's backward pass, several chips an exchange of tokens between the experts' holders.
SUPPORTS = frozenset()
LAYER_TYPES = ()  # every layer is of one kind: `ModelConfig.layer_types` names none (`models/__init__.py`)

ROUTING_EPS = 1e-20  # under the chosen scores' sum, as the published code has it
EMBEDDING_FAN_IN = 2500  # the embedding is drawn at 1 / sqrt(2500) = 0.02

# What a program counts beside the expert layers' pairs (`init_stats`): rows of a layer's slab
# visible to a decode step's queries (the gated slots' lengths) and rows its products ran over,
# each as (thousand-and-twenty-fours, remainder) so that neither wraps an int32 in a window:
# 16 slots of 32768 rows add at most 512 and 1023 a step.
LATENT_COUNTS = ("rows_visible", "rows_read")
_SPLIT = 1024


# -- sizes ---------------------------------------------------------------------------


def dims(cfg: ModelConfig) -> dict:
    """Heads, latent ranks, head sizes and rope base (`latent.attn_dims`)."""
    if cfg.mla_rescale:
        raise ValueError("block 'pangu_moe' does not rescale its latents: set mla_rescale=False")
    return attn_dims(cfg)


def row_width(cfg: ModelConfig) -> int:
    """Values a cached token keeps in a layer: c_kv | k_r."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, fan_in)}; fan_in 0 marks a norm gain (ones). Kernels
    are normal(0, 1 / sqrt(fan_in)): a product of a unit-variance input has unit variance, and
    an attention score (192 products of unit variance over sqrt(192)) has standard deviation 1."""
    D, d, out = cfg.hidden, dims(cfg), {}
    H = d["heads"]
    out["embedding",] = ((cfg.vocab_size, D), EMBEDDING_FAN_IN)
    for i in range(cfg.n_layers):
        L = f"layer_{i}"
        for name in ("attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm"):
            out[L, name, "scale"] = ((D,), 0)
        a = (L, "attn")
        out[a + ("q_a", "kernel")] = ((D, d["q_rank"]), D)
        out[a + ("q_norm", "scale")] = ((d["q_rank"],), 0)
        # two axes, heads and their [nope | rope] together: a last axis of 192 is one and a half rows of
        # 128 lanes, kept as two, and every program would copy the matrix into the product's shape first
        out[a + ("q_b", "kernel")] = ((d["q_rank"], H * (d["nope"] + d["rope"])), d["q_rank"])
        out[a + ("kv_a", "kernel")] = ((D, d["kv_rank"] + d["rope"]), D)
        out[a + ("kv_norm", "scale")] = ((d["kv_rank"],), 0)
        out[a + ("kv_b", "kernel")] = ((d["kv_rank"], H, d["nope"] + d["v"]), d["kv_rank"])
        out[a + ("o", "kernel")] = ((H, d["v"], D), H * d["v"])
        m = (L, "mlp")
        if i < cfg.first_k_dense:
            F = cfg.mlp_dim
            out[m + ("gate", "kernel")] = ((D, F), D)
            out[m + ("up", "kernel")] = ((D, F), D)
            out[m + ("down", "kernel")] = ((F, D), F)
        else:
            E, F = cfg.n_routed_experts, cfg.moe_mlp_dim
            Fs = F * cfg.n_shared_experts
            out[m + ("router", "kernel")] = ((D, cfg.n_routed_experts_total), D)
            out[m + ("experts", "gate")] = ((E, D, F), D)
            out[m + ("experts", "up")] = ((E, D, F), D)
            out[m + ("experts", "down")] = ((E, F, D), F)
            out[m + ("shared", "gate", "kernel")] = ((D, Fs), D)
            out[m + ("shared", "up", "kernel")] = ((D, Fs), D)
            out[m + ("shared", "down", "kernel")] = ((Fs, D), Fs)
    out["final_norm", "scale"] = ((D,), 0)
    out["lm_head", "kernel"] = ((D, cfg.vocab_size), D)
    return out


def num_params(cfg: ModelConfig) -> int:
    return scaffold.num_params(param_shapes(cfg))


serving_params = scaffold.as_drawn


def init_params(cfg: ModelConfig, key):
    """The tree at seeded random weights in `cfg.param_dtype` (`scaffold.tree_from_shapes`)."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype)


# -- the cache and the counts --------------------------------------------------------


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    """One latent slab a layer, `[slots, max_seq, slab_width]`, and nothing else."""
    shape = (slots, max_seq, la.slab_width(row_width(cfg)))
    return [(jnp.zeros(shape, cfg.dtype),) for _ in range(cfg.n_layers)]


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: the expert layers' int32 array [2 + E] (pairs
    routed, pairs held, pairs by held expert: `dots3`'s) and the slabs' (`LATENT_COUNTS`, split)."""
    return (jnp.zeros((2 + cfg.n_routed_experts,), jnp.int32), jnp.zeros((2 * len(LATENT_COUNTS),), jnp.int32))


def split(rows):
    """A count of rows as (thousand-and-twenty-fours, remainder): `LATENT_COUNTS`."""
    return jnp.stack([rows // _SPLIT, rows % _SPLIT]).astype(jnp.int32)


def _joined(counts) -> dict:
    """{name: rows} of an array of `LATENT_COUNTS` as `split` pairs."""
    return {name: int(counts[2 * j]) * _SPLIT + int(counts[2 * j + 1]) for j, name in enumerate(LATENT_COUNTS)}


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["experts"]` with `dots3`'s keys, and `["latent"]`: rows of a layer's
    slab the decode steps' queries could see and rows their products ran over (a step of 16
    slots counts each slot's rows once, not once a layer), since the engine started and, under
    `window`, since the last report."""
    (experts, latent), (w_experts, w_latent) = total, window
    return {"experts": {
        "held": cfg.n_routed_experts, "of": cfg.n_routed_experts_total, "first": cfg.first_expert,
        "pairs_routed": int(experts[0]), "pairs_held": int(experts[1]),
        "window": {"pairs_routed": int(w_experts[0]), "pairs_held": int(w_experts[1]),
                   "max_load": int(w_experts[2:].max()), "mean_load": float(w_experts[2:].mean())},
    }, "latent": dict(_joined(latent), window=_joined(w_latent),
                      bytes_per_row=la.slab_width(row_width(cfg)) * jnp.dtype(cfg.dtype).itemsize)}


# -- attention -------------------------------------------------------------------------


def _pad_row(row, width: int):
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, width - row.shape[-1])])


def _out(p, o):
    """W_o over the concatenated heads. o: [B, S, H, v]."""
    return _dense(o.reshape(o.shape[:2] + (-1,)), p["o"]["kernel"].reshape(-1, p["o"]["kernel"].shape[-1]))


def attn_prefill(p, x, cache, offset, cfg: ModelConfig):
    """x: [1, S, D] at positions offset + [0, S); cache: (lat [1, T, W],). Writes the chunk's
    rows, then attends over rows [0, offset + S) in blocks of keys, every one under the
    causal mask alone (`ops/latent_attention.py:latent_chunk_attention` with no selection)."""
    d = dims(cfg)
    S, (lat,) = x.shape[1], cache
    kb = key_block(lat.shape[1], S)
    positions = offset + jnp.arange(S)[None, :]
    _, q_nope, q_rope, row = latents(p, x, positions, cfg, d)
    lat = jax.lax.dynamic_update_slice(lat, _pad_row(row, lat.shape[-1]).astype(lat.dtype), (0, offset, 0))
    with jax.named_scope("latent"):
        scale = d.get("score_scale", 1.0) / math.sqrt(d["nope"] + d["rope"])
        q_full = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1)
        o = la.latent_chunk_attention(q_full, lat[0], p["kv_b"]["kernel"].astype(x.dtype), offset, kb, d, scale)[None]
    return _out(p, o), (lat,)


def attn_decode(p, x, cache, lens, gate, cfg: ModelConfig):
    """x: [B, 1, D], slot b at position lens[b]. Returns (out, cache, rows of this layer's
    slab the products ran over)."""
    d = dims(cfg)
    (lat,) = cache
    B, T, W = lat.shape
    _, q_nope, q_rope, row = latents(p, x, lens[:, None], cfg, d)
    lat = put_row(lat, _pad_row(row, W), lens, gate)
    with jax.named_scope("latent"):
        # W_kvb folded into the query, the products over the slab as it lies, W_kvb's other half over their output
        dt, kv_b = x.dtype, p["kv_b"]["kernel"].astype(x.dtype)
        scale = d.get("score_scale", 1.0) / math.sqrt(d["nope"] + d["rope"])
        q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], kv_b[..., :d["nope"]], preferred_element_type=jnp.float32)
        q = _pad_row(jnp.concatenate([q_abs.astype(dt), q_rope[:, 0]], axis=-1), W)
        if attention._use_pallas():
            seen = jnp.where(gate, lens, 0)  # an idle slot's output is read by nobody: one block of it
            o_lat, read = la.latent_attention(q, lat, seen, scale=scale), jnp.sum(la.rows_read(seen, T))
        else:
            o_lat, read = la.latent_attention_xla(q, lat, lens, scale=scale), jnp.int32(B * T)
        o = jnp.einsum("bhc,chd->bhd", o_lat[..., :d["kv_rank"]], kv_b[..., d["nope"]:],
                       preferred_element_type=jnp.float32).astype(dt)[:, None]
    return _out(p, o), (lat,), read


# -- the layers round the attention ----------------------------------------------------


def _forward(params, cfg: ModelConfig, tokens, valid, attend):
    """The layers round `attend(i, layer_params, normed) -> (out, cache_i)`, each sub-layer's
    output normed before it joins the residual. Returns (hidden after the final norm, caches,
    expert stats [2 + E]: valid pairs routed, pairs held here, pairs by held expert)."""
    with jax.named_scope("embedding"):
        x = params["embedding"][tokens].astype(cfg.dtype)
    caches, counts = [], jnp.zeros((cfg.n_routed_experts,), jnp.int32)

    def norm(layer, name, y):
        with jax.named_scope(name):
            return _rmsnorm(y, layer[name]["scale"], cfg.norm_eps)

    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            normed = norm(layer, "attn_norm", x)
            with jax.named_scope("attn"):
                out, cache = attend(i, layer["attn"], normed)
                out = norm(layer, "attn_post_norm", out)
            caches.append(cache)
            x = x + out
            normed = norm(layer, "mlp_norm", x)
            with jax.named_scope("mlp"):
                if i < cfg.first_k_dense:
                    y = swiglu(layer["mlp"], normed)
                else:
                    y, c = routed_experts(layer["mlp"], normed, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                          eps=ROUTING_EPS, first=cfg.first_expert)
                    counts = counts + c
                x = x + norm(layer, "mlp_post_norm", y)
    with jax.named_scope("final_norm"):
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    routed = jnp.sum(valid, dtype=jnp.int32) * (cfg.experts_per_token * scaffold.num_expert_layers(cfg))
    return x, caches, jnp.concatenate([routed[None], jnp.sum(counts)[None], counts])


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len, lora=None, adapter_id=None):
    """The engine's prefill program for this block. tokens: [1, S] right-padded, the chunk at
    positions offset + [0, S) of a prompt of `total_len` tokens, into slot `slot`. Padding's
    rows land past the prompt's end, where the next chunk or the decode steps write before any
    query sees them. Returns (logits of the prompt's last token if it is in this chunk, caches, stats)."""
    S = tokens.shape[1]
    n_valid = jnp.minimum(S, total_len - offset)
    view = scaffold.slot_view(caches, slot)
    x, new, stats = _forward(params, cfg, tokens, jnp.arange(S)[None, :] < n_valid,
                             lambda i, p, normed: attn_prefill(p, normed, view[i], offset, cfg))
    caches = scaffold.write_back(caches, new, slot)
    logits = scaffold.head(params, scaffold.last_row(x, offset, total_len))[0]
    return logits, caches, (stats, jnp.zeros((2 * len(LATENT_COUNTS),), jnp.int32))


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots with
    `gate` write their rows. Returns (logits [B, V], caches, stats)."""
    read = []

    def attend(i, p, normed):
        out, cache, rows = attn_decode(p, normed, caches[i], lens, gate, cfg)
        read.append(rows)
        return out, cache

    x, new, stats = _forward(params, cfg, last_token[:, None], gate[:, None], attend)
    visible = jnp.sum(jnp.where(gate, lens + 1, 0))
    return scaffold.head(params, x[:, 0]), new, (stats, jnp.concatenate([split(visible), split(read[0])]))


# -- the plain reference -------------------------------------------------------------


def forward_plain(params, cfg: ModelConfig, tokens, experts=None):
    """tokens [S] -> logits [S, V]: the whole sequence at once in float32 under "highest",
    every score matrix whole, no cache and no blocks. `experts` is the (first, count) of
    routed experts computed, by default those the tree holds; the router always scores
    `n_routed_experts_total`."""
    first, count = experts or (cfg.first_expert, cfg.n_routed_experts)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    d, S = dims(cfg), tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps) * f32(scale)

    def rope(x):  # [S, H, R]
        return _rope(x[None], pos[None], d["theta"])[0]

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"])[tokens]
        for i in range(cfg.n_layers):
            layer = params[f"layer_{i}"]
            p = layer["attn"]
            a = norm(x, layer["attn_norm"]["scale"])
            c_q = norm(a @ f32(p["q_a"]["kernel"]), p["q_norm"]["scale"])
            q = (c_q @ f32(p["q_b"]["kernel"])).reshape(S, d["heads"], -1)
            q = jnp.concatenate([q[..., :d["nope"]], rope(q[..., d["nope"]:])], axis=-1)
            kv = a @ f32(p["kv_a"]["kernel"])
            c_kv = norm(kv[:, :d["kv_rank"]], p["kv_norm"]["scale"])
            k_r = rope(kv[:, None, d["kv_rank"]:])
            kvx = jnp.einsum("sc,chd->shd", c_kv, f32(p["kv_b"]["kernel"]))
            k = jnp.concatenate([kvx[..., :d["nope"]], jnp.broadcast_to(k_r, (S, d["heads"], d["rope"]))], axis=-1)
            s = jnp.einsum("shd,khd->hsk", q, k) / math.sqrt(d["nope"] + d["rope"])
            pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hsk,khd->shd", pr, kvx[..., d["nope"]:])
            x = x + norm(jnp.einsum("shd,hde->se", o, f32(p["o"]["kernel"])), layer["attn_post_norm"]["scale"])
            h, m = norm(x, layer["mlp_norm"]["scale"]), layer["mlp"]
            if i < cfg.first_k_dense:
                y = swiglu(h, m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"])
                x = x + norm(y, layer["mlp_post_norm"]["scale"])
                continue
            ids, weights = sigmoid_routing(h, m["router"]["kernel"], jnp.zeros((cfg.n_routed_experts_total,)),
                                           cfg.experts_per_token, cfg.routed_scaling_factor, eps=ROUTING_EPS)
            y = swiglu(h, m["shared"]["gate"]["kernel"], m["shared"]["up"]["kernel"], m["shared"]["down"]["kernel"])
            for e in range(first, first + count):
                j = e - cfg.first_expert  # the tree holds experts [first_expert, first_expert + n_routed_experts)
                w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
                y = y + w_e[:, None] * swiglu(h, m["experts"]["gate"][j], m["experts"]["up"][j], m["experts"]["down"][j])
            x = x + norm(y, layer["mlp_post_norm"]["scale"])
        x = norm(x, params["final_norm"]["scale"])
        return x @ f32(params["lm_head"]["kernel"])
