"""The `dots3` block on the serve path: latent attention with a learned sparse
indexer, windowed latent layers over a ring cache, sigmoid-routed experts beside a
shared one (`ModelConfig(block="dots3")`).

One set of pure functions over one parameter tree, behind the seam every block is
served through (`models/__init__.py`). The engine's prefill and decode programs call
`prefill` and `decode`; `init_params` builds the tree `load_model` serves at random
weights; `report` reads the expert layers' counts; `forward_plain` is the repo's
plain reference (whole sequence, float32, no cache, no blocks) that the tests hold
the cached paths to.

Per layer i, h = RMSNorm(x) the sub-layer's input:

full layer (`layer_types[i] == "full_attention"`)
    c_q = r_q RMSNorm(h W_qa);  q = c_q W_qb -> H x [nope | rope], rotary on rope
    [c_kv | k_r] = h W_kva;  c_kv = r_kv RMSNorm(c_kv);  k_r rotated, one for all heads
    [k_nope | v] = c_kv W_kvb
    indexer: q_I = c_q W_qI (Hi x Di), k_I = LayerNorm(h W_kI) (Di), rotary on the
      first `rope` of each, w = h W_w / sqrt(Hi Di);
      I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]), s <= t;
      S_t = the `index_topk` positions of largest I[t, .]
    o_head = softmax_{s in S_t}((q_nope . k_nope[s] + q_rope . k_r[s]) / sqrt(nope + rope)) v[s]
    out = concat_heads(sigmoid(h W_g)_head o_head) W_o
    cached per token: c_kv | k_r (one row), k_I
sliding layer: the same latent form at the `swa_*` sizes, no indexer; position t
    sees s with 0 <= t - s < sliding_window; cached: a ring of `sliding_window` rows.
expert layer (every layer from `first_k_dense` on): `ops/moe.py` (`sigmoid_routing`,
    `grouped_experts` over the experts held here) plus the shared expert.

Two attention paths that must agree (tests/test_dots3.py): a prefill chunk expands
keys and values from the latent rows, block of keys by block of keys, under the
mask the selection gives; a decode step gathers the selected latent rows and folds
W_kvb into the query and the output, so it never expands a key.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models import scaffold
from ray_tpu.models.latent import attn_dims, key_block, latents, put_row, rescale, rope_rows
from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm, _rope
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.moe import routed_experts, sigmoid_routing, swiglu

_NEG = -1e30

# What the engine and the layers round it may ask of this block (`models.require`): served
# by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7).
SUPPORTS = frozenset()
# Nothing but the next program reads the caches: every program of the engine consumes
# them (`donate_argnums`), so the compiler writes each one in place.


# -- sizes ---------------------------------------------------------------------------


def _is_full(cfg: ModelConfig, i: int) -> bool:
    return cfg.layer_types[i] == "full_attention"


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, fan_in)}; fan_in 0 marks a norm scale (ones),
    -1 a bias drawn small. Kernels are normal(0, 1 / sqrt(fan_in)), so that a product of
    a unit-variance input has unit variance. A kernel that reads a rescaled latent counts
    the latent's variance r^2 into its fan-in (with the published rescale that makes it
    `hidden`): drawn at 1 / sqrt(rank) its queries and keys would give attention scores of
    standard deviation 6, a softmax that is all but an argmax, and logits that bfloat16
    rounding moves by 0.39 of their spread (PERF.md, PR 28)."""
    D, out = cfg.hidden, {}
    out["embedding",] = ((cfg.vocab_size, D), 1)
    for i in range(cfg.n_layers):
        L = f"layer_{i}"
        full = _is_full(cfg, i)
        d = attn_dims(cfg, full)
        H = d["heads"]
        out[L, "attn_norm", "scale"] = ((D,), 0)
        out[L, "mlp_norm", "scale"] = ((D,), 0)
        a = (L, "attn")
        out[a + ("q_a", "kernel")] = ((D, d["q_rank"]), D)
        out[a + ("q_norm", "scale")] = ((d["q_rank"],), 0)
        fan_q = d["q_rank"] * rescale(cfg, d["q_rank"]) ** 2
        fan_kv = d["kv_rank"] * rescale(cfg, d["kv_rank"]) ** 2
        out[a + ("q_b", "kernel")] = ((d["q_rank"], H, d["nope"] + d["rope"]), fan_q)
        out[a + ("kv_a", "kernel")] = ((D, d["kv_rank"] + d["rope"]), D)
        out[a + ("kv_norm", "scale")] = ((d["kv_rank"],), 0)
        out[a + ("kv_b", "kernel")] = ((d["kv_rank"], H, d["nope"] + d["v"]), fan_kv)
        out[a + ("gate", "kernel")] = ((D, H), D)
        out[a + ("o", "kernel")] = ((H, d["v"], D), H * d["v"])
        if full:
            x = a + ("indexer",)
            out[x + ("q", "kernel")] = ((d["q_rank"], cfg.index_n_heads, cfg.index_head_dim), fan_q)
            out[x + ("k", "kernel")] = ((D, cfg.index_head_dim), D)
            out[x + ("k_norm", "scale")] = ((cfg.index_head_dim,), 0)
            out[x + ("k_norm", "bias")] = ((cfg.index_head_dim,), -1)
            out[x + ("w", "kernel")] = ((D, cfg.index_n_heads), D)
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
            out[m + ("router", "bias")] = ((cfg.n_routed_experts_total,), -1)
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
    """The tree at seeded random weights in `cfg.param_dtype` (`scaffold.tree_from_shapes`). The
    router's correction bias is drawn at a scale (0.1) that changes some of the choices
    the scores alone would make."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype)


# -- the cache -----------------------------------------------------------------------


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    """One tuple of arrays per layer, each `[slots, rows, width]` in `cfg.dtype`: a full
    layer holds a latent row (c_kv | k_r) and an indexer key for each of `max_seq`
    positions, a sliding layer a ring of `sliding_window` latent rows (position p in
    row p mod window)."""
    out = []
    for i in range(cfg.n_layers):
        d = attn_dims(cfg, _is_full(cfg, i))
        width = d["kv_rank"] + d["rope"]
        if _is_full(cfg, i):
            out.append((jnp.zeros((slots, max_seq, width), cfg.dtype),
                        jnp.zeros((slots, max_seq, cfg.index_head_dim), cfg.dtype)))
        else:
            out.append((jnp.zeros((slots, cfg.sliding_window, width), cfg.dtype),))
    return out


# -- the expert layers' counts ------------------------------------------------------


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: one int32 array [2 + E] (`_forward`)."""
    return (jnp.zeros((2 + cfg.n_routed_experts,), jnp.int32),)


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["experts"]`: token-expert pairs routed and pairs whose expert
    this chip holds, since the engine started and since the last report, with the
    largest and the mean load of a held expert there."""
    (total,), (window,) = total, window
    return {"experts": {
        "held": cfg.n_routed_experts, "of": cfg.n_routed_experts_total,
        "first": cfg.first_expert,
        "pairs_routed": int(total[0]), "pairs_held": int(total[1]),
        "window": {"pairs_routed": int(window[0]), "pairs_held": int(window[1]),
                   "max_load": int(window[2:].max()), "mean_load": float(window[2:].mean())},
    }}


# -- projections both paths share ----------------------------------------------------


def _index_terms(p, x, c_q, positions, cfg: ModelConfig, theta):
    """The indexer's queries [B, S, Hi, Di], key [B, S, Di] and head weights [B, S, Hi]."""
    Hi, Di, R = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _dense(c_q, p["q"]["kernel"].reshape(c_q.shape[-1], -1)).reshape(x.shape[:2] + (Hi, Di))
    q = jnp.concatenate([_rope(q[..., :R], positions, theta), q[..., R:]], axis=-1)
    k = _dense(x, p["k"]["kernel"]).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(jnp.mean((k - mean) ** 2, axis=-1, keepdims=True) + cfg.norm_eps)
    k = (k * p["k_norm"]["scale"].astype(jnp.float32) + p["k_norm"]["bias"].astype(jnp.float32)).astype(x.dtype)
    k = jnp.concatenate([rope_rows(k[..., :R], positions, theta), k[..., R:]], axis=-1)
    w = _dense(x, p["w"]["kernel"]).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q, k, w


def _gated_out(p, x, o, d: dict):
    """Head-wise gate on the normed input, then W_o. o: [B, S, H, v]."""
    g = jax.nn.sigmoid(_dense(x, p["gate"]["kernel"]).astype(jnp.float32)).astype(o.dtype)
    o = (o * g[..., None]).reshape(o.shape[:2] + (-1,))
    return _dense(o, p["o"]["kernel"].reshape(-1, p["o"]["kernel"].shape[-1]))


def _sortable(x):
    """float32 -> uint32 that sorts as the floats do."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)


def _kth_largest(scores, k: int):
    """(sortable scores, the k-th largest of each row) of float32 `scores` [S, T],
    exactly and without a sort: the answer's bits are fixed from the top down, four at a
    time, by counting the row's entries that reach each of the 15 candidates (one read of
    the scores settles four bits; the counts are what the loop is bound by). A row of
    fewer than k entries gives 0, under every entry."""
    u = _sortable(scores)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)

    def fix(i, got):
        shift = jnp.uint32(28) - jnp.uint32(4) * i.astype(jnp.uint32)
        cands = got[:, None] | (digits[None, :] << shift)                      # [S, 15], increasing
        enough = jnp.sum(u[:, :, None] >= cands[:, None, :], axis=1, dtype=jnp.int32) >= k
        digit = jnp.sum(enough, axis=-1).astype(jnp.uint32)                    # enough is a prefix of the 15
        return got | (digit << shift)

    return u, jax.lax.fori_loop(0, 8, fix, jnp.zeros(scores.shape[:1], jnp.uint32))


def _top_k_mask(scores, k: int):
    """[S, T] bool: the k largest of each row as `lax.top_k` picks them (of equal scores
    the lower index first), rows of `_NEG` entries apart: where the k-th largest is a
    masked entry every masked entry is marked, and the caller's own mask takes them out.
    Equal scores past the k-th are what a count cannot settle; only then is a running
    count of them taken."""
    u, kth = _kth_largest(scores, k)
    above, equal = u > kth[:, None], u == kth[:, None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    settled = (jnp.sum(equal, axis=-1, dtype=jnp.int32) <= room) | (kth <= _sortable(jnp.float32(_NEG)))
    return jax.lax.cond(
        jnp.all(settled), lambda: above | equal,
        lambda: above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room[:, None])))


# -- a prefill chunk of one slot -----------------------------------------------------


def _full_attn_prefill(p, x, cache, offset, cfg: ModelConfig):
    """x: [1, S, D] at positions offset + [0, S); cache: (lat [1, T, W], kidx [1, T, Di]).
    Writes the chunk's rows, then attends over rows [0, offset + S) in blocks."""
    d = attn_dims(cfg, True)
    S, (lat, kidx) = x.shape[1], cache
    T, kb = lat.shape[1], key_block(lat.shape[1], S)
    positions = offset + jnp.arange(S)[None, :]
    c_q, q_nope, q_rope, row = latents(p, x, positions, cfg, d)
    with jax.named_scope("indexer"):
        q_i, k_i, w_i = _index_terms(p["indexer"], x, c_q, positions, cfg, d["theta"])
    lat = jax.lax.dynamic_update_slice(lat, row.astype(lat.dtype), (0, offset, 0))
    kidx = jax.lax.dynamic_update_slice(kidx, k_i.astype(kidx.dtype), (0, offset, 0))
    n_blocks = (offset + S + kb - 1) // kb
    q_pos = positions[0][:, None]

    with jax.named_scope("indexer"):
        def score_block(j, scores):
            keys = jax.lax.dynamic_slice(kidx[0], (j * kb, 0), (kb, kidx.shape[-1]))
            s = jnp.einsum("shd,kd->shk", q_i[0], keys.astype(q_i.dtype),
                           preferred_element_type=jnp.float32)
            s = jnp.einsum("shk,sh->sk", jax.nn.relu(s), w_i[0])
            s = jnp.where(j * kb + jnp.arange(kb)[None, :] <= q_pos, s, _NEG)
            return jax.lax.dynamic_update_slice(scores, s, (0, j * kb))

        scores = jax.lax.fori_loop(0, n_blocks, score_block, jnp.full((S, T), _NEG, jnp.float32))
        chosen = _top_k_mask(scores, cfg.index_topk) & (jnp.arange(T)[None, :] <= q_pos)

    with jax.named_scope("latent"):
        q_full = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1)
        o = la.latent_chunk_attention(q_full, lat[0], p["kv_b"]["kernel"].astype(x.dtype), offset, kb, d,
                                      1.0 / math.sqrt(d["nope"] + d["rope"]), mask=chosen)[None]
    return _gated_out(p, x, o, d), (lat, kidx)


def _window_attn_prefill(p, x, cache, offset, n_valid, cfg: ModelConfig):
    """x: [1, S, D]; cache: (ring [1, W, width],). Keys are the W - 1 positions before
    the chunk, read from the ring, and the chunk's own; then the ring takes the last
    W of the chunk's `n_valid` positions (padding is never written to it)."""
    d = attn_dims(cfg, False)
    S, (ring,), W = x.shape[1], cache, cfg.sliding_window
    positions = offset + jnp.arange(S)[None, :]
    _, q_nope, q_rope, row = latents(p, x, positions, cfg, d)
    row = row.astype(ring.dtype)
    with jax.named_scope("window"):
        before = offset - (W - 1) + jnp.arange(W - 1)
        keys = jnp.concatenate([ring[0][before % W], row[0]], axis=0).astype(x.dtype)
        k_pos = jnp.concatenate([before, positions[0]])
        kv = jnp.einsum("kc,chd->khd", keys[:, :d["kv_rank"]], p["kv_b"]["kernel"].astype(x.dtype),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        s = jnp.einsum("shd,khd->hsk", q_nope[0], kv[..., :d["nope"]], preferred_element_type=jnp.float32)
        s = s + jnp.einsum("shd,kd->hsk", q_rope[0], keys[:, d["kv_rank"]:], preferred_element_type=jnp.float32)
        back = positions[0][:, None] - k_pos[None, :]
        mask = (back >= 0) & (back < W) & (k_pos[None, :] >= 0)
        s = jnp.where(mask[None], s / math.sqrt(d["nope"] + d["rope"]), _NEG)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("hsk,khd->shd", pr, kv[..., d["nope"]:], preferred_element_type=jnp.float32)
        o = o.astype(x.dtype)[None]
        last = offset + n_valid - 1
        newest = last - (last - jnp.arange(W)) % W  # the newest position each ring row can hold
        ring = jnp.where((newest >= offset)[None, :, None],
                         row[:, jnp.clip(newest - offset, 0, S - 1)], ring)
    return _gated_out(p, x, o, d), (ring,)


# -- a decode step of every slot -----------------------------------------------------


def _absorbed(p, q_nope, q_rope, rows, valid, d: dict):
    """Attention over latent rows with W_kvb folded into the query and the output.
    q_*: [B, H, .]; rows: [B, K, kv_rank + rope]; valid: [B, K] -> [B, 1, H, v]."""
    dt = q_nope.dtype
    kv_b = p["kv_b"]["kernel"].astype(dt)
    rows = rows.astype(dt)
    c_kv, k_r = rows[..., :d["kv_rank"]], rows[..., d["kv_rank"]:]
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, kv_b[..., :d["nope"]], preferred_element_type=jnp.float32).astype(dt)
    s = jnp.einsum("bhc,bkc->bhk", q_abs, c_kv, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhd,bkd->bhk", q_rope, k_r, preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, :], s / math.sqrt(d["nope"] + d["rope"]), _NEG)
    pr = jax.nn.softmax(s, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhk,bkc->bhc", pr, c_kv, preferred_element_type=jnp.float32).astype(dt)
    o = jnp.einsum("bhc,chd->bhd", o_lat, kv_b[..., d["nope"]:], preferred_element_type=jnp.float32)
    return o.astype(dt)[:, None]


def _full_attn_decode(p, x, cache, lens, gate, cfg: ModelConfig):
    """x: [B, 1, D], slot b at position lens[b]."""
    d = attn_dims(cfg, True)
    lat, kidx = cache
    T = lat.shape[1]
    positions = lens[:, None]
    c_q, q_nope, q_rope, row = latents(p, x, positions, cfg, d)
    with jax.named_scope("indexer"):
        q_i, k_i, w_i = _index_terms(p["indexer"], x, c_q, positions, cfg, d["theta"])
    lat, kidx = put_row(lat, row, lens, gate), put_row(kidx, k_i, lens, gate)
    with jax.named_scope("indexer"):
        s = jnp.einsum("bhd,btd->bht", q_i[:, 0], kidx.astype(q_i.dtype), preferred_element_type=jnp.float32)
        scores = jnp.einsum("bht,bh->bt", jax.nn.relu(s), w_i[:, 0])
        scores = jnp.where(jnp.arange(T)[None, :] <= positions, scores, _NEG)
        top, chosen = jax.lax.top_k(scores, min(cfg.index_topk, T))
    with jax.named_scope("select"):
        rows = jnp.take_along_axis(lat, chosen[..., None], axis=1)
    with jax.named_scope("latent"):
        o = _absorbed(p, q_nope[:, 0], q_rope[:, 0], rows, top > _NEG / 2, d)
    return _gated_out(p, x, o, d), (lat, kidx)


def _window_attn_decode(p, x, cache, lens, gate, cfg: ModelConfig):
    d = attn_dims(cfg, False)
    (ring,), W = cache, cfg.sliding_window
    positions = lens[:, None]
    _, q_nope, q_rope, row = latents(p, x, positions, cfg, d)
    with jax.named_scope("window"):
        ring = put_row(ring, row, lens % W, gate)
        held = positions - (positions - jnp.arange(W)[None, :]) % W  # the position in each ring row
        o = _absorbed(p, q_nope[:, 0], q_rope[:, 0], ring, held >= 0, d)
    return _gated_out(p, x, o, d), (ring,)


# -- the layers round the attention ----------------------------------------------------


def _forward(params, cfg: ModelConfig, tokens, valid, attend):
    """The layers round `attend(i, layer_params, normed) -> (out, cache_i)`. Returns
    (hidden after the final norm, caches, expert stats [2 + E]: valid pairs routed,
    pairs held here, pairs by held expert, summed over the expert layers)."""
    with jax.named_scope("embedding"):
        x = params["embedding"][tokens].astype(cfg.dtype)
    caches, counts = [], jnp.zeros((cfg.n_routed_experts,), jnp.int32)
    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            with jax.named_scope("attn_norm"):
                normed = _rmsnorm(x, layer["attn_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attn"):
                out, cache = attend(i, layer["attn"], normed)
            caches.append(cache)
            x = x + out
            with jax.named_scope("mlp_norm"):
                normed = _rmsnorm(x, layer["mlp_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                if i < cfg.first_k_dense:
                    x = x + swiglu(layer["mlp"], normed)
                else:
                    y, c = routed_experts(layer["mlp"], normed, valid, cfg.experts_per_token,
                                          cfg.routed_scaling_factor, first=cfg.first_expert)
                    x, counts = x + y, counts + c
    with jax.named_scope("final_norm"):
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    routed = jnp.sum(valid, dtype=jnp.int32) * (cfg.experts_per_token * scaffold.num_expert_layers(cfg))
    return x, caches, jnp.concatenate([routed[None], jnp.sum(counts)[None], counts])


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len, lora=None, adapter_id=None):
    """The engine's prefill program for this block. tokens: [1, S] right-padded, the
    chunk at positions offset + [0, S) of a prompt of `total_len` tokens, into slot
    `slot`. Returns (logits of the prompt's last token if it is in this chunk, caches, stats)."""
    S = tokens.shape[1]
    n_valid = jnp.minimum(S, total_len - offset)
    view = scaffold.slot_view(caches, slot)

    def attend(i, p, normed):
        if _is_full(cfg, i):
            return _full_attn_prefill(p, normed, view[i], offset, cfg)
        return _window_attn_prefill(p, normed, view[i], offset, n_valid, cfg)

    x, new, stats = _forward(params, cfg, tokens, jnp.arange(S)[None, :] < n_valid, attend)
    caches = scaffold.write_back(caches, new, slot)
    return scaffold.head(params, scaffold.last_row(x, offset, total_len))[0], caches, (stats,)


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots
    with `gate` write their rows. Returns (logits [B, V], caches, stats)."""

    def attend(i, p, normed):
        if _is_full(cfg, i):
            return _full_attn_decode(p, normed, caches[i], lens, gate, cfg)
        return _window_attn_decode(p, normed, caches[i], lens, gate, cfg)

    x, new, stats = _forward(params, cfg, last_token[:, None], gate[:, None], attend)
    return scaffold.head(params, x[:, 0]), new, (stats,)


# -- the plain reference -------------------------------------------------------------


def forward_plain(params, cfg: ModelConfig, tokens, experts=None):
    """tokens [S] -> logits [S, V]: the whole sequence at once in float32 under
    "highest", every score matrix whole, the selection by `top_k`, no cache and no
    blocks. `experts` is the (first, count) of routed experts computed, by default
    those the tree holds; the router always scores `n_routed_experts_total`."""
    first, count = experts or (cfg.first_expert, cfg.n_routed_experts)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    S = tokens.shape[0]
    pos = jnp.arange(S)
    back = pos[:, None] - pos[None, :]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps) * f32(scale)

    def rope(x, theta):  # [S, H, R]
        return _rope(x[None], pos[None], theta)[0]

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"])[tokens]
        for i in range(cfg.n_layers):
            layer, full = params[f"layer_{i}"], _is_full(cfg, i)
            p, d = layer["attn"], attn_dims(cfg, full)
            h = norm(x, layer["attn_norm"]["scale"])
            c_q = norm(h @ f32(p["q_a"]["kernel"]), p["q_norm"]["scale"]) * rescale(cfg, d["q_rank"])
            q = jnp.einsum("sr,rhd->shd", c_q, f32(p["q_b"]["kernel"]))
            q = jnp.concatenate([q[..., :d["nope"]], rope(q[..., d["nope"]:], d["theta"])], axis=-1)
            kv = h @ f32(p["kv_a"]["kernel"])
            c_kv = norm(kv[:, :d["kv_rank"]], p["kv_norm"]["scale"]) * rescale(cfg, d["kv_rank"])
            k_r = rope(kv[:, None, d["kv_rank"]:], d["theta"])
            kvx = jnp.einsum("sc,chd->shd", c_kv, f32(p["kv_b"]["kernel"]))
            k = jnp.concatenate([kvx[..., :d["nope"]], jnp.broadcast_to(k_r, (S, d["heads"], d["rope"]))], axis=-1)
            if full:
                ix, R = p["indexer"], cfg.qk_rope_head_dim
                q_i = jnp.einsum("sr,rhd->shd", c_q, f32(ix["q"]["kernel"]))
                q_i = jnp.concatenate([rope(q_i[..., :R], d["theta"]), q_i[..., R:]], axis=-1)
                k_i = h @ f32(ix["k"]["kernel"])
                mu = jnp.mean(k_i, axis=-1, keepdims=True)
                k_i = (k_i - mu) * jax.lax.rsqrt(jnp.mean((k_i - mu) ** 2, axis=-1, keepdims=True) + cfg.norm_eps)
                k_i = k_i * f32(ix["k_norm"]["scale"]) + f32(ix["k_norm"]["bias"])
                k_i = jnp.concatenate([rope(k_i[:, None, :R], d["theta"])[:, 0], k_i[:, R:]], axis=-1)
                w = (h @ f32(ix["w"]["kernel"])) * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
                score = jnp.einsum("shk,sh->sk", jax.nn.relu(jnp.einsum("shd,kd->shk", q_i, k_i)), w)
                score = jnp.where(back >= 0, score, -jnp.inf)
                _, chosen = jax.lax.top_k(score, min(cfg.index_topk, S))
                mask = jnp.zeros((S, S), bool).at[pos[:, None], chosen].set(True) & (back >= 0)
            else:
                mask = (back >= 0) & (back < cfg.sliding_window)
            s = jnp.einsum("shd,khd->hsk", q, k) / math.sqrt(d["nope"] + d["rope"])
            pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hsk,khd->shd", pr, kvx[..., d["nope"]:])
            o = o * jax.nn.sigmoid(h @ f32(p["gate"]["kernel"]))[..., None]
            x = x + jnp.einsum("shd,hde->se", o, f32(p["o"]["kernel"]))
            h, m = norm(x, layer["mlp_norm"]["scale"]), layer["mlp"]
            if i < cfg.first_k_dense:
                x = x + swiglu(h, m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"])
                continue
            ids, weights = sigmoid_routing(h, m["router"]["kernel"], m["router"]["bias"],
                                           cfg.experts_per_token, cfg.routed_scaling_factor)
            y = swiglu(h, m["shared"]["gate"]["kernel"], m["shared"]["up"]["kernel"], m["shared"]["down"]["kernel"])
            for e in range(first, first + count):
                j = e - cfg.first_expert  # the tree holds experts [first_expert, first_expert + n_routed_experts)
                w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
                y = y + w_e[:, None] * swiglu(h, m["experts"]["gate"][j], m["experts"]["up"][j], m["experts"]["down"][j])
            x = x + y
        x = norm(x, params["final_norm"]["scale"])
        return x @ f32(params["lm_head"]["kernel"])
