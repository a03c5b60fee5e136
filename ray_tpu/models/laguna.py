"""The `laguna` block on the serve path: grouped-query attention layers of two kinds in one model,
full layers over K/V slabs of `max_seq` rows and window layers, of more heads, over a K/V ring of
`sliding_window` rows; a sigmoid gate a head on the attention's output; a rotary table a kind; and,
after the leading dense layer, softmax-routed experts beside a shared one
(`ModelConfig(block="laguna")`; poolside/Laguna-S-2.1, `model_type` laguna).

One set of pure functions over one parameter tree, behind the seam every block is served through
(`models/__init__.py`). `benchmark/lib/reference_laguna.py` is the plain reference the tests hold
the cached paths to.

    x = E[token]
    each layer i:  x = x + attn_i(rmsnorm(x));  x = x + ff_i(rmsnorm(x))
    logits = rmsnorm(x) W_head                                        (untied head)

attn_i, h its normed input, H = `n_heads` (full) or `swa_n_heads` (sliding), heads of `head_dim`:

    q = h W_q as H heads;  k = h W_k, v = h W_v as `n_kv_heads`;  query head j reads KV head j // (H / Hkv)
    rotary (rotate-half) over the first r values of every head of q and k, the rest passed through:
      full:    r = `partial_rotary_factor` x head_dim, `rope_theta` under `rope_scaling` (YaRN's table over
               the r rotating values, cos and sin times the group's `attention_factor`)
      sliding: r = head_dim, `swa_rope_theta`, plain
    scores q . k / sqrt(head_dim), softmax over keys s <= t (full) or t - `sliding_window` < s <= t (sliding)
    o_j = sigmoid(h W_g)_j o_j;  out = concat_j(o_j) W_o

ff_i: `ops/moe.py:swiglu` at `mlp_dim` for i < `first_k_dense`, otherwise `routed_experts` with
`score="softmax"` over the held experts [`first_expert`, `first_expert` + `n_routed_experts`) of
`n_routed_experts_total`, plus the shared expert.

The cache, one (K, V) pair a layer in `cfg.dtype`, keys kept rotated: a full layer
`[slots, max_seq, Hkv, head_dim]`, row p the position p; a sliding layer a ring
`[slots, sliding_window, Hkv, head_dim]`, position p in row p mod window. A softmax does not ask
for its keys in order, so a decode step's window layer is the full layer's attention
(`ops/attention.py:cached_attention` on the TPU, which writes the step's row at p mod window itself)
over the ring, told a length of at most window - 1.
A prefill chunk may be longer than the ring: it attends to the ring's rows from before it and to
its own keys inside the band, a block of queries at a time, then leaves its last rows in the ring;
its full layers loop over the slab's key blocks up to the chunk's last row and no further.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models import scaffold
from ray_tpu.models.latent import key_block
from ray_tpu.models.pangu_moe import split
from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm, _rope_angles, _rope_apply, yarn_inv_freq
from ray_tpu.ops import attention
from ray_tpu.ops.moe import routed_experts, swiglu

_NEG = -1e30
_WINDOW_QUERIES = 128  # queries a block of a chunk's window layer takes at a time (`_window_attn_prefill`)

# Served by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7): a prefix hit
# would have to rebuild a ring from rows it does not keep, the train step needs the window layers'
# and the expert layer's backward pass, several chips an exchange of tokens between the experts' holders.
SUPPORTS = frozenset()
LAYER_TYPES = ("full_attention", "sliding_attention")  # `ModelConfig.layer_types` names each layer's kind

EMBEDDING_FAN_IN = 2500  # the embedding is drawn at 1 / sqrt(2500) = 0.02

# What a program counts (`init_stats`). The expert layers': valid pairs routed and pairs held here,
# and, of the decode programs alone, held experts that took a pair and expert layers run (summed
# over the layers and the steps), then the pairs each held expert took. The attention's: rows a
# decode step's queries could see, summed over the gated slots and the layers of a kind (all live
# rows; at most `sliding_window`), and query-key pairs a chunk's full layers score (padding not
# counted), each as `pangu_moe.split` has it so that no window wraps an int32.
EXPERT_COUNTS = ("pairs_routed", "pairs_held", "decode_experts_hit", "decode_layer_steps")
ATTN_COUNTS = ("full_rows_visible", "window_rows_visible", "chunk_pairs_full")


# -- sizes ---------------------------------------------------------------------------


def _is_full(cfg: ModelConfig, i: int) -> bool:
    return cfg.layer_types[i] == "full_attention"


def heads(cfg: ModelConfig, full: bool) -> int:
    return cfg.n_heads if full else cfg.swa_n_heads


def rotary(cfg: ModelConfig, full: bool) -> tuple:
    """(values of a head that rotate, theta, the frequency table or None for theta's own, what cos
    and sin are multiplied by) of a layer's kind."""
    if not full:
        return cfg.head_dim, cfg.swa_rope_theta, None, 1.0
    r = int(cfg.head_dim * cfg.partial_rotary_factor)
    scaling = dict(cfg.rope_scaling or ())
    if not scaling:
        return r, cfg.rope_theta, None, 1.0
    return r, cfg.rope_theta, yarn_inv_freq(r, cfg.rope_theta, scaling), float(scaling.get("attention_factor", 1.0))


def n_full(cfg: ModelConfig) -> int:
    return sum(_is_full(cfg, i) for i in range(cfg.n_layers))


def row_bytes(cfg: ModelConfig) -> int:
    """One cached position's K and V in one layer."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, fan-in)}: 0 marks a norm scale (ones), a kernel is normal at
    1 / sqrt(fan-in) (`scaffold.draw_by_fan_in`), the gate and the router among them (a gate's and a
    router's logit have unit variance), the embedding at `EMBEDDING_FAN_IN`."""
    if cfg.router_score != "softmax":
        raise ValueError(f"block 'laguna' routes by a softmax: router_score={cfg.router_score!r}")
    if set(cfg.layer_types) - set(LAYER_TYPES):
        raise ValueError(f"block 'laguna' has layers of {LAYER_TYPES}: layer_types={cfg.layer_types}")
    for H in (cfg.n_heads, cfg.swa_n_heads):
        if H % cfg.n_kv_heads:
            raise ValueError(f"block 'laguna': {H} query heads over {cfg.n_kv_heads} KV heads")
    Dm, hd, out = cfg.hidden, cfg.head_dim, {}
    out["embedding",] = ((cfg.vocab_size, Dm), EMBEDDING_FAN_IN)
    for i in range(cfg.n_layers):
        L, a, m = f"layer_{i}", (f"layer_{i}", "attn"), (f"layer_{i}", "mlp")
        H = heads(cfg, _is_full(cfg, i))
        out[L, "attn_norm", "scale"] = ((Dm,), 0)
        out[L, "mlp_norm", "scale"] = ((Dm,), 0)
        out[a + ("q", "kernel")] = ((Dm, H * hd), Dm)
        out[a + ("k", "kernel")] = ((Dm, cfg.n_kv_heads * hd), Dm)
        out[a + ("v", "kernel")] = ((Dm, cfg.n_kv_heads * hd), Dm)
        out[a + ("g", "kernel")] = ((Dm, H), Dm)
        out[a + ("o", "kernel")] = ((H * hd, Dm), H * hd)
        if i < cfg.first_k_dense:
            F = cfg.mlp_dim
            out[m + ("gate", "kernel")] = ((Dm, F), Dm)
            out[m + ("up", "kernel")] = ((Dm, F), Dm)
            out[m + ("down", "kernel")] = ((F, Dm), F)
        else:
            E, F = cfg.n_routed_experts, cfg.moe_mlp_dim
            out[m + ("router", "kernel")] = ((Dm, cfg.n_routed_experts_total), Dm)
            out[m + ("experts", "gate")] = ((E, Dm, F), Dm)
            out[m + ("experts", "up")] = ((E, Dm, F), Dm)
            out[m + ("experts", "down")] = ((E, F, Dm), F)
            Fs = F * cfg.n_shared_experts
            out[m + ("shared", "gate", "kernel")] = ((Dm, Fs), Dm)
            out[m + ("shared", "up", "kernel")] = ((Dm, Fs), Dm)
            out[m + ("shared", "down", "kernel")] = ((Fs, Dm), Fs)
    out["final_norm", "scale"] = ((Dm,), 0)
    out["lm_head", "kernel"] = ((Dm, cfg.vocab_size), Dm)
    return out


def num_params(cfg: ModelConfig) -> int:
    return scaffold.num_params(param_shapes(cfg))


serving_params = scaffold.as_drawn


def init_params(cfg: ModelConfig, key):
    """The tree at seeded random weights in `cfg.param_dtype` (`scaffold.tree_from_shapes`)."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype)


# -- the cache and the counts --------------------------------------------------------


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    """A (K, V) pair a layer: slabs of `max_seq` rows in a full layer, rings of `sliding_window` in
    a sliding one."""
    out = []
    for i in range(cfg.n_layers):
        shape = (slots, max_seq if _is_full(cfg, i) else cfg.sliding_window, cfg.n_kv_heads, cfg.head_dim)
        out.append((jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)))
    return out


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: the expert layers' int32 array (`EXPERT_COUNTS`, then the
    pairs each held expert took) and the attention's (`ATTN_COUNTS`, each split)."""
    return (jnp.zeros((len(EXPERT_COUNTS) + cfg.n_routed_experts,), jnp.int32),
            jnp.zeros((2 * len(ATTN_COUNTS),), jnp.int32))


def _joined(counts) -> dict:
    """{name: count} of an array of `ATTN_COUNTS` as `split` pairs (thousand-and-twenty-fours, remainder)."""
    return {name: int(counts[2 * j]) * 1024 + int(counts[2 * j + 1]) for j, name in enumerate(ATTN_COUNTS)}


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["experts"]` (pairs routed and held, the decode programs' experts hit and
    layers run, since the engine started and, under `window`, since the last report, there with the
    largest and the mean load of a held expert) and `["attn"]` (`ATTN_COUNTS` the same way, with a
    slab's bytes a cached token and a ring's bytes a slot, both over the layers of their kind)."""
    (experts, attn), (w_experts, w_attn) = total, window
    n = len(EXPERT_COUNTS)
    out = {"held": cfg.n_routed_experts, "of": cfg.n_routed_experts_total, "first": cfg.first_expert}
    out.update({name: int(experts[j]) for j, name in enumerate(EXPERT_COUNTS)})
    out["window"] = {name: int(w_experts[j]) for j, name in enumerate(EXPERT_COUNTS)}
    out["window"].update(max_load=int(w_experts[n:].max()), mean_load=float(w_experts[n:].mean()))
    return {"experts": out, "attn": dict(
        _joined(attn), window=_joined(w_attn), slab_bytes_per_token=n_full(cfg) * row_bytes(cfg),
        ring_bytes_per_slot=(cfg.n_layers - n_full(cfg)) * cfg.sliding_window * row_bytes(cfg))}


# -- attention -------------------------------------------------------------------------


def _rotated(x, positions, rot: tuple):
    """x: [B, S, H, D] with its first `r` values of every head rotated (rotate-half over those r)."""
    r, theta, inv_freq, factor = rot
    cos, sin = _rope_angles(positions, r, theta, inv_freq)
    turned = _rope_apply(x[..., :r], cos * factor, sin * factor)
    return turned if r == x.shape[-1] else jnp.concatenate([turned, x[..., r:]], axis=-1)


def _qkv(p, x, positions, cfg: ModelConfig, full: bool):
    """x: [B, S, D] -> q [B, S, Hkv, G, hd] and k, v [B, S, Hkv, hd], q and k rotated."""
    B, S, _ = x.shape
    H, Hkv, hd, rot = heads(cfg, full), cfg.n_kv_heads, cfg.head_dim, rotary(cfg, full)
    q = _rotated(_dense(x, p["q"]["kernel"]).reshape(B, S, H, hd), positions, rot)
    k = _rotated(_dense(x, p["k"]["kernel"]).reshape(B, S, Hkv, hd), positions, rot)
    v = _dense(x, p["v"]["kernel"]).reshape(B, S, Hkv, hd)
    return q.reshape(B, S, Hkv, H // Hkv, hd), k, v


def _gated_out(p, x, o):
    """o: [B, S, Hkv, G, hd], every head times its gate sigmoid(x W_g), then W_o."""
    B, S = o.shape[:2]
    with jax.named_scope("gate"):
        g = jax.nn.sigmoid(_dense(x, p["g"]["kernel"]).astype(jnp.float32)).astype(o.dtype)
        o = o.reshape(B, S, -1, o.shape[-1]) * g[..., None]
    return _dense(o.reshape(B, S, -1), p["o"]["kernel"])


def chunk_attention(q, cache_k, cache_v, offset, kb: int, scale: float):
    """A chunk's queries over the slab's live rows: q [S, Hkv, G, D] at positions offset + [0, S),
    cache_k/v [T, Hkv, D] with the chunk's own rows written -> [S, Hkv, G, D] in q's type. A loop
    over the blocks of `kb` keys up to the chunk's last row with an online softmax, so that no
    score is taken over a row past it (`ops/attention.py:cached_attention_xla` takes two products
    over all T rows). Scores in float32, the weights cast to q's type before the values' product."""
    S, Hkv, G, D = q.shape
    q_pos = offset + jnp.arange(S)[:, None]
    # A block is taken of the slab's rows flattened to [T * Hkv, D], which moves nothing (a row of the slab is
    # Hkv rows of D lanes), and made head-major inside the loop: taken of [T, Hkv, D], the compiler lays the
    # whole slot's slab out head-major before the loop, rows past the live ones and all.
    flat_k, flat_v = cache_k.reshape(-1, D), cache_v.reshape(-1, D)

    def one_block(j, carry):
        m_prev, l_prev, acc = carry
        k = jax.lax.dynamic_slice_in_dim(flat_k, j * kb * Hkv, kb * Hkv, axis=0).reshape(kb, Hkv, D).astype(q.dtype)
        v = jax.lax.dynamic_slice_in_dim(flat_v, j * kb * Hkv, kb * Hkv, axis=0).reshape(kb, Hkv, D).astype(q.dtype)
        s = jnp.einsum("skgd,tkd->kgst", q, k, preferred_element_type=jnp.float32) * scale
        s = jnp.where(j * kb + jnp.arange(kb)[None, :] <= q_pos, s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc = acc * alpha + jnp.einsum("kgst,tkd->kgsd", pr.astype(q.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True), acc

    # every query sees row 0, so no row of the first block is all masked and m is finite from there on
    _, l, acc = jax.lax.fori_loop(0, (offset + S + kb - 1) // kb, one_block, (
        jnp.full((Hkv, G, S, 1), _NEG, jnp.float32), jnp.zeros((Hkv, G, S, 1), jnp.float32),
        jnp.zeros((Hkv, G, S, D), jnp.float32)))
    return jnp.transpose(acc / l, (2, 0, 1, 3)).astype(q.dtype)


def _full_attn_prefill(p, x, cache, offset, cfg: ModelConfig):
    """x: [1, S, D] at positions offset + [0, S); cache: (K, V) [1, T, Hkv, hd]. Writes the chunk's
    rows (its padding's land past the prompt's end, where the next chunk or the decode steps write
    before any query sees them), then attends over rows [0, offset + S)."""
    S, (ck, cv) = x.shape[1], cache
    q, k, v = _qkv(p, x, offset + jnp.arange(S)[None, :], cfg, True)
    with jax.named_scope("kv_attn"):
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, offset, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, offset, 0, 0))
        o = chunk_attention(q[0], ck[0], cv[0], offset, key_block(ck.shape[1], S), 1.0 / math.sqrt(cfg.head_dim))[None]
    return _gated_out(p, x, o), (ck, cv)


def _window_attn_prefill(p, x, cache, offset, n_valid, cfg: ModelConfig):
    """x: [1, S, D]; cache: the rings (K, V) [1, W, Hkv, hd]. Keys are the W positions before the
    chunk, read from the ring, and the chunk's own; a block of queries takes the W before it and
    itself, under the band's mask. Then the ring takes the last W of the chunk's `n_valid`
    positions (padding is never written to it)."""
    S, (rk, rv), W = x.shape[1], cache, cfg.sliding_window
    positions = offset + jnp.arange(S)
    q, k, v = _qkv(p, x, positions[None, :], cfg, False)
    k, v = k.astype(rk.dtype), v.astype(rv.dtype)
    with jax.named_scope("kv_attn"):
        # queries a block; its keys are the W before it and its own: the smaller the block, the fewer keys outside
        # its queries' bands are scored (at 512 queries half of a block's 1024 keys, at 128 a fifth of its 640)
        bq = math.gcd(S, W, _WINDOW_QUERIES)
        before = (offset - W + jnp.arange(W)) % W
        rows = jnp.arange(S // bq)[:, None] * bq + jnp.arange(W + bq)[None, :]    # [blocks, W + bq] into before | chunk
        keys = jnp.concatenate([rk[0][before], k[0]], axis=0)[rows].astype(x.dtype)
        vals = jnp.concatenate([rv[0][before], v[0]], axis=0)[rows].astype(x.dtype)
        qb = q[0].reshape((S // bq, bq) + q.shape[2:])
        s = jnp.einsum("nskgd,ntkd->nkgst", qb, keys, preferred_element_type=jnp.float32) / math.sqrt(cfg.head_dim)
        k_pos = offset - W + rows                                                  # [blocks, W + bq]
        back = positions.reshape(-1, bq)[:, :, None] - k_pos[:, None, :]           # [blocks, bq, W + bq]
        mask = (back >= 0) & (back < W) & (k_pos[:, None, :] >= 0)
        pr = jax.nn.softmax(jnp.where(mask[:, None, None], s, _NEG), axis=-1).astype(x.dtype)
        o = jnp.einsum("nkgst,ntkd->nskgd", pr, vals, preferred_element_type=jnp.float32).astype(x.dtype)
        o = o.reshape((1, S) + q.shape[2:])
        last = offset + n_valid - 1
        newest = last - (last - jnp.arange(W)) % W  # the newest position each ring row can hold
        take, kept = jnp.clip(newest - offset, 0, S - 1), (newest >= offset)[None, :, None, None]
        rk, rv = jnp.where(kept, k[:, take], rk), jnp.where(kept, v[:, take], rv)
    return _gated_out(p, x, o), (rk, rv)


def _attn_decode(p, x, cache, lens, gate, cfg: ModelConfig, full: bool):
    """x: [B, 1, D], slot b at position lens[b]; cache: (K, V) slabs or rings. The new row lands at
    the position (mod the window in a ring), then one query a head over the rows it may see: all
    up to its own in a slab, and in a ring the whole of it once it has wrapped. On the TPU the
    kernel writes the row itself; elsewhere XLA's gated write comes before the products."""
    ck, cv = cache
    q, k, v = _qkv(p, x, lens[:, None], cfg, full)
    with jax.named_scope("kv_attn"):
        at = lens if full else lens % cfg.sliding_window
        k, v = k.astype(ck.dtype), v.astype(cv.dtype)
        seen = jnp.where(gate, lens if full else jnp.minimum(lens, cfg.sliding_window - 1), 0)  # an idle slot: one row
        scale = 1.0 / math.sqrt(cfg.head_dim)
        if attention._use_pallas():
            o, ck, cv = attention.cached_attention(q, ck, cv, seen, scale=scale, new_k=k, new_v=v, write_at=at, gate=gate)
        else:
            ck, cv = attention.put_gated(ck, k, at, gate), attention.put_gated(cv, v, at, gate)
            o = attention.cached_attention_xla(q, ck, cv, seen, scale=scale)
    return _gated_out(p, x, o), (ck, cv)


# -- the layers round the attention ----------------------------------------------------


def _forward(params, cfg: ModelConfig, tokens, valid, attend, decoding: bool):
    """The layers round `attend(i, layer_params, normed) -> (out, cache_i)`: hidden states after the
    final norm, the caches, and the expert layers' counts (`EXPERT_COUNTS`, then pairs by held expert)."""
    with jax.named_scope("embedding"):
        x = params["embedding"][tokens].astype(cfg.dtype)
    caches, counts, hit = [], jnp.zeros((cfg.n_routed_experts,), jnp.int32), jnp.zeros((), jnp.int32)
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
                    y, c = routed_experts(layer["mlp"], normed, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                          first=cfg.first_expert, score="softmax")
                    x, counts, hit = x + y, counts + c, hit + jnp.sum(c > 0, dtype=jnp.int32)
    with jax.named_scope("final_norm"):
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    n_moe = scaffold.num_expert_layers(cfg)
    named = dict(pairs_routed=jnp.sum(valid, dtype=jnp.int32) * (cfg.experts_per_token * n_moe), pairs_held=jnp.sum(counts))
    if decoding:
        named.update(decode_experts_hit=hit, decode_layer_steps=n_moe)
    return x, caches, jnp.concatenate([scaffold.counts(EXPERT_COUNTS, **named), counts])


def _attn_counts(**named):
    return jnp.concatenate([split(jnp.asarray(named.get(name, 0), jnp.int32)) for name in ATTN_COUNTS])


# -- what the engine's programs call ---------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len, lora=None, adapter_id=None):
    """The engine's prefill program for this block. tokens: [1, S] right-padded, the chunk at
    positions offset + [0, S) of a prompt of `total_len` tokens, into slot `slot`. Returns
    (logits of the prompt's last token if it is in this chunk, caches, stats)."""
    S = tokens.shape[1]
    n_valid = jnp.minimum(S, total_len - offset)
    view = scaffold.slot_view(caches, slot)

    def attend(i, p, normed):
        if _is_full(cfg, i):
            return _full_attn_prefill(p, normed, view[i], offset, cfg)
        return _window_attn_prefill(p, normed, view[i], offset, n_valid, cfg)

    x, new, experts = _forward(params, cfg, tokens, jnp.arange(S)[None, :] < n_valid, attend, decoding=False)
    caches = scaffold.write_back(caches, new, slot)
    # valid query t of the chunk scores the offset + t + 1 keys up to its own
    pairs = n_full(cfg) * (n_valid * offset + n_valid * (n_valid + 1) // 2)
    logits = scaffold.head(params, scaffold.last_row(x, offset, total_len))[0]
    return logits, caches, (experts, _attn_counts(chunk_pairs_full=pairs))


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots with `gate`
    write their rows and are routed. Returns (logits [B, V], caches, stats)."""

    def attend(i, p, normed):
        return _attn_decode(p, normed, caches[i], lens, gate, cfg, _is_full(cfg, i))

    x, new, experts = _forward(params, cfg, last_token[:, None], gate[:, None], attend, decoding=True)
    visible = jnp.where(gate, lens + 1, 0)
    attn = _attn_counts(full_rows_visible=n_full(cfg) * jnp.sum(visible),
                        window_rows_visible=(cfg.n_layers - n_full(cfg)) * jnp.sum(jnp.minimum(visible, cfg.sliding_window)))
    return scaffold.head(params, x[:, 0]), new, (experts, attn)
