"""The latent sub-layer's half that every latent block shares (`dots3`, `pangu_moe`, `xing4`):
the row a cached token keeps and how it is computed, how a decode step puts it, how many keys a
prefill chunk takes at a time, and the sizes, a scaled rotary's two numbers among them.

    c_q = r_q RMSNorm(h W_qa);  q = c_q W_qb -> H x [nope | rope], rotary on rope
    [c_kv | k_r] = h W_kva;  c_kv = r_kv RMSNorm(c_kv);  k_r rotated, one for all heads
    cached per token: the row c_kv | k_r

with r = sqrt(hidden / rank) where the model rescales its latents (`mla_rescale`) and 1 where it
does not. What reads the rows is each block's own: a prefill chunk expands keys and values from
them a block of keys at a time (`ops/latent_attention.py:latent_chunk_attention`), a decode step
folds W_kvb into the query and the output and runs over the rows as they lie (`dots3` over the
rows its indexer chose or its ring holds, `pangu_moe` and `xing4` over the whole slab through the
kernel `latent_attn`). How wide a slab keeps the row is the block's too (576 as it is in `dots3`,
640, whole rows of 128 lanes, in `pangu_moe`: ROADMAP S10).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm, _rope, yarn_inv_freq, yarn_mscale


def attn_dims(cfg: ModelConfig, full: bool = True) -> dict:
    """Heads, latent ranks, head sizes and rope base of one kind of layer: the model's latent
    fields, or (`full` off) those of `dots3`'s windowed layers."""
    if full:
        d = dict(heads=cfg.n_heads, q_rank=cfg.q_lora_rank, kv_rank=cfg.kv_lora_rank,
                 nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim, v=cfg.v_head_dim,
                 theta=cfg.rope_theta)
        if cfg.rope_scaling:  # a scaled rotary: its table of frequencies and what it multiplies the scores by
            scaling = dict(cfg.rope_scaling)
            if scaling.get("type") != "yarn" or yarn_mscale(scaling, "mscale") != yarn_mscale(scaling, "mscale_all_dim"):
                raise ValueError(f"rope_scaling {scaling}: only yarn with mscale equal to mscale_all_dim "
                                 "(cos and sin unscaled) is written")
            d.update(inv_freq=yarn_inv_freq(d["rope"], d["theta"], scaling),
                     score_scale=yarn_mscale(scaling, "mscale_all_dim") ** 2)
        return d
    return dict(heads=cfg.swa_n_heads, q_rank=cfg.swa_q_lora_rank, kv_rank=cfg.swa_kv_lora_rank,
                nope=cfg.swa_qk_nope_head_dim, rope=cfg.swa_qk_rope_head_dim, v=cfg.swa_v_head_dim,
                theta=cfg.swa_rope_theta)


def rescale(cfg: ModelConfig, rank: int) -> float:
    """`apply_mla_qkv_lora_rescale`: a latent is scaled by sqrt(hidden / rank) after its norm."""
    return math.sqrt(cfg.hidden / rank) if cfg.mla_rescale else 1.0


def rope_rows(x, positions, theta, inv_freq=None):
    """Rotary on rows that have no head axis. x: [..., S, R]; positions: [..., S]."""
    return _rope(x[..., None, :], positions, theta, inv_freq)[..., 0, :]


def latents(p, x, positions, cfg: ModelConfig, d: dict):
    """x: [B, S, D] -> c_q [B, S, q_rank], q_nope [B, S, H, nope], q_rope (rotated)
    [B, S, H, rope], and the row the cache keeps, c_kv | k_r (rotated) [B, S, kv_rank + rope]."""
    c_q = _rmsnorm(_dense(x, p["q_a"]["kernel"]), p["q_norm"]["scale"], cfg.norm_eps)
    c_q = c_q * jnp.asarray(rescale(cfg, d["q_rank"]), c_q.dtype)
    q = _dense(c_q, p["q_b"]["kernel"].reshape(d["q_rank"], -1))
    q = q.reshape(x.shape[:2] + (d["heads"], d["nope"] + d["rope"]))
    q_nope, q_rope = q[..., :d["nope"]], _rope(q[..., d["nope"]:], positions, d["theta"], d.get("inv_freq"))
    kv = _dense(x, p["kv_a"]["kernel"])
    c_kv = _rmsnorm(kv[..., :d["kv_rank"]], p["kv_norm"]["scale"], cfg.norm_eps)
    c_kv = c_kv * jnp.asarray(rescale(cfg, d["kv_rank"]), c_kv.dtype)
    k_r = rope_rows(kv[..., d["kv_rank"]:], positions, d["theta"], d.get("inv_freq"))
    return c_q, q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def put_row(cache, row, at, gate):
    """cache: [B, rows, W]; row: [B, 1, W]; slot b's row lands at `at[b]` where `gate[b]`."""

    def put(slot_cache, slot_row, a, g):
        cur = jax.lax.dynamic_slice(slot_cache, (a, 0), slot_row.shape)
        return jax.lax.dynamic_update_slice(slot_cache, jnp.where(g, slot_row, cur), (a, 0))

    return jax.vmap(put)(cache, row.astype(cache.dtype), at, gate)


def key_block(rows: int, queries: int) -> int:
    """Keys a chunk of `queries` attends at a time: a power of two from 1024 down that
    divides the cache's rows at least four times (so that small caches, as the tests' are,
    still take several blocks), halved while a block of scores (heads x queries x keys,
    float32) would pass a million a head."""
    kb = 1024
    while kb > 16 and (rows % kb or 4 * kb > rows):
        kb //= 2
    while kb > 128 and queries * kb > (1 << 20):
        kb //= 2
    return math.gcd(rows, kb)
