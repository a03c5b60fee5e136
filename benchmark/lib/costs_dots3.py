"""Operations and bytes the `dots3` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/dots3-note-prev.json` (`ModelConfig` field
names). What the mathematics requires of THIS chip: the experts a token is routed to among
those held here (not all 32, and none of the 224 that are absent), the rows a query selects
(not every cached row), the indexer keys it has to score (every live one). Nothing is
imported from the program.
"""

from __future__ import annotations

DECODE_TOKENS = 16  # tokens a decode step carries where a caller says nothing: the cell's slots


def _kinds(cfg: dict) -> tuple:
    full = sum(t == "full_attention" for t in cfg["layer_types"])
    return full, cfg["n_layers"] - full


def attn_params(cfg: dict, full: bool) -> int:
    """One layer's attention matrices (the indexer's with a full layer's)."""
    D = cfg["hidden"]
    if full:
        H, qr, kvr = cfg["n_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
        nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        index = qr * cfg["index_n_heads"] * cfg["index_head_dim"] + D * cfg["index_head_dim"] + D * cfg["index_n_heads"]
    else:
        H, qr, kvr = cfg["swa_n_heads"], cfg["swa_q_lora_rank"], cfg["swa_kv_lora_rank"]
        nope, rope, v = cfg["swa_qk_nope_head_dim"], cfg["swa_qk_rope_head_dim"], cfg["swa_v_head_dim"]
        index = 0
    return D * qr + qr * H * (nope + rope) + D * (kvr + rope) + kvr * H * (nope + v) + D * H + H * v * D + index


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["moe_mlp_dim"]


def _dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense", 1)


def fixed_matmul_params(cfg: dict) -> int:
    """What every token multiplies here whatever it is routed to: attention, the leading
    dense MLPs, routers, shared experts, the head's slice."""
    full, sliding = _kinds(cfg)
    D, n_moe = cfg["hidden"], cfg["n_layers"] - _dense_layers(cfg)
    return (full * attn_params(cfg, True) + sliding * attn_params(cfg, False)
            + _dense_layers(cfg) * 3 * D * cfg["mlp_dim"]
            + n_moe * (D * cfg["n_routed_experts_total"] + cfg.get("n_shared_experts", 1) * expert_params(cfg))
            + D * cfg["vocab_size"])


def held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / cfg["n_routed_experts_total"]


def matmul_params(cfg: dict) -> float:
    """Parameters multiplied per token on this chip: the fixed part and, in each expert
    layer, the `experts_per_token` routed experts times the share of them held here."""
    n_moe = cfg["n_layers"] - _dense_layers(cfg)
    return fixed_matmul_params(cfg) + n_moe * cfg["experts_per_token"] * held_share(cfg) * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """Every parameter this chip holds (norm scales and biases left out: under 0.01%)."""
    n_moe = cfg["n_layers"] - _dense_layers(cfg)
    return fixed_matmul_params(cfg) + n_moe * cfg["n_routed_experts"] * expert_params(cfg) + cfg["hidden"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were this cut trained: 6 per matmul parameter, the indexer's
    scores over half the sequence, attention over min(index_topk, half the sequence) keys in a
    full layer and min(window, half) in a sliding one (4 x width forward, x 3 with backward)."""
    full, sliding = _kinds(cfg)
    half = seq / 2.0
    index = 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * half
    keys_f, keys_s = min(cfg["index_topk"], half), min(cfg["sliding_window"], half)
    attn_f = 2 * cfg["n_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * keys_f
    attn_s = 2 * cfg["swa_n_heads"] * (cfg["swa_qk_nope_head_dim"] + cfg["swa_qk_rope_head_dim"] + cfg["swa_v_head_dim"]) * keys_s
    return 6.0 * matmul_params(cfg) + 3.0 * (full * (index + attn_f) + sliding * attn_s)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds: in each full layer a latent row (c_kv | k_r) and an indexer
    key. A sliding layer's ring does not grow with the context."""
    full, _ = _kinds(cfg)
    return full * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["index_head_dim"]) * dtype_bytes


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected count of held experts that at least one of `tokens` tokens is routed to, under
    even routing: each token misses a given expert with probability 1 - k / total."""
    miss = 1.0 - cfg["experts_per_token"] / cfg["n_routed_experts_total"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, tokens: float = DECODE_TOKENS) -> float:
    """Bytes one decode step of `tokens` slots holding `live_rows` rows has to read: the fixed
    matrices once in bf16; in each expert layer the held experts some token is routed to; in
    each full layer every live indexer key and, a slot, the selected latent rows (its context
    or `index_topk`, whichever is less); in each sliding layer the ring's live rows."""
    full, sliding = _kinds(cfg)
    n_moe = cfg["n_layers"] - _dense_layers(cfg)
    weights = fixed_matmul_params(cfg) + n_moe * experts_hit(cfg, tokens) * expert_params(cfg)
    context = live_rows / max(tokens, 1.0)
    latent = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2
    ring = (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) * 2
    cache = (full * (live_rows * cfg["index_head_dim"] * 2 + tokens * min(context, cfg["index_topk"]) * latent)
             + sliding * tokens * min(context, cfg["sliding_window"]) * ring)
    return weights * weight_bytes + cache
