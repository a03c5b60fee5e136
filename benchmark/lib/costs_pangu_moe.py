"""Operations and bytes the `pangu_moe` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/openpangu-ultra-moe-718b.json` (`ModelConfig`
field names). What the mathematics requires of THIS chip: the experts a token is routed to among
those held here (not all 8, and none of the 248 that are absent), and of the cache every live row
once a layer: attention is dense, a query scores every row up to its own. Nothing is imported
from the program.
"""

from __future__ import annotations

DECODE_TOKENS = 16  # tokens a decode step carries where a caller says nothing: the cell's slots


def attn_params(cfg: dict) -> int:
    """One layer's attention matrices: W_qa, W_qb, W_kva, W_kvb, W_o."""
    D, H, qr, kvr = cfg["hidden"], cfg["n_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return D * qr + qr * H * (nope + rope) + D * (kvr + rope) + kvr * H * (nope + v) + H * v * D


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["moe_mlp_dim"]


def _dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense", 1)


def _expert_layers(cfg: dict) -> int:
    return cfg["n_layers"] - _dense_layers(cfg)


def fixed_matmul_params(cfg: dict) -> int:
    """What every token multiplies here whatever it is routed to: attention, the leading dense
    MLPs, routers, shared experts, the head's slice."""
    D = cfg["hidden"]
    return (cfg["n_layers"] * attn_params(cfg) + _dense_layers(cfg) * 3 * D * cfg["mlp_dim"]
            + _expert_layers(cfg) * (D * cfg["n_routed_experts_total"] + cfg.get("n_shared_experts", 1) * expert_params(cfg))
            + D * cfg["vocab_size"])


def held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / cfg["n_routed_experts_total"]


def matmul_params(cfg: dict) -> float:
    """Parameters multiplied per token on this chip: the fixed part and, in each expert layer,
    the `experts_per_token` routed experts times the share of them held here."""
    return fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["experts_per_token"] * held_share(cfg) * expert_params(cfg)


def norm_params(cfg: dict) -> int:
    """Norm gains: four of the hidden width a layer (before and after each sub-layer), the two latents', the final one."""
    per_layer = 4 * cfg["hidden"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return cfg["n_layers"] * per_layer + cfg["hidden"]


def total_params(cfg: dict) -> int:
    """Every parameter this chip holds: the matrices, every held expert, the embedding's slice, the norms' gains."""
    return (fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["hidden"] * cfg["vocab_size"] + norm_params(cfg))


def latent_row_values(cfg: dict) -> int:
    """Values a cached token keeps in a layer: c_kv | k_r."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics reads of a cached row in a layer (1152 bytes; the program keeps it in
    whole rows of 128 lanes, 1280, which no function here counts as needed)."""
    return latent_row_values(cfg) * dtype_bytes


def latent_row_flops(cfg: dict) -> int:
    """Operations a decode step's query spends on one cached row of one layer, W_kvb folded in:
    every head's score over c_kv | k_r and its weighted sum over c_kv."""
    return 2 * cfg["n_heads"] * (latent_row_values(cfg) + cfg["kv_lora_rank"])


def latent_step_need_s(cfg: dict, live_rows: float, peaks: dict) -> float:
    """Seconds the dense latent attention of one decode step needs over `live_rows` rows (all
    slots together) in every layer: the greater of its bytes at the memory's speed and its
    operations at the bf16 peak (at 241 FLOP a byte it sits on a v5e's ridge)."""
    n = cfg["n_layers"] * live_rows
    return max(n * latent_row_bytes(cfg) / peaks["hbm_bytes_per_s"], n * latent_row_flops(cfg) / peaks["bf16_flops"])


def latent_attn_call_need_s(cfg: dict, live_rows: float, peaks: dict) -> float:
    """Seconds one call of the kernel `latent_attn` (one layer, every slot) needs over `live_rows`
    rows: a layer's part of `latent_step_need_s`, the mathematics' bytes and operations whatever
    lanes the kernel keeps a row in (576 values as 640: the padding reads as a gap, not as work)."""
    return latent_step_need_s(cfg, live_rows, peaks) / cfg["n_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were this cut trained: 6 per matmul parameter, and attention over
    half the sequence in every layer (2 x heads x (qk + v) forward, x 3 with backward)."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 6.0 * matmul_params(cfg) + 3.0 * cfg["n_layers"] * 2 * cfg["n_heads"] * (qk + cfg["v_head_dim"]) * seq / 2.0


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds: a latent row (c_kv | k_r) in every layer, and nothing else."""
    return cfg["n_layers"] * latent_row_bytes(cfg, dtype_bytes)


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected count of held experts that at least one of `tokens` tokens is routed to, under
    even routing: each token misses a given expert with probability 1 - k / total."""
    miss = 1.0 - cfg["experts_per_token"] / cfg["n_routed_experts_total"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, tokens: float = DECODE_TOKENS) -> float:
    """Bytes one decode step of `tokens` slots holding `live_rows` rows has to read: the fixed
    matrices once in bf16, in each expert layer the held experts some token is routed to (3.2 of 8
    at 16 tokens), and every live row's 1152 bytes in every layer."""
    weights = fixed_matmul_params(cfg) + _expert_layers(cfg) * experts_hit(cfg, tokens) * expert_params(cfg)
    return weights * weight_bytes + live_rows * kv_bytes_per_token(cfg)
