"""Operations and bytes the `xing4` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/xing4.0-29b-a4b.json` (`ModelConfig` field names).
What the mathematics requires of this chip: every expert of a layer is held, so the experts a step's
tokens are routed to; of the cache every live row once a layer (attention is dense, a query scores
every row up to its own); of the hyper-connection the streams read once and written once a
sub-layer. Nothing is imported from the program.

ISSUE 42's arithmetic, which `benchmark/tests/test_xing4.py` holds these functions to: attention
28.41M parameters a layer, a hyper-connection 0.344M a sub-layer, a dense layer 128.2M, an expert
layer 745.0M (40.3M of it outside the routed experts), embedding and head 939.5M; the cut (one dense
and five expert layers) 4.793B, 9.59 GB in bfloat16; the cache 7680 bytes a token as the slabs hold
it, 48 slots of 8192 rows 3.02 GB.
"""

from __future__ import annotations

DECODE_TOKENS = 48  # tokens a decode step carries where a caller says nothing: the cell's slots
LANES = 128


def attn_params(cfg: dict) -> int:
    """One layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o and the two latents' norm gains."""
    D, H, qr, kvr = cfg["hidden"], cfg["n_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return D * qr + qr * H * (nope + rope) + D * (kvr + rope) + kvr * H * (nope + v) + H * v * D + qr + kvr


def hc_coefficients(cfg: dict) -> int:
    """Coefficients a sub-layer's hyper-connection computes a token: H_pre, H_post, H_res."""
    n = cfg["hc_mult"]
    return 2 * n + n * n


def hc_params(cfg: dict) -> int:
    """One sub-layer's hyper-connection: Phi (hc_mult x hidden by 2n + n^2), its biases, three gains."""
    return cfg["hc_mult"] * cfg["hidden"] * hc_coefficients(cfg) + hc_coefficients(cfg) + 3


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["moe_mlp_dim"]


def _dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense", 1)


def _expert_layers(cfg: dict) -> int:
    return cfg["n_layers"] - _dense_layers(cfg)


def layer_fixed_params(cfg: dict) -> int:
    """What every layer holds whatever its feed-forward part: attention, two hyper-connections, two norm gains."""
    return attn_params(cfg) + 2 * hc_params(cfg) + 2 * cfg["hidden"]


def dense_layer_params(cfg: dict) -> int:
    return layer_fixed_params(cfg) + 3 * cfg["hidden"] * cfg["mlp_dim"]


def expert_layer_fixed_params(cfg: dict) -> int:
    """An expert layer outside its routed experts: the fixed part, the router with its selection bias, the shared expert."""
    D, total = cfg["hidden"], cfg["n_routed_experts_total"]
    return layer_fixed_params(cfg) + D * total + total + cfg.get("n_shared_experts", 1) * expert_params(cfg)


def expert_layer_params(cfg: dict) -> int:
    return expert_layer_fixed_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def fixed_matmul_params(cfg: dict) -> int:
    """What every token multiplies whatever it is routed to: attention, the hyper-connections' projections,
    the leading dense MLPs, routers, shared experts, the head."""
    D = cfg["hidden"]
    per_layer = attn_params(cfg) - cfg["q_lora_rank"] - cfg["kv_lora_rank"] + 2 * cfg["hc_mult"] * D * hc_coefficients(cfg)
    return (cfg["n_layers"] * per_layer + _dense_layers(cfg) * 3 * D * cfg["mlp_dim"]
            + _expert_layers(cfg) * (D * cfg["n_routed_experts_total"] + cfg.get("n_shared_experts", 1) * expert_params(cfg))
            + D * cfg["vocab_size"])


def matmul_params(cfg: dict) -> float:
    """Parameters multiplied per token: the fixed part and, in each expert layer, `experts_per_token` routed experts."""
    return fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["experts_per_token"] * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """Every parameter this chip holds: the layers, the embedding, the untied head, the final norm's gain."""
    return (_dense_layers(cfg) * dense_layer_params(cfg) + _expert_layers(cfg) * expert_layer_params(cfg)
            + 2 * cfg["hidden"] * cfg["vocab_size"] + cfg["hidden"])


def latent_row_values(cfg: dict) -> int:
    """Values a cached token keeps in a layer: c_kv | k_r."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics reads of a cached row in a layer (1152 bytes)."""
    return latent_row_values(cfg) * dtype_bytes


def cache_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What the slabs hold of a token: in every layer a row kept in whole rows of 128 lanes (576 values as
    640: 1280 bytes; 7680 over six layers), which is what the cache's size is counted from."""
    return cfg["n_layers"] * -(-latent_row_values(cfg) // LANES) * LANES * dtype_bytes


def latent_row_flops(cfg: dict) -> int:
    """Operations a decode step's query spends on one cached row of one layer, W_kvb folded in:
    every head's score over c_kv | k_r and its weighted sum over c_kv (69,632 at 32 heads: 60 a byte)."""
    return 2 * cfg["n_heads"] * (latent_row_values(cfg) + cfg["kv_lora_rank"])


def latent_step_need_s(cfg: dict, live_rows: float, peaks: dict) -> float:
    """Seconds the dense latent attention of one decode step needs over `live_rows` rows (all slots
    together) in every layer: the greater of its bytes at the memory's speed and its operations at
    the bf16 peak (at 60 FLOP a byte against a v5e's ridge of 241, the bytes)."""
    n = cfg["n_layers"] * live_rows
    return max(n * latent_row_bytes(cfg) / peaks["hbm_bytes_per_s"], n * latent_row_flops(cfg) / peaks["bf16_flops"])


def latent_attn_call_need_s(cfg: dict, live_rows: float, peaks: dict) -> float:
    """Seconds one call of the kernel `latent_attn` (one layer, every slot) needs over `live_rows` rows."""
    return latent_step_need_s(cfg, live_rows, peaks) / cfg["n_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were this cut trained: 6 per matmul parameter, and attention over
    half the sequence in every layer (2 x heads x (qk + v) forward, x 3 with backward)."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 6.0 * matmul_params(cfg) + 3.0 * cfg["n_layers"] * 2 * cfg["n_heads"] * (qk + cfg["v_head_dim"]) * seq / 2.0


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a decode step has to read of a cached token: a latent row (c_kv | k_r, 1152 bytes) in every
    layer. The slabs hold more (`cache_bytes_per_token`): the padding is no work."""
    return cfg["n_layers"] * latent_row_bytes(cfg, dtype_bytes)


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected count of a layer's experts that at least one of `tokens` tokens is routed to, under
    even routing: each token misses a given expert with probability 1 - k / total (61.1 of 64 at 48 tokens)."""
    miss = 1.0 - cfg["experts_per_token"] / cfg["n_routed_experts_total"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def experts_step_bytes(cfg: dict, hit: float, weight_bytes: int = 2) -> float:
    """Bytes the expert layers of one step have to read where `hit` experts a layer took a token:
    each of them once, its three matrices."""
    return _expert_layers(cfg) * hit * expert_params(cfg) * weight_bytes


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, tokens: float = DECODE_TOKENS) -> float:
    """Bytes one decode step of `tokens` slots holding `live_rows` rows has to read: the fixed matrices
    once in bf16 (1.6 GB), the experts some token is routed to under even routing (6.7 GB at 48 tokens),
    and every live row's 1152 bytes in every layer. The harness's form takes no count of the experts hit:
    where routing is uneven, as at the configuration's seeded selection bias (43 of 64 hit a step, 4.7 GB:
    my chip run, PR 42), this over-counts, which is why the cell is on the list of neither
    `decode_roofline.serve` nor `decode_hbm_util.serve`; `experts_roofline.decode64` reads the program's count."""
    return (fixed_matmul_params(cfg) * weight_bytes + experts_step_bytes(cfg, experts_hit(cfg, tokens), weight_bytes)
            + live_rows * kv_bytes_per_token(cfg))


# -- the hyper-connection ----------------------------------------------------------------


def hc_sublayer_bytes(cfg: dict, tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes one sub-layer's hyper-connection has to move for `tokens` tokens: the streams read once
    and written once, the mixture written and the sub-layer's output read, Phi once."""
    D, n = cfg["hidden"], cfg["hc_mult"]
    return (tokens * (2 * n * D + 2 * D) + n * D * hc_coefficients(cfg)) * dtype_bytes
