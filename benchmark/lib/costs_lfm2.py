"""Operations and bytes the `lfm2` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/lfm2-24b-a2b.json` (`ModelConfig` field names).
What the mathematics requires: the operators', the dense layers' and the routers' matrices and
the head once; in each expert layer the experts that some token of the step is routed to, each
once (not all 64 where fewer are hit, and not once a tile); the live K and V rows of the
attention layers; a read and a write of the convolution inputs of every slot that takes the
step. Nothing is imported from the program.
"""

from __future__ import annotations

DECODE_TOKENS = 64  # tokens a decode step carries where a caller says nothing: the cell's slots


def _kinds(cfg: dict) -> tuple:
    conv = sum(t == "conv" for t in cfg["layer_types"])
    return conv, cfg["n_layers"] - conv


def _dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense", 1)


def _expert_layers(cfg: dict) -> int:
    return cfg["n_layers"] - _dense_layers(cfg)


def _taps(cfg: dict) -> int:
    return cfg.get("conv_L_cache", 3)


def conv_params(cfg: dict) -> int:
    """One conv operator's matrices: in_proj (B | C | u) and out_proj."""
    return cfg["hidden"] * 3 * cfg["hidden"] + cfg["hidden"] * cfg["hidden"]


def attn_params(cfg: dict) -> int:
    hd = cfg["hidden"] // cfg["n_heads"]
    return 2 * cfg["hidden"] * cfg["n_heads"] * hd + 2 * cfg["hidden"] * cfg["n_kv_heads"] * hd


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["moe_mlp_dim"]


def fixed_matmul_params(cfg: dict) -> int:
    """What every token multiplies whatever it is routed to: the operators, the leading dense
    MLPs, the routers, and the head, which is the embedding again."""
    conv, attn = _kinds(cfg)
    D = cfg["hidden"]
    return (conv * conv_params(cfg) + attn * attn_params(cfg) + _dense_layers(cfg) * 3 * D * cfg["mlp_dim"]
            + _expert_layers(cfg) * D * cfg["n_routed_experts_total"] + D * cfg["vocab_size"])


def matmul_params(cfg: dict) -> int:
    """Parameters multiplied per token: the fixed part and `experts_per_token` experts in each
    expert layer."""
    return fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["experts_per_token"] * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """Every parameter held: the tied embedding once, every expert, the operators, the taps, the
    routers with their selection biases, and the norms (two a layer, two of a head's width in an
    attention layer, the final one)."""
    conv, attn = _kinds(cfg)
    hd = cfg["hidden"] // cfg["n_heads"]
    small = (conv * _taps(cfg) * cfg["hidden"] + attn * 2 * hd + _expert_layers(cfg) * cfg["n_routed_experts_total"]
             + (2 * cfg["n_layers"] + 1) * cfg["hidden"])
    return fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg) + small


def conv_state_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """A slot's state over all conv layers: the last `conv_L_cache - 1` gated inputs."""
    conv, _ = _kinds(cfg)
    return conv * (_taps(cfg) - 1) * cfg["hidden"] * dtype_bytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds: K and V in each attention layer. A conv layer's state does not
    grow with the context."""
    _, attn = _kinds(cfg)
    return attn * 2 * cfg["n_kv_heads"] * (cfg["hidden"] // cfg["n_heads"]) * dtype_bytes


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were the block trained: 6 per matmul parameter; attention over half
    the sequence in the attention layers (4 x width forward, x 3 with backward)."""
    _, attn = _kinds(cfg)
    return 6.0 * matmul_params(cfg) + 3.0 * attn * 4 * cfg["hidden"] * seq / 2.0


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected count of a layer's experts that at least one of `tokens` tokens is routed to, under
    even routing: each token misses a given expert with probability 1 - k / total."""
    miss = 1.0 - cfg["experts_per_token"] / cfg["n_routed_experts_total"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def experts_step_bytes(cfg: dict, hit: float, weight_bytes: int = 2) -> float:
    """Bytes the expert layers of one step have to read where `hit` experts a layer took a token:
    each of them once, its three matrices. The tokens' own rows (256 pairs of 4 KB in, 4 KB out at
    64 slots: 2 MB a layer beside 1.2 GB) are left out."""
    return _expert_layers(cfg) * hit * expert_params(cfg) * weight_bytes


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, tokens: float = DECODE_TOKENS) -> float:
    """Bytes one decode step of `tokens` slots holding `live_rows` rows has to move: the fixed
    matrices once in bf16, the experts some token is routed to, the live K and V rows of the
    attention layers, and a read and a write of each slot's convolution inputs."""
    return (fixed_matmul_params(cfg) * weight_bytes + experts_step_bytes(cfg, experts_hit(cfg, tokens), weight_bytes)
            + live_rows * kv_bytes_per_token(cfg) + 2.0 * tokens * conv_state_bytes(cfg))
