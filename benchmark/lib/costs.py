"""Operations and bytes the algorithm needs, from a configuration's shapes alone.

`cfg` is the dict of a `benchmark/configs/<name>.json` file (`ModelConfig` field
names). Recomputed operations (remat) are not counted: these are the operations the
mathematics requires, which is what a utilisation is taken against.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden"] // cfg["n_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """q, k, v, o and the three SwiGLU matrices of one block."""
    attn = cfg["hidden"] * head_dim(cfg) * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    mlp = 3 * cfg["hidden"] * cfg["mlp_dim"]
    return attn + mlp


def matmul_params(cfg: dict) -> int:
    """Parameters that are multiplied per token: the blocks and the output head. The
    embedding table is a gather, not a matmul (embeddings are untied here)."""
    head = cfg["hidden"] * cfg["vocab_size"]
    return cfg["n_layers"] * layer_matmul_params(cfg) + head


def total_params(cfg: dict) -> int:
    emb = cfg["hidden"] * cfg["vocab_size"]
    head = 0 if cfg.get("tie_embeddings") else emb
    norms = 2 * cfg["hidden"]
    return emb + head + cfg["n_layers"] * (layer_matmul_params(cfg) + norms) + cfg["hidden"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul parameter, plus causal attention. Scores and
    the weighted sum are 2 * 2 * seq * hidden forward per token and layer when every
    key is visible; backward doubles it again (12), and a causal mask leaves half (6)."""
    attn = 6 * cfg["n_layers"] * cfg["n_heads"] * head_dim(cfg) * seq
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V rows of every layer for one token, in the cache's type (bf16)."""
    return 2 * cfg["n_layers"] * cfg["n_kv_heads"] * head_dim(cfg) * dtype_bytes


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight once at the
    configuration's compute width (bf16), and the K and V rows that are live in the
    active slots. What the engine reads beyond that (float32 weights, dead rows up to
    `max_seq`, an undonated slab) is the gap this number exposes."""
    return matmul_params(cfg) * weight_bytes + live_rows * kv_bytes_per_token(cfg)
