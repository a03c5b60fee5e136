"""Operations and bytes the `laguna` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/laguna-s-2.1.json` (`ModelConfig` field names).
What the mathematics requires of THIS chip: the attention's, the dense layer's, the routers' and
the shared experts' matrices and the head's slice once; in each expert layer the held experts that
some token of the step is routed to, each once (not all 64 where fewer are hit, and none of the
192 that are absent); of the cache the rows a layer can see: every live row of a slot in a full
layer, at most `sliding_window` of them in a sliding one. Nothing is imported from the program.
"""

from __future__ import annotations

DECODE_TOKENS = 24  # tokens a decode step carries where a caller says nothing: the cell's slots


def _kinds(cfg: dict) -> tuple:
    """(full layers, sliding layers)."""
    full = sum(t == "full_attention" for t in cfg["layer_types"])
    return full, cfg["n_layers"] - full


def _dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense", 1)


def _expert_layers(cfg: dict) -> int:
    return cfg["n_layers"] - _dense_layers(cfg)


def attn_params(cfg: dict, full: bool) -> int:
    """One layer's attention matrices: W_q, W_k, W_v, W_o and the gate W_g, at the kind's heads."""
    D, hd, Hkv = cfg["hidden"], cfg["head_width"], cfg["n_kv_heads"]
    H = cfg["n_heads"] if full else cfg["swa_n_heads"]
    return 2 * D * H * hd + 2 * D * Hkv * hd + D * H


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["moe_mlp_dim"]


def fixed_matmul_params(cfg: dict) -> int:
    """What every token multiplies here whatever it is routed to: attention of both kinds, the
    leading dense MLP, routers, shared experts, the head's slice."""
    D = cfg["hidden"]
    full, sliding = _kinds(cfg)
    return (full * attn_params(cfg, True) + sliding * attn_params(cfg, False) + _dense_layers(cfg) * 3 * D * cfg["mlp_dim"]
            + _expert_layers(cfg) * (D * cfg["n_routed_experts_total"] + cfg.get("n_shared_experts", 1) * expert_params(cfg))
            + D * cfg["vocab_size"])


def held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / cfg["n_routed_experts_total"]


def matmul_params(cfg: dict) -> float:
    """Parameters multiplied per token on this chip: the fixed part and, in each expert layer, the
    `experts_per_token` routed experts times the share of them held here."""
    return fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["experts_per_token"] * held_share(cfg) * expert_params(cfg)


def norm_params(cfg: dict) -> int:
    """Norm gains: two of the hidden width a layer and the final one."""
    return (2 * cfg["n_layers"] + 1) * cfg["hidden"]


def total_params(cfg: dict, norms: bool = True) -> int:
    """Every parameter this chip holds: the matrices, every held expert, the embedding's slice and,
    unless `norms` is off (the issue's count leaves them out), the norms' gains."""
    return (fixed_matmul_params(cfg) + _expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["hidden"] * cfg["vocab_size"] + (norm_params(cfg) if norms else 0))


def published_params(cfg: dict, published: dict) -> int:
    """The whole model's matrices, norms left out: `cfg`'s widths at the published counts
    (`num_hidden_layers`, `num_experts`, `vocab_size`; the pattern of kinds repeats, the leading
    dense layers counted once)."""
    whole = dict(cfg, n_layers=published["num_hidden_layers"], vocab_size=published["vocab_size"],
                 n_routed_experts=published["num_experts"])
    period = cfg["layer_types"][_dense_layers(cfg):]
    rest = published["num_hidden_layers"] - _dense_layers(cfg)
    whole["layer_types"] = list(cfg["layer_types"][:_dense_layers(cfg)]) + [period[i % len(period)] for i in range(rest)]
    return total_params(whole, norms=False)


def row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """One cached position's K and V in one layer (4096 bytes)."""
    return 2 * cfg["n_kv_heads"] * cfg["head_width"] * dtype_bytes


def slab_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds: K and V in each full layer (8192 bytes)."""
    full, _ = _kinds(cfg)
    return full * row_bytes(cfg, dtype_bytes)


def ring_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """A slot's K and V ring in one sliding layer (2.10 MB), whatever the context."""
    return cfg["sliding_window"] * row_bytes(cfg, dtype_bytes)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds, as the harness asks it of every block: the full layers' slabs. A
    sliding layer's ring does not grow with the context."""
    return slab_bytes_per_token(cfg, dtype_bytes)


def cache_bytes(cfg: dict, slots: int, max_seq: int, dtype_bytes: int = 2) -> int:
    """The engine's cache: `slots` slabs of `max_seq` rows in the full layers, `slots` rings in the
    sliding ones."""
    _, sliding = _kinds(cfg)
    return slots * (max_seq * slab_bytes_per_token(cfg, dtype_bytes) + sliding * ring_bytes(cfg, dtype_bytes))


def visible_rows(cfg: dict, lens) -> tuple:
    """(rows the full layers' queries see, rows the sliding layers' see) in a decode step of slots
    at lengths `lens` (each the rows a slot holds, the new one counted), summed over the layers."""
    full, sliding = _kinds(cfg)
    return full * sum(lens), sliding * sum(min(n, cfg["sliding_window"]) for n in lens)


def chunk_pair_flops(cfg: dict) -> int:
    """Operations a chunk's full layer spends on one visible query-key pair: the score and the
    weighted sum over every head (4 x 48 x 128)."""
    return 4 * cfg["n_heads"] * cfg["head_width"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were this cut trained: 6 per matmul parameter, and attention over half
    the sequence in a full layer and over the window in a sliding one (4 x width forward, x 3 with backward)."""
    full, sliding = _kinds(cfg)
    hd = cfg["head_width"]
    attn = full * 4 * cfg["n_heads"] * hd * seq / 2.0 + sliding * 4 * cfg["swa_n_heads"] * hd * min(seq / 2.0, cfg["sliding_window"])
    return 6.0 * matmul_params(cfg) + 3.0 * attn


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected count of held experts that at least one of `tokens` tokens is routed to, under
    even routing: each token misses a given expert with probability 1 - k / total."""
    miss = 1.0 - cfg["experts_per_token"] / cfg["n_routed_experts_total"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def experts_step_bytes(cfg: dict, hit: float, weight_bytes: int = 2) -> float:
    """Bytes the expert layers of one step have to read where `hit` held experts a layer took a
    token: each of them once, its three matrices."""
    return _expert_layers(cfg) * hit * expert_params(cfg) * weight_bytes


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, tokens: float = DECODE_TOKENS) -> float:
    """Bytes one decode step of `tokens` slots holding `live_rows` rows in all has to read: the fixed
    matrices once in bf16, the held experts some token is routed to, every live row in the full
    layers and at most `sliding_window` rows a slot in the sliding ones (the slots taken as equally long)."""
    per_slot = live_rows / max(tokens, 1)
    rows_full, rows_window = visible_rows(cfg, [per_slot] * int(round(tokens)))
    return (fixed_matmul_params(cfg) * weight_bytes + experts_step_bytes(cfg, experts_hit(cfg, tokens), weight_bytes)
            + (rows_full + rows_window) * row_bytes(cfg))
