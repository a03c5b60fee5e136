"""Operations and bytes the `granite_hybrid` block needs, from a configuration's shapes alone.

`cfg` is the `model` dict of `benchmark/configs/granite-4.0-h-micro.json` (`ModelConfig`
field names). What the mathematics requires: every matmul weight once, the live K and V rows
of the attention layers, and, which no other block has, a read and a write of the recurrent
state of every slot that takes the step. Nothing is imported from the program.
"""

from __future__ import annotations

DECODE_SLOTS = 48  # slots a decode step advances where a caller says nothing: the cell's slots


def _kinds(cfg: dict) -> tuple:
    mamba = sum(t == "mamba" for t in cfg["layer_types"])
    return mamba, cfg["n_layers"] - mamba


def _inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def _conv_width(cfg: dict) -> int:
    return _inner(cfg) + 2 * cfg["mamba_d_state"]


def mamba_params(cfg: dict) -> int:
    """One mamba mixer's matrices: in_proj (z | xBC | dt) and out_proj."""
    return cfg["hidden"] * (_inner(cfg) + _conv_width(cfg) + cfg["mamba_n_heads"]) + _inner(cfg) * cfg["hidden"]


def mamba_small_params(cfg: dict) -> int:
    """What a mamba mixer holds besides: the convolution's taps and bias, A_log, D, dt_bias, the gated norm."""
    return (cfg["mamba_d_conv"] + 1) * _conv_width(cfg) + 3 * cfg["mamba_n_heads"] + _inner(cfg)


def attn_params(cfg: dict) -> int:
    hd = cfg["hidden"] // cfg["n_heads"]
    return 2 * cfg["hidden"] * cfg["n_heads"] * hd + 2 * cfg["hidden"] * cfg["n_kv_heads"] * hd


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden"] * cfg["mlp_dim"]


def matmul_params(cfg: dict) -> int:
    """Parameters multiplied per token: the mixers' and MLPs' matrices and the head, which is the
    embedding again."""
    mamba, attn = _kinds(cfg)
    return (mamba * mamba_params(cfg) + attn * attn_params(cfg) + cfg["n_layers"] * mlp_params(cfg)
            + cfg["hidden"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    """Every parameter: the tied embedding once, the layers' matrices, their small vectors and norms."""
    mamba, _ = _kinds(cfg)
    return matmul_params(cfg) + mamba * mamba_small_params(cfg) + (2 * cfg["n_layers"] + 1) * cfg["hidden"]


def recurrent_state_bytes(cfg: dict) -> int:
    """The float32 recurrent state alone, a slot: H x P x N a mamba layer."""
    mamba, _ = _kinds(cfg)
    return mamba * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * 4


def ssm_state_bytes(cfg: dict, conv_bytes: int = 2) -> int:
    """A slot's state over all mamba layers: the recurrent state in float32 and the convolution's
    last `mamba_d_conv - 1` inputs in bfloat16."""
    mamba, _ = _kinds(cfg)
    return recurrent_state_bytes(cfg) + mamba * (cfg["mamba_d_conv"] - 1) * _conv_width(cfg) * conv_bytes


def mamba_layers_step_bytes(cfg: dict, slots: float, weight_bytes: int = 2) -> float:
    """Bytes one decode step of `slots` slots has to move in the mamba layers alone: their mixers'
    and MLPs' matrices once, and a read and a write of each slot's state."""
    mamba, _ = _kinds(cfg)
    return mamba * (mamba_params(cfg) + mlp_params(cfg)) * weight_bytes + 2.0 * slots * ssm_state_bytes(cfg)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What a cached token adds: K and V in each attention layer. A mamba layer's state does not
    grow with the context."""
    _, attn = _kinds(cfg)
    return attn * 2 * cfg["n_kv_heads"] * (cfg["hidden"] // cfg["n_heads"]) * dtype_bytes


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, were the block trained: 6 per matmul parameter; attention over half
    the sequence in the attention layers (4 x width forward, x 3 with backward); in a mamba layer
    the recurrence's own 6 H P N a token (update, decay, read-out), x 3."""
    mamba, attn = _kinds(cfg)
    hd = cfg["hidden"] // cfg["n_heads"]
    ssm = 6 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return 6.0 * matmul_params(cfg) + 3.0 * (attn * 4 * cfg["n_heads"] * hd * seq / 2.0 + mamba * ssm)


def decode_step_bytes(cfg: dict, live_rows: float, weight_bytes: int = 2, slots: float = DECODE_SLOTS) -> float:
    """Bytes one decode step of `slots` slots holding `live_rows` rows has to move: every matmul
    weight once in bf16, the live K and V rows of the attention layers, and a read and a write of
    each slot's state."""
    return matmul_params(cfg) * weight_bytes + live_rows * kv_bytes_per_token(cfg) + 2.0 * slots * ssm_state_bytes(cfg)
