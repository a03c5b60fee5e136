"""Test only: the costs of a block named `toy`: `lib/costs.py`'s, with twice the
operations a token, by which the test sees that a reader took them from here."""
from lib.costs import decode_step_bytes, kv_bytes_per_token, matmul_params, total_params  # noqa: F401
from lib import costs as _dense


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 2.0 * _dense.train_flops_per_token(cfg, seq)
