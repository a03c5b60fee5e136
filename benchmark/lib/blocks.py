"""The block a configuration runs, found by name like everything else.

A configuration file may say `"block": "<name>"`. Its plain reference is then
`lib/reference_<name>.py` and its count of operations and bytes `lib/costs_<name>.py`;
a file without the key gets `lib/reference.py` and `lib/costs.py` (the dense
llama-family block of the first two configurations). Drivers, `lib/serving.py` and the
readers that count operations or bytes ask here and never look at the name.

`of` is any mapping that may hold the key: the configuration file (`ctx.config`) or a
run's record (`run.py` copies the key into it).

What a block's two modules must offer (`benchmark/README.md`, "A block"):

- reference: `plain_tree`, `forward`, `token_losses`, `loss`, `greedy`, `compare_greedy`,
  `LOSS_ABS_TOL`, `TOKEN_LOSS_RMS_TOL`, `NEAR_TIE_MARGIN`, `MIN_COMPARED_POSITIONS`, `MAX_PROBES`. A block
  states and defends its own tolerances; it inherits none.
- costs: `matmul_params`, `total_params`, `train_flops_per_token`,
  `kv_bytes_per_token`, `decode_step_bytes`.
"""

from __future__ import annotations

import importlib

REFERENCE_NAMES = ("plain_tree", "forward", "token_losses", "loss", "greedy", "compare_greedy", "LOSS_ABS_TOL",
                   "TOKEN_LOSS_RMS_TOL", "NEAR_TIE_MARGIN", "MIN_COMPARED_POSITIONS", "MAX_PROBES")
COSTS_NAMES = ("matmul_params", "total_params", "train_flops_per_token", "kv_bytes_per_token",
               "decode_step_bytes")


def _module(kind: str, of, names: tuple):
    block = of.get("block")
    mod = importlib.import_module(f"lib.{kind}_{block}" if block else f"lib.{kind}")
    missing = [n for n in names if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"{mod.__name__} lacks {missing}: see benchmark/README.md, 'A block'")
    return mod


def reference(of):
    """The plain reference of the block that `of` names."""
    return _module("reference", of, REFERENCE_NAMES)


def costs(of):
    """The operations and bytes of the block that `of` names."""
    return _module("costs", of, COSTS_NAMES)
