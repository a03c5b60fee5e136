"""Device self time a step under the scope `optimizer` (`parallel/spmd.py`: the optax update and
`apply_updates`). Updates the compiler fused into a gradient's matmul count for that matmul's
scope, not here."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "opt_dev_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)
SCOPES = ("optimizer",)


def read(record):
    events = pt.for_record(record)
    return None if events is None else pt.scope_ms_per_step(events, SCOPES)
