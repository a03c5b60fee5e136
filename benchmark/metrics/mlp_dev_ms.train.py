"""Device self time a step under the scope `mlp` (gate, up, down and the activation), forward,
recomputed and backward operations together (`lib/program_trace.py`)."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "mlp_dev_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)
SCOPES = ("mlp",)


def read(record):
    events = pt.for_record(record)
    return None if events is None else pt.scope_ms_per_step(events, SCOPES)
