"""Device self time a step under the scope `attn` (projections, rotary, the flash kernels, the
output projection), forward, recomputed and backward operations together: the scope path is
the `tf_op` of each operation's metadata in the device trace (`lib/program_trace.py`)."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "attn_dev_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)
SCOPES = ("attn",)


def read(record):
    events = pt.for_record(record)
    return None if events is None else pt.scope_ms_per_step(events, SCOPES)
