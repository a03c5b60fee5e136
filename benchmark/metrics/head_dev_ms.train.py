"""Device self time a step under the scopes `lm_head` and `loss`: the output projection and the
cross-entropy, forward and backward. A fusion counts for the one scope the compiler names it
by: the head's weight gradient fused with its optimizer update counts here."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "head_dev_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)
SCOPES = ("lm_head", "loss")


def read(record):
    events = pt.for_record(record)
    return None if events is None else pt.scope_ms_per_step(events, SCOPES)
