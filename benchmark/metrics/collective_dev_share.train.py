"""Share of the first chip's device self time in the traced window spent in the operations
that move data between chips (`collective_dev_ms.train` over the step's device time): what
the mesh costs a step that the compiler did not hide behind computation. None where the
step has none (a one-chip cell)."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "collective_dev_share.train", "%", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)


def read(record):
    events = pt.for_record(record)
    by_kind = None if events is None else pt.device_seconds_by_collective(events)
    total = sum(pt.device_seconds_by_scope(events).values()) if by_kind else 0.0
    return 100.0 * sum(by_kind.values()) / total if total > 0 else None
