"""Share of the device's self time in the traced window that no scope of
`lib/program_trace.SCOPES` names: operations the compiler made itself (layout copies,
converts of the parameters, the scan's slices) and whatever the program left unnamed."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "unscoped_dev_share.train", "%", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)


def read(record):
    events = pt.for_record(record)
    by_scope = None if events is None else pt.device_seconds_by_scope(events)
    total = sum(by_scope.values()) if by_scope else 0.0
    return 100.0 * by_scope.get(pt.UNSCOPED, 0.0) / total if total > 0 else None
