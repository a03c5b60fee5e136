"""Device time a step in the Pallas flash kernels, forward (run again where the block is
recomputed) and backward: the operations named `flash_fwd` and `flash_bwd`."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "flash_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)


def read(record):
    events = pt.for_record(record)
    if events is None:
        return None
    parts = [pt.kernel_ms_per_step(events, kernel) for kernel in ("flash_fwd", "flash_bwd")]
    return sum(parts) if all(p is not None for p in parts) else None
