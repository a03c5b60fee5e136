"""Device self milliseconds a step, on the first chip's operation line, in the operations
that move data between chips (`lib/program_trace.COLLECTIVES`: all-gather, reduce-scatter,
all-reduce, collective-permute, all-to-all, with the `-start` / `-done` halves of an
asynchronous one and the fusions the compiler makes round them): the time the core spends in
them or waiting for them, which is the part of the communication that no computation hides.
None where the step has none (a one-chip cell).

What a v5e's trace shows (the fsdp=4 step, my chip run, PR 27): a synchronous collective (the
head's `all-reduce.66` and `all-gather.327`, the layers' `all-reduce-scatter` fusions) lies on
the `XLA Ops` line for its whole duration. An asynchronous one (the layer weights' gathers,
`async-collective-start.N` / `-done.N`; `collective-permute-start` / `-done`) leaves there
only its two halves, 1 to 8 microseconds a call: the transfer is a span on the first chip's
`Async XLA Ops` line (1.2 s of `collective-permute-start.1` in a 6 s window), overlaps the
computation, and is no part of this number."""
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "collective_dev_ms.train", "ms", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)


def read(record):
    events = pt.for_record(record)
    by_kind = None if events is None else pt.device_seconds_by_collective(events)
    return pt.ms_per_step(events, sum(by_kind.values())) if by_kind else None
