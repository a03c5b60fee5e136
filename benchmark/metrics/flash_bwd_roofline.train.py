"""The flash backward kernel's share of its roofline, per call: the causal operations of one
call from the shapes in its HLO text (`lib/costs_kernels.py`) over the chip's published bf16
peak, over the call's median device time. Bound by compute at these shapes."""
from lib import costs_kernels, program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "flash_bwd_roofline.train", "%", "train step", "train_tok_s", "program_span"
DRIVERS = ("train_steps",)


def read(record):
    events = pt.for_record(record)
    return None if events is None else costs_kernels.flash_roofline(events, "flash_bwd", record["peaks"]["bf16_flops"])
