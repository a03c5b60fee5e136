"""Model FLOP/s utilisation: tokens per second of this run times the operations the
forward and backward passes require per token (`lib/costs.py`; recomputed operations
not counted, embedding table excluded), over the chip's published bf16 peak. An
end-to-end utilisation: it says nothing of any one kernel."""
from lib import costs

NAME, UNIT, LAYER, MOVES, SOURCE = "mfu.train", "%", "train step", "train_tok_s", "host_clock"
DRIVERS = ("train_steps",)


def read(record):
    if not record["steps"]:
        return None
    tok_s = record["steps"] * record["tokens_per_step"] / record["window_s"]
    flops = costs.train_flops_per_token(record["model"], record["seq"])
    return 100.0 * tok_s * flops / record["peaks"]["bf16_flops"]
