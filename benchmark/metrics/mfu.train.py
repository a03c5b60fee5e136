"""Model FLOP/s utilisation: tokens per second of this run times the operations the
forward and backward passes require per token (the block's costs module, `lib/blocks.py`;
recomputed operations not counted, embedding table excluded), over the published bf16
peak of all the chips the cell runs on together. An end-to-end utilisation: it says
nothing of any one kernel."""
from lib import blocks

NAME, UNIT, LAYER, MOVES, SOURCE = "mfu.train", "%", "train step", "train_tok_s", "host_clock"
DRIVERS = ("train_steps",)


def read(record):
    if not record["steps"]:
        return None
    tok_s = record["steps"] * record["tokens_per_step"] / record["window_s"]
    flops = blocks.costs(record).train_flops_per_token(record["model"], record["seq"])
    return 100.0 * tok_s * flops / (record["chips"] * record["peaks"]["bf16_flops"])
