"""The kernel `latent_attn`'s share of its roofline, per call (one layer, every slot): what the
mathematics needs over the live rows (the mean `rows` of the traced `rt.engine.dispatch` spans), a
row's 1152 bytes at the chip's published bandwidth or its 278,528 operations at the published bf16
peak, whichever is greater (`latent_attn_call_need_s` of the block's costs module: a layer's part of
`latent_roofline.mla`'s work), over the median device time of the kernel's calls in the traced window.
The 64 lanes of zeros a row is kept with (576 values as 640, in both products), rows of a block past a
slot's last one, an idle slot's block and the start of each slot's first copy are what keep it under
100%. One chip only."""
from lib import blocks, stats
from lib import program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_attn_roofline.mla", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs = blocks.costs(record)
    events = pt.for_record(record) if record["chips"] == 1 and hasattr(costs, "latent_attn_call_need_s") else None
    calls = pt.kernel_calls(events, "latent_attn") if events is not None else []
    rows = [e[3]["rows"] for e in pt.spans_named(events, "rt.engine.dispatch") if "rows" in e[3]] if calls else []
    if not rows:
        return None
    need_s = costs.latent_attn_call_need_s(record["model"], sum(rows) / len(rows), record["peaks"])
    return 100.0 * need_s / (stats.pctl([d for _, _, d in calls], 0.5) / 1e9)
