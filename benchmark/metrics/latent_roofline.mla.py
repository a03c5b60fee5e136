"""Dense latent attention's share of its roofline in a decode step: what the mathematics needs over
the live rows (the mean `rows` of the traced `rt.engine.dispatch` spans, as `decode_roofline.serve`
takes it) in every layer, the greater of a row's 1152 bytes at the chip's published bandwidth and its
278,528 operations at the published bf16 peak (`latent_step_need_s` of the block's costs module: the
two are within 2% of each other on a v5e), over the device self time a decode step under the scope
`latent` (`latent_dev_ms_per_step.mla`'s reading). The same work whatever implements it: rows read
past the live ones, lanes of padding and the folding of W_kvb are what keep it under 100%. One chip only."""
from lib import blocks
from lib import scope_trace as st
from lib.program_trace import spans_named

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_roofline.mla", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs = blocks.costs(record)
    events = st.for_record(record) if record["chips"] == 1 and hasattr(costs, "latent_step_need_s") else None
    step_ms = None if events is None else st.scope_ms_per_decode_step(events, "latent")
    rows = [e[3]["rows"] for e in spans_named(events, "rt.engine.dispatch") if "rows" in e[3]] if step_ms else []
    if not rows:
        return None
    return 100.0 * costs.latent_step_need_s(record["model"], sum(rows) / len(rows), record["peaks"]) / (step_ms / 1e3)
