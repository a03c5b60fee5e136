"""Share of the prefill programs' device time that the four streams' least traffic needs at the memory's
speed: of every chunk inside the traced window (`rt.engine.prefill`'s `tokens`), in each of the model's
sub-layers, the streams read once and written once, the mixture written and the sub-layer's output read,
Phi once (`hc_sublayer_bytes` of the block's costs module), over the device time of the prefill programs'
executions inside the window. It is what a hyper-connection fused into one pass a sub-layer would cost a
chunk, and it does not depend on what a fusion is named: `hc_prefill_share.mhc` sums only the operations
that carry the scope `hc`, and XLA fuses the mixture and two of the new streams into fusions named for
`attn` and `mlp`, so that reader is a floor of what the mechanism costs as written and can read under
this one. Where the block's costs count no such bytes, as another block's do not, nothing."""
from lib import blocks
from lib import scope_trace as st
from lib.program_trace import executions, spans_named

NAME, UNIT, LAYER, MOVES, SOURCE = "hc_stream_need_share.mhc", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs = blocks.costs(record)
    events = st.for_record(record) if hasattr(costs, "hc_sublayer_bytes") else None
    if events is None:
        return None
    model = record["model"]
    chunks = [e[3].get("tokens", 0) for e in spans_named(events, "rt.engine.prefill")]
    ns = sum(m[2] for m in executions(events, "jit_rt_prefill_b"))
    need = sum(2 * model["n_layers"] * costs.hc_sublayer_bytes(model, t) for t in chunks if t) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / (ns / 1e9) if need and ns else None
