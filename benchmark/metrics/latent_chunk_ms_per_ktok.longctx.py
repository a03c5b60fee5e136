"""Device milliseconds under the scope `latent` of the prefill programs a thousand prompt tokens: a
chunk's expansion of keys and values from the latent rows and its attention over every block of keys
before the chunk's last, by self time (`lib/scope_trace.py`), over the `tokens` of the `rt.engine.prefill`
spans wholly inside the traced window, as `prefill_dev_ms_per_ktok.longctx` counts them. The part of
that metric that grows with the context, and what a kernel under the chunk's loop moves; it reads
wherever the block writes the scope, kernel or none."""
from lib import scope_trace as st
from lib.program_trace import spans_named

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_chunk_ms_per_ktok.longctx", "ms", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    tokens = sum(e[3].get("tokens", 0) for e in spans_named(events, "rt.engine.prefill"))
    ns = st.scope_ns(events, st.PREFILL, "latent")
    return ns / 1e6 / (tokens / 1e3) if tokens and ns > 0 else None
