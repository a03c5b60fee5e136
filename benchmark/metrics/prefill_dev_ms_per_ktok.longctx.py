"""Device milliseconds of prefill a thousand prompt tokens: the time of the prefill programs'
executions wholly inside the traced window (`jit_rt_prefill_b<bucket>`) over the `tokens` of the
`rt.engine.prefill` spans wholly inside it (real tokens, not a bucket's padding). The two edges
can differ by one chunk of some forty."""
from lib import scope_trace as st
from lib.program_trace import executions, spans_named

NAME, UNIT, LAYER, MOVES, SOURCE = "prefill_dev_ms_per_ktok.longctx", "ms", "engine", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    tokens = sum(e[3].get("tokens", 0) for e in spans_named(events, "rt.engine.prefill"))
    ns = sum(m[2] for m in executions(events, "jit_rt_prefill_b"))
    return ns / 1e6 / (tokens / 1e3) if tokens and ns else None
