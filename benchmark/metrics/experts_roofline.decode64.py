"""The expert layers' share of their byte roofline in a decode step: the three matrices of every
expert that took a token, each once in bf16 (`experts_step_bytes` of the block's costs module at
the experts a layer the window's decode steps hit, from the program's own counts and not from an
expectation: `lib/expert_counts.py`), over the chip's published bandwidth, over the device self
time a decode step under the scope `experts` (the sort of the pairs, the loop over the tiles, the
weighted sum back: `lib/scope_trace.py`). The loop's matrix products read each tile's expert with
operations of their own on the operations line (no asynchronous fetch ahead of the scope, as
`ssm_state_roofline.sessions` found for a state: PERF.md §6, PR 34), so the scope's own time is
the time the bytes took. One chip only."""
from lib import blocks, expert_counts
from lib import scope_trace as st

NAME, UNIT, LAYER, MOVES, SOURCE = "experts_roofline.decode64", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs, hit = blocks.costs(record), expert_counts.hit_per_decode_step(record)
    events = st.for_record(record) if record["chips"] == 1 and hit and hasattr(costs, "experts_step_bytes") else None
    step_ms = None if events is None else st.scope_ms_per_decode_step(events, "experts")
    if not step_ms:
        return None
    need_s = costs.experts_step_bytes(record["model"], hit) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / (step_ms / 1e3)
