"""The mamba layers' share of their byte roofline in a decode step: their matrices once in bf16
and a read and a write of the state of the slots the step advances (`mamba_layers_step_bytes` of
the block's costs module at the mean `slots` of the traced `rt.engine.dispatch` spans; at 48
slots the state is 57% of those bytes), over the chip's published bandwidth, over the device self
time a decode step in everything those layers run (`lib/scope_trace_state.py`, by the `layer_<i>`
of each operation's scope path and the configuration's `layer_types`). Not the `ssm` scope's own
time: the compiler fetches three quarters of a layer's state into on-chip memory with
asynchronous slices issued two layers ahead (`slice-start`, not on the operations line), so the
update's own operations read 957 GB/s of a memory that gives 819, and a share over that time
leaves out part of the work (PERF.md §6, PR 32). Over whole layers the fetches fall inside the
time counted, but for those that run under the four attention layers (2% of the bytes). The
program reads and writes every slot's state, gated or not, so the bytes counted are no more than
it moves. One chip only."""
from lib import blocks, program_trace as pt
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "ssm_state_roofline.sessions", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs = blocks.costs(record)
    events = st.for_record(record) if record["chips"] == 1 and hasattr(costs, "mamba_layers_step_bytes") else None
    mamba = {i for i, kind in enumerate(record["model"].get("layer_types") or ()) if kind == "mamba"}
    step_ms = None if events is None else sts.layers_ms_per_decode_step(events, mamba)
    slots = [e[3]["slots"] for e in pt.spans_named(events, "rt.engine.dispatch") if "slots" in e[3]] if step_ms else []
    if not slots:
        return None
    need_s = costs.mamba_layers_step_bytes(record["model"], sum(slots) / len(slots)) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / (step_ms / 1e3)
