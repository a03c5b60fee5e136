"""The decode program's share of its roofline. A decode step is bound by bytes: every matmul
weight once in bf16 plus the live K and V rows (`decode_step_bytes` of the block's costs module,
`lib/blocks.py`, at the mean `rows` of the traced `rt.engine.dispatch` spans: the rows the
decoding slots hold), over the chip's published bandwidth, over the device time of one step
(`decode_dev_ms_per_step.serve`). One chip only: over several, the first chip's share of the
bytes is not in the record, so there is no roofline to take its step against."""
from lib import blocks, program_trace as pt

NAME, UNIT, LAYER, MOVES, SOURCE = "decode_roofline.serve", "%", "engine", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = pt.for_record(record) if record["chips"] == 1 else None
    step_ms = None if events is None else pt.decode_ms_per_step(events)
    rows = [e[3]["rows"] for e in pt.spans_named(events, "rt.engine.dispatch") if "rows" in e[3]] if step_ms else []
    if not rows:
        return None
    need_s = blocks.costs(record).decode_step_bytes(record["model"], sum(rows) / len(rows)) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / (step_ms / 1e3)
