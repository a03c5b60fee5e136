"""The cached attention's share of its roofline in a decode step. Bound by bytes: the K and V
rows the decoding slots hold (the mean `rows` of the traced `rt.engine.dispatch` spans, as
`decode_roofline.serve` takes it, times `kv_bytes_per_token` of the block's costs module,
`lib/blocks.py`), over the chip's published bandwidth, over the device self time a decode step
under the scope `kv_attn` (`kv_attn_dev_ms_per_step.sessions`' reading: the slabs' write and the
attention against them, `models/llama.py:_attn_cached`, which every block's attention layers
run). It counts the live rows alone, so it stays under 100% for as long as the program reads at
least the rows that are visible; rows read past them, and copies of a slab, are what keeps it low.
What it does not count: device time outside the scope. A program that waits for its rows' fetches
in operations with no scope (`copy-done`, `slice-done`: 4.68 ms of a 15.68 ms serve-chat step at
PR 34) can lose those waits, and its step a third, while this share stands or falls a little
(serve-chat 6.7 -> 6.2% at PR 35, `tpot_ms_p90` -25%): read it beside `decode_dev_ms_per_step.serve`."""
from lib import blocks
from lib import scope_trace as st
from lib import scope_trace_state as sts
from lib.program_trace import spans_named

NAME, UNIT, LAYER, MOVES, SOURCE = "kv_attn_roofline.serve", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed", "serve_open")


def read(record):
    events = st.for_record(record) if record["chips"] == 1 else None
    step_ms = None if events is None else sts.ms_per_decode_step(events, ("kv_attn",))
    rows = [e[3]["rows"] for e in spans_named(events, "rt.engine.dispatch") if "rows" in e[3]] if step_ms else []
    if not rows:
        return None
    need_s = sum(rows) / len(rows) * blocks.costs(record).kv_bytes_per_token(record["model"]) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / (step_ms / 1e3)
