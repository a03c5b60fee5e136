"""The cached attention's share of its byte roofline in a decode step of a block whose layers keep
slabs or rings: the rows the step's queries could see, from the program's own counts over the
measured window (`scheduler_stats()["attn"]`: `full_rows_visible` + `window_rows_visible`, all live
rows of the gated slots in a full layer and at most `sliding_window` in a sliding one, summed over
the layers, which `drivers/serve_closed_counts.py` puts among the record's `counters`), a decode
step (the window's `decode_layer_steps` over the expert layers: `lib/expert_counts.py`), at a row's
K and V bytes in one layer (`row_bytes` of the block's costs module: 4096), over the chip's published
bandwidth, over the device self time a decode step under the scope `kv_attn` in every layer
(`kv_attn_dev_ms_per_step.sessions`' reading). It counts visible rows alone, neither the new rows'
writes nor whole blocks of the kernel nor an idle slot's block, so it stays under 100% for as long
as the program reads at least the rows it may see. One chip only."""
from lib import blocks, expert_counts
from lib import scope_trace as st
from lib import scope_trace_state as sts

NAME, UNIT, LAYER, MOVES, SOURCE = "kv_attn_roofline.mixed", "%", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    costs, model, c = blocks.costs(record), record["model"], record.get("counters") or {}
    layer_steps = expert_counts.window(record).get("decode_layer_steps")
    if record["chips"] != 1 or not layer_steps or not hasattr(costs, "row_bytes") or "attn_full_rows_visible" not in c:
        return None
    events = st.for_record(record)
    step_ms = None if events is None else sts.ms_per_decode_step(events, ("kv_attn",))
    if not step_ms:
        return None
    steps = layer_steps / (model["n_layers"] - model.get("first_k_dense", 1))
    rows = (c["attn_full_rows_visible"] + c["attn_window_rows_visible"]) / steps
    return 100.0 * rows * costs.row_bytes(model) / record["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
