"""Device self time a decode step under the scope `kv_attn` in the full-attention layers of a block
whose layers are of two kinds (`layer_<i>` by the configuration's `layer_types`): the new row's write
into the K and V slabs and the attention over every live row of every slot, the part of a step that
grows with the contexts in the slots, in the decode programs wholly inside the traced window."""
from lib import kinds_trace

NAME, UNIT, LAYER, MOVES, SOURCE = "full_attn_dev_ms_per_step.mixed", "ms", "model block", "tpot_ms_p90", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    return kinds_trace.kv_attn_ms_per_decode_step(record, "full_attention")
