"""Share of the prefill programs' device time under the scope `kv_attn` in the full-attention
layers: a chunk's rows written into the slabs and its loop over the key blocks up to its last row,
the part of a chunk that grows with its offset (`lib/kinds_trace.py`)."""
from lib import kinds_trace

NAME, UNIT, LAYER, MOVES, SOURCE = "full_attn_prefill_share.mixed", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)


def read(record):
    return kinds_trace.kv_attn_prefill_share(record, "full_attention")
