"""Of the prefill programs' device time under the scope `latent`, the share inside the calls of the
Pallas kernel `latent_chunk` (a block of keys' scores, softmax and second product, one call a block a
layer: `ray_tpu/ops/latent_attention.py`), by the kernel's name, in the executions wholly inside the
traced window as the scope's time is counted. The rest is the expansion of keys and values, the slices
and what joins the blocks; a bucket too small for a tile runs no kernel. 0 would mean the programs took the XLA body on the chip; a program without the kernel, as
the parent of the PR that brought it, reads nothing."""
from lib import scope_trace as st
from lib.program_trace import executions, kernel_calls

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_chunk_kernel_share.longctx", "%", "model block", "serve_out_tok_s", "program_span"
DRIVERS = ("serve_closed",)
KERNEL = "latent_chunk"


def read(record):
    events = st.for_record(record)
    if events is None:
        return None
    ns = st.scope_ns(events, st.PREFILL, "latent")
    runs = [(s, s + d) for _, s, d in executions(events, "jit_rt_prefill_b")]
    calls = [d for _, s, d in kernel_calls(events, KERNEL) if any(a <= s < b for a, b in runs)]
    return 100.0 * sum(calls) / ns if calls and ns > 0 else None
