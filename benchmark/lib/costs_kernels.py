"""Operations a kernel's one call needs, from the shapes the trace shows for it.

A kernel's event in the device trace is named by its HLO text, which holds its operands'
shapes: `%flash_fwd.18 = (...) custom-call(bf16[64,4096,128]{...} %q, bf16[64,4096,128]{...}
%k, ...)`. The flash kernels of `ray_tpu/ops/attention.py` take q as [batch x heads, S, D]
and k as [batch x heads, T, D] (forward: q, k, v; backward: q, g, lse, delta, k, v).
Both are bound by compute on a v5e at these sizes (about 1000 operations for each byte of
q, k, v and the output at S = T = 4096, D = 128, against the chip's ridge of 240), so
their roofline is the bf16 peak.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"(?:bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")


def operand_shapes(hlo: str) -> list:
    """[batch x heads, rows, width] of each rank-3 operand, in order."""
    operands = hlo.split("custom-call(", 1)[1] if "custom-call(" in hlo else ""
    return [tuple(int(x) for x in m.groups()) for m in _SHAPE.finditer(operands.split("), custom_call_target", 1)[0])]


def flash_dims(hlo: str, kernel: str):
    """(batch x heads, S, T, D) of one call of `flash_fwd` or `flash_bwd`, or None."""
    shapes = operand_shapes(hlo)
    k_at = {"flash_fwd": 1, "flash_bwd": 4}.get(kernel)
    if k_at is None or len(shapes) <= k_at:
        return None
    (bh, s, d), (_, t, _) = shapes[0], shapes[k_at]
    return bh, s, t, d


def flash_fwd_flops(bh: int, s: int, t: int, d: int, causal: bool = True) -> float:
    """Scores and the weighted sum: 2 x 2 x S x T x D a head; a causal mask leaves half."""
    return 4.0 * bh * s * t * d * (0.5 if causal else 1.0)


def flash_bwd_flops(bh: int, s: int, t: int, d: int, causal: bool = True) -> float:
    """Twice the forward: dv, dp, dk and dq are four products where the forward has two
    (the recomputation of the scores inside the kernel is not counted)."""
    return 2.0 * flash_fwd_flops(bh, s, t, d, causal)


def flash_roofline(events, kernel: str, peak_flops: float):
    """A flash kernel's share of the bf16 peak, in %, per call: the operations of one call
    over the peak, over the median device time of its calls in the traced window
    (`events`: `lib/program_trace.load_events`). None where the kernel never ran."""
    from lib import program_trace, stats

    calls = program_trace.kernel_calls(events, kernel)
    dims = flash_dims(events["hlo"].get(calls[0][0], ""), kernel) if calls else None
    if dims is None:
        return None
    flops = {"flash_fwd": flash_fwd_flops, "flash_bwd": flash_bwd_flops}[kernel](*dims)
    return 100.0 * (flops / peak_flops) / (stats.pctl([d for _, _, d in calls], 0.5) / 1e9)
