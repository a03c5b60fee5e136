"""Device self time under the scope `kv_attn` by the kind of layer, for a block whose layers are of
two kinds that keep different caches (`models/laguna.py`: slabs in `full_attention` layers, rings in
`sliding_attention` ones): `lib/scope_trace_state.py`'s sums by program, `layer_<i>` and scope, the
layer's kind read from the configuration's `layer_types`. Where the record's model names no layer
kinds or the program wrote no such scope, as one without the block does not, the readers return nothing."""
from __future__ import annotations

import re

from lib import scope_trace as st
from lib import scope_trace_state as sts


def _kv_attn_ns(record, kind: str, programs: str):
    """(ns under `kv_attn` in the layers of `kind` in `programs`, the events), or (None, None)."""
    kinds = record["model"].get("layer_types") or ()
    layers = {i for i, k in enumerate(kinds) if k == kind}
    events = st.for_record(record) if layers else None
    if events is None:
        return None, None
    ns = sum(t for (p, layer, scope), t in sts.by_program_layer_and_scope(events).items()
             if scope == "kv_attn" and layer in layers and re.fullmatch(programs, p))
    return ns, events


def kv_attn_ms_per_decode_step(record, kind: str):
    """Device self milliseconds a decode step under `kv_attn` in the layers of `kind`; None where
    the decode programs carry no such scope in such a layer."""
    ns, events = _kv_attn_ns(record, kind, st.DECODE)
    steps = st.decode_steps(events) if ns else 0
    return ns / 1e6 / steps if ns and steps else None


def kv_attn_prefill_share(record, kind: str):
    """Percent of the prefill programs' device time under `kv_attn` in the layers of `kind`."""
    ns, events = _kv_attn_ns(record, kind, st.PREFILL)
    total = st.program_ns(events, st.PREFILL) if ns else 0
    return 100.0 * ns / total if ns and total else None
