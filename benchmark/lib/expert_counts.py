"""The expert layers' counts of a run's measured window, for the readers of `program_counter`
metrics that `counters` does not carry. `drivers/serve_closed_long.py` copies two of the counts
of `scheduler_stats()["experts"]` into the record's `counters` (pairs routed, pairs held) and
writes the window's whole report (`["experts"]["window"]`: since the report taken at the
window's start) into a note, `experts in the window: {...}`. A block that counts more there
(`models/lfm2.py`: experts hit, tiles run, layer steps, the decode programs' share of both, the
largest and the mean load of an expert) reaches its readers through that note: a dict of numbers,
read back as written. Where the program reports no such window, as one without the block does
not, there is no note and the readers return nothing."""
from __future__ import annotations

import ast

_NOTE = "experts in the window: "


def window(record) -> dict:
    """The window's expert counts, or {}."""
    for note in record.get("notes") or ():
        if isinstance(note, str) and note.startswith(_NOTE):
            try:
                found = ast.literal_eval(note[len(_NOTE):])
            except (ValueError, SyntaxError):
                return {}
            return found if isinstance(found, dict) else {}
    return {}


def hit_per_decode_step(record):
    """Experts a layer that took at least one pair, mean over the window's decode steps and the
    expert layers; None where the program does not count it."""
    w = window(record)
    return w["decode_experts_hit"] / w["decode_layer_steps"] if w.get("decode_layer_steps") else None
