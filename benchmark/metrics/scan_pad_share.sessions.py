"""Share of the positions the prefill programs ran over the window that were the right-padding
of a bucketed chunk, from the program's own counts (`scheduler_stats()["state"]`: positions run,
positions of padding). Padding takes no step of the recurrence, but its matrix products are
computed like any position's."""
NAME, UNIT, LAYER, MOVES, SOURCE = "scan_pad_share.sessions", "%", "model block", "serve_out_tok_s", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    c = record["counters"]
    return 100.0 * c["state_prefill_padding"] / c["state_prefill_positions"] if c.get("state_prefill_positions") else None
