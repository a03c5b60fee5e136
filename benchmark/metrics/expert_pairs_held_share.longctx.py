"""Share of the window's token-expert pairs whose expert this chip holds, from the program's
own counts (`scheduler_stats()["experts"]`: pairs routed, pairs held). 32 of 256 experts under
even routing give 12.5%; what differs is the router's skew towards or away from the share."""
NAME, UNIT, LAYER, MOVES, SOURCE = "expert_pairs_held_share.longctx", "%", "model block", "serve_out_tok_s", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    c = record["counters"]
    return 100.0 * c["expert_pairs_held"] / c["expert_pairs_routed"] if c.get("expert_pairs_routed") else None
