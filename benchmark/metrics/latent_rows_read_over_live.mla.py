"""Rows of a layer's latent slab the decode steps' products ran over, over the rows their queries
could see, from the program's own counts over the measured window (`scheduler_stats()["latent"]`:
`rows_read` over `rows_visible`, which `drivers/serve_closed_counts.py` puts among the record's
`counters` as deltas over the window). 1 is a path that reads the live rows alone; the kernel reads
whole blocks of 512 rows and one block of every idle slot; two products over every row of 16 slots
of 32768 read 2.5 times the live rows at this traffic's mean."""

NAME, UNIT, LAYER, MOVES, SOURCE = "latent_rows_read_over_live.mla", "ratio", "model block", "tpot_ms_p90", "program_counter"
DRIVERS = ("serve_closed",)


def read(record):
    c = record.get("counters") or {}
    return c["latent_rows_read"] / c["latent_rows_visible"] if c.get("latent_rows_visible") else None
