"""Process start to the first measured step or request: import, weights, compiles or
cache reads, warm-up, the reference check, the ramp."""
NAME, UNIT, LAYER, MOVES, SOURCE = "setup_s", "s", "end to end", None, "host_clock"
DRIVERS = ("train_steps", "serve_closed", "serve_open")


def read(record):
    return record["setup_s"]
