"""Programs JAX built (compiled or read from the cache) inside the measured window.
Expected 0: a shape the warm-up missed shows here."""
NAME, UNIT, LAYER, MOVES, SOURCE = "window_compiles", "count", "launch and compile", "setup_s", "program_counter"
DRIVERS = ("train_steps", "serve_closed", "serve_open")


def read(record):
    return record.get("window_compiles")
