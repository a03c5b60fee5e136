"""Seconds JAX spent tracing, lowering and compiling (or reading from the persistent
cache) during set-up, as its own monitoring events report them: every program of the
cell, the reference's included."""
NAME, UNIT, LAYER, MOVES, SOURCE = "compile_s", "s", "launch and compile", "setup_s", "program_span"
DRIVERS = ("train_steps", "serve_closed", "serve_open")


def read(record):
    return record["setup"].get("compile_s")
