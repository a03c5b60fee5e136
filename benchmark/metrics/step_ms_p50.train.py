"""Host time of one step in the traced run, where every step is blocked: median over
the steps taken before the profiler started (those under it are a little slower)."""
from lib import stats

NAME, UNIT, LAYER, MOVES, SOURCE = "step_ms_p50.train", "ms", "train step", "train_tok_s", "host_clock"
DRIVERS = ("train_steps",)


def read(record):
    return stats.median([r["ms"] for r in record["step_rows"] if not r["traced"]])
