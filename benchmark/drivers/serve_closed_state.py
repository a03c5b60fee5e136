"""Driver `serve_closed_state`: `serve_closed_long`'s loop, unchanged, for a block that keeps a
recurrent state in the engine's slots. It returns `serve_closed`'s record. What it adds:

- the block's own counts (`scheduler_stats()["state"]`, where the program has them: positions the
  prefill programs ran, how many of them were padding, states reset, decode steps times the slots
  they advanced) go into the record's `counters` beside the scheduler's, as deltas over the window
  like the others. `serve_closed_long.counters` copies the expert layers' counts alone, so those
  of another block would reach no reader;
- in a traced run the profiler is started `TRACE_LEAD_S` before the traced window and the window
  is always `trace_seconds` long (`trace_span`). `serving.trace_span` starts the profiler at the
  window's own start and ends the window at a fixed time, so whatever the start takes is taken
  out of the window: in the first check of PR 32 one start took 2.97 of the 3 s, the window was
  29 ms and held no whole iteration, and the readers had nothing to read.
"""

from __future__ import annotations

import asyncio
import time

from drivers import serve_closed_long as loop
from lib import serving

RECORD = "serve_closed"

STATE_COUNTS = ("prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps")
TRACE_LEAD_S = 1.5  # 30 times the start's usual 0.05 s. Not longer: every second under the profiler is 14 MB more of
# trace and 20 s more of reading it after the run (183 MB and 317 s a traced run at 3.0, 162 MB and 286 s at 1.5; untraced 160 s)


async def trace_span(ctx, t0: float, notes=None) -> None:
    """`serving.trace_span` with the profiler's start moved out of the traced window: it is
    started `TRACE_LEAD_S` before `t0` (or at once, where the run is shorter), and `bench.window`
    opens at `t0` for `trace_seconds`. Where the start takes longer than the lead, the window opens
    when the profiler runs and still lasts `trace_seconds`, past the measured window's end, where
    the clients send nothing new: a full window on a falling load rather than none. The note says
    which it was."""
    import jax

    aio = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, t0 - TRACE_LEAD_S - time.monotonic()))
    asked = time.monotonic()
    await aio.run_in_executor(None, jax.profiler.start_trace, ctx.trace_dir)
    started = time.monotonic()
    await asyncio.sleep(max(0.0, t0 - started))
    opened = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.window"):
        await asyncio.sleep(float(ctx.traffic["trace_seconds"]))
    await aio.run_in_executor(None, jax.profiler.stop_trace)
    if notes is not None:
        notes.append(f"profiler: asked for {t0 - asked:.2f} s before the traced window, started in {started - asked:.2f} s; "
                     f"the window opened {opened - t0:.3f} s after its time")


def run(ctx) -> dict:
    loop_counters, serving_trace_span, notes = loop.counters, serving.trace_span, []

    async def counters(server, ctx):
        out, experts = await loop_counters(server, ctx)
        state = (await server.scheduler_stats()).get("state") or {}
        out.update({"state_" + key: state[key] for key in STATE_COUNTS if key in state})
        return out, experts

    loop.counters = counters  # the loop reads its module's `counters` round the window
    serving.trace_span = lambda ctx, t0: trace_span(ctx, t0, notes)  # and `serving`'s `trace_span` in it
    try:
        record = loop.run(ctx)
    finally:
        loop.counters, serving.trace_span = loop_counters, serving_trace_span
    record.setdefault("notes", []).extend(notes)
    return record
