"""Driver `serve_closed_counts`: `serve_closed_state`'s run, unchanged, for a block whose `report`
says more than the expert layers' pairs and a recurrent state's counts. It returns `serve_closed`'s
record. What it adds: the traffic file's `counts` names sections of `scheduler_stats()` and the keys
of each to carry ({"latent": ["rows_visible", "rows_read"]}); each goes into the record's `counters`
as `<section>_<key>`, a delta over the window like the scheduler's own. The drivers under it copy
what they name themselves (`["experts"]`' pairs, `["state"]`'s four counts), so what another block
counts would reach no reader. Where the program reports no such section, as one without the block
does not, the record has no such counter and the readers return nothing.
"""

from __future__ import annotations

from drivers import serve_closed_long as loop
from drivers import serve_closed_state as state

RECORD = "serve_closed"


def run(ctx) -> dict:
    loop_counters, wanted = loop.counters, ctx.traffic.get("counts") or {}

    async def counters(server, ctx):
        out, experts = await loop_counters(server, ctx)
        stats = await server.scheduler_stats()
        for section, keys in wanted.items():
            found = stats.get(section) or {}
            out.update({f"{section}_{key}": found[key] for key in keys if key in found})
        return out, experts

    loop.counters = counters  # `serve_closed_state.run` wraps the loop's `counters` as it finds it
    try:
        return state.run(ctx)
    finally:
        loop.counters = loop_counters
