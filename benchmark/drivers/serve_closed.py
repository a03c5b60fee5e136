"""Driver `serve_closed`: one in-process `LLMServer`, `clients` callers in a closed
loop, each sending its next request when its last one ends.

The loop starts `ramp_seconds` before the window (counted as set-up), so the window
opens and closes in the steady state, with every slot taken. Requests come from a cycle
of `pool` sizes that is the same for every seed (`order_seed` fixes its order); the
seed chooses where in the cycle the run starts and draws the token ids, every prompt
unique. At the end of the window the server is shut
down, which ends the requests in flight with what they have produced so far.
"""

from __future__ import annotations

import asyncio
import time

from lib import arrivals, hostwatch, serving


def run(ctx) -> dict:
    tr, notes, vocab = ctx.traffic, [], ctx.model["vocab_size"]
    server, probes, setup, c0 = serving.build(ctx)

    order = arrivals.rng_for(tr["order_seed"], 0)
    plens = arrivals.lengths(tr["prompt_len"], tr["pool"], order)
    outs = arrivals.lengths(tr["max_tokens"], tr["pool"], order)

    def request_stream():
        rng = arrivals.rng_for(ctx.seed, 2)
        i = arrivals.rotation(ctx.seed, tr["pool"])
        while True:
            p, m = plens[i % tr["pool"]], outs[i % tr["pool"]]
            yield dict(i=i, prompt=arrivals.token_ids(p, vocab, rng), max_tokens=int(m),
                       temperature=tr["temperature"], top_k=tr["top_k"])
            i += 1

    async def main():
        ok_ref = await serving.prepare(ctx, server, probes, setup, notes, plens)

        stream = request_stream()
        rows, stop = [], asyncio.Event()
        clock0 = time.monotonic()

        async def client():
            while not stop.is_set():
                rows.append(await serving.timed_request(server, next(stream), clock0, vocab))

        clients = [asyncio.create_task(client()) for _ in range(tr["clients"])]
        await asyncio.sleep(tr["ramp_seconds"])
        serving.note_compiles(ctx, setup, c0)
        before = await serving.counters(server, ctx)
        setup_s = ctx.since_start()
        w0 = time.monotonic()
        host = hostwatch.start(ticker=not ctx.trace)
        # the window's last seconds: in a closed loop every slot is taken there as anywhere
        tracer = (asyncio.create_task(serving.trace_span(ctx, w0 + ctx.seconds - float(tr["trace_seconds"])))
                  if ctx.trace else None)
        await asyncio.sleep(ctx.seconds)
        w1 = time.monotonic()
        notes.append(host.stop())
        after = await serving.counters(server, ctx)
        stop.set()
        if tracer is not None:
            await tracer
        await server.shutdown()
        await asyncio.gather(*clients)
        return serving.finish(rows, w0 - clock0, w1 - clock0, setup, setup_s, ok_ref, notes,
                              before, after, slots=tr["slots"])

    return asyncio.run(main())
