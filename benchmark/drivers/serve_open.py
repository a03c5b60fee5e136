"""Driver `serve_open`: one in-process `LLMServer` under an open loop of Poisson
arrivals at the rate fixed in the traffic file. A request is sent when it is due,
whatever the server is doing, and timed from when it was due.

Arrivals start `ramp_seconds` before the window (counted as set-up), so the window
opens on a queue in its steady state. Every seed replays the same cycle of gaps and
sizes from another phase (`lib/arrivals.py`). After the window the generator waits
`grace_seconds` for the first tokens of the last arrivals, then shuts the server down,
which ends the requests in flight with what they have produced so far. A traced run
first replays the cycle's busiest `trace_seconds` under the profiler (`encore`).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from lib import arrivals, hostwatch, serving


def schedule(tr: dict, seconds: float, seed: int, vocab: int) -> list:
    """The requests of a ramp plus a window; `due` is relative to the start of the ramp.

    The window is one cycle of n = rate x seconds arrivals: the exponential gaps and the
    two lognormal sizes, each a fixed multiset in a fixed order (`order_seed`), with the
    gaps scaled to fill the window exactly. The seed rolls the cycle to its own starting
    phase. The ramp replays the end of the same cycle, so the window opens on the queue
    the cycle's last arrivals leave behind."""
    rate, ramp = float(tr["rate_per_s"]), float(tr["ramp_seconds"])
    n = max(2, int(round(rate * seconds)))
    order = arrivals.rng_for(tr["order_seed"], 0)
    gaps = arrivals.exponential_gaps(rate, n, order)
    gaps = gaps * (seconds / gaps.sum())
    plens = arrivals.lengths(tr["prompt_len"], n, order)
    outs = arrivals.lengths(tr["max_tokens"], n, order)
    k = arrivals.rotation(seed, n)
    gaps, plens, outs = np.roll(gaps, -k), np.roll(plens, -k), np.roll(outs, -k)
    starts = np.cumsum(gaps) - gaps           # arrival j opens gap j; arrival 0 is at the window's start
    cycle = [(float(starts[j]), int(plens[j]), int(outs[j])) for j in range(n)]
    lead = [(t - seconds, p, m) for t, p, m in cycle if t - seconds >= -ramp]  # the cycle's end, before the window
    rng = arrivals.rng_for(seed, 2)
    return [dict(i=i, due=ramp + t, phase=int(t >= 0), prompt=arrivals.token_ids(p, vocab, rng),
                 max_tokens=m, temperature=tr["temperature"], top_k=tr["top_k"])
            for i, (t, p, m) in enumerate(lead + cycle)]


def encore(requests: list, ramp: float, seconds: float, span: float) -> list:
    """What a traced run replays under the profiler, after its window: the `span` seconds
    of the cycle with the most arrivals, as (seconds from the span's start, request), the
    cycle taken as the ring it is. Every seed traces the same arrivals, and the device is
    never idle under the profiler, which it can be in the window's own tail: the cycle
    also has seconds with no request in flight (3.7 s at `chat-open`), and a seed can turn
    them there. Ties go to the span that opens on the longest prompt."""
    cycle = [(r["due"] - ramp, r) for r in requests if r["phase"]]
    ring = cycle + [(t + seconds, r) for t, r in cycle]

    def inside(t0):
        return [(t - t0, r) for t, r in ring if t0 <= t < t0 + span]

    t0, _ = max(cycle, key=lambda c: (len(inside(c[0])), len(c[1]["prompt"])))
    return inside(t0)


def run(ctx) -> dict:
    tr, notes, vocab = ctx.traffic, [], ctx.model["vocab_size"]
    server, probes, setup, c0 = serving.build(ctx)
    ramp = float(tr["ramp_seconds"])
    requests = schedule(tr, ctx.seconds, ctx.seed, vocab)

    async def main():
        ok_ref = await serving.prepare(ctx, server, probes, setup, notes,
                                       [len(r["prompt"]) for r in requests])
        serving.note_compiles(ctx, setup, c0)

        clock0 = time.monotonic()
        w0 = clock0 + ramp
        w1 = w0 + ctx.seconds
        marks, tasks = {}, []

        async def mark_window():
            await asyncio.sleep(max(0.0, w0 - time.monotonic()))
            marks["before"] = await serving.counters(server, ctx)
            marks["setup_s"] = ctx.since_start()
            host = hostwatch.start(ticker=not ctx.trace)
            await asyncio.sleep(max(0.0, w1 - time.monotonic()))
            notes.append(host.stop())
            marks["after"] = await serving.counters(server, ctx)

        marker = asyncio.create_task(mark_window())
        for req in requests:
            delay = clock0 + req["due"] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(serving.timed_request(server, req, clock0, vocab)))
        await marker
        await asyncio.sleep(tr["grace_seconds"])
        if ctx.trace:
            # after the window and its grace, so that the profiler weighs on no request that is
            # measured; the replayed requests are due outside the window and count nowhere
            t0 = time.monotonic()
            tracer = asyncio.create_task(serving.trace_span(ctx, t0))
            rng = arrivals.rng_for(ctx.seed, 5)
            for n, (offset, req) in enumerate(encore(requests, ramp, ctx.seconds, float(tr["trace_seconds"]))):
                await asyncio.sleep(max(0.0, t0 + offset - time.monotonic()))
                again = dict(req, i=len(requests) + n, due=t0 + offset - clock0,
                             prompt=arrivals.token_ids(len(req["prompt"]), vocab, rng))
                tasks.append(asyncio.create_task(serving.timed_request(server, again, clock0, vocab)))
            await tracer
        await server.shutdown()
        rows = list(await asyncio.gather(*tasks))
        # the window by construction, not w0 - clock0: a rounding there drops the arrival due at its start
        return serving.finish(rows, ramp, ramp + ctx.seconds, setup, marks["setup_s"], ok_ref, notes,
                              marks["before"], marks["after"], slots=tr["slots"])

    return asyncio.run(main())
