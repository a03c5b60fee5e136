"""Driver `serve_closed_long`: `serve_closed`'s closed loop for long contexts. It returns
`serve_closed`'s record, so every reader of that kind reads it. What differs:

- flags: the traffic file's `flags` are the system's own documented flags (`RAY_TPU_<NAME>`,
  `ray_tpu/_private/config.py`), set before the server is built, as an operator would.
- probes: `lib/serving.py`'s 64-token probes see neither a selection nor a window, and its
  reference holds a second copy of the weights, which does not fit beside a server whose
  weights and cache fill 9.7 of 16 GB. Here the server is built first; `MAX_PROBES` seeded
  prompts of `probe.prompt_len` tokens are sent to it all together (the first is chunked alone
  on an idle engine, the others beside slots that decode, as the traffic is); then the block's
  reference scores each generated sequence in one full float32 pass over the server's own
  tree (`LLMServer.weights()`, `reference.score`): at every generated position, the reference's
  choice given the same tokens before it. The limits are the block's own (`compare_scored`:
  no id further under the reference's largest logit than `NEAR_TIE_MARGIN`, their mean at most
  `MEAN_DEFICIT_TOL`; `MIN_COMPARED_POSITIONS`, `MAX_PROBES`).
- the window's own requests: the probes are sent before the window and are not of its
  sizes (16 slots at contexts of 6k to 25k). After the window and the server's shutdown the same
  reference scores a sample of the requests the window finished (`window_check` in the traffic
  file: the shortest one past `over` tokens and the `short` shortest ones), each padded to one of
  `lens` so that two programs score any of them, over its last `n_last` generated positions,
  under the same limits. `correct` needs both.
- warm-up: one prompt per prefill bucket; no prefix-cache block counts (`cover_block`).
- phase: `--seed` draws the weights and every token id, as elsewhere, but not where in the
  cycle of sizes the run starts. `serve_closed` lets the seed rotate its cycle because its
  window holds the cycle more than twice over; this one holds 20 of its 32 requests, so a
  rotation changes the work and not the system (`lib/arrivals.py`: "a seed may not change
  the amount of work"). Every run starts at the traffic file's `phase`.
- counters: the expert layers' counts of `scheduler_stats()["experts"]`, where the program
  has them, beside the scheduler's.
"""

from __future__ import annotations

import asyncio
import gc
import os
import time

import numpy as np

from lib import arrivals, blocks, hostwatch, serving

RECORD = "serve_closed"


def set_flags(flags: dict) -> None:
    from ray_tpu._private.config import CONFIG

    for name, value in flags.items():
        if name == "why":
            continue
        os.environ["RAY_TPU_" + name.upper()] = str(value)
        if getattr(CONFIG, name) != value:  # read before this driver ran
            raise SystemExit(f"flag {name} is already {getattr(CONFIG, name)!r}, not {value!r}")


def score_all(ctx, server, sequences: list, n_last: int, lens, q_block: int) -> dict:
    """The reference's score of what the server generated, on the server's own weights.
    sequences: [(prompt ids, generated ids)]; each is padded to the smallest of `lens` that
    holds it (one program a length) and scored over its last `n_last` generated positions."""
    import jax
    import jax.numpy as jnp

    reference = blocks.reference(ctx.config)
    _, params = server.weights()
    params = reference.plain_tree(params)
    score = jax.jit(lambda p, seq, n: reference.score(p, ctx.model, seq, n_last, length=n, q_block=q_block))
    out = dict(agrees=True, compared=0, parted=[], deficits=[], far=[])
    for prompt, got in sequences:
        seq = list(prompt) + list(got)
        if len(got) < n_last or len(seq) > max(lens):
            out["agrees"] = False
            continue
        padded = np.zeros((min(n for n in lens if n >= len(seq)),), np.int32)
        padded[:len(seq)] = seq
        ids, margins, own = (np.asarray(a).tolist() for a in score(params, jnp.asarray(padded), jnp.int32(len(seq))))
        ok, n, where = reference.compare_scored(ids, margins, got[-n_last:], [-d for d in own])
        out["agrees"], out["compared"] = out["agrees"] and ok, out["compared"] + n
        out["parted"] += where
        out["deficits"] += [-d for d in own]
        out["far"] += [f"prompt of {len(prompt)}, generated position {len(got) - n_last + j}: reference {r} by {m:.4f}, server {g} "
                       f"({-d:.4f} under)" for j, (r, m, g, d) in enumerate(zip(ids, margins, got[-n_last:], own))
                       if -d > reference.NEAR_TIE_MARGIN]
    del params, score
    gc.collect()
    out["mean_deficit"] = sum(out["deficits"]) / max(len(out["deficits"]), 1)
    out["agrees"] = out["agrees"] and out["mean_deficit"] <= reference.MEAN_DEFICIT_TOL
    return out


def _note(what: str, reference, r: dict, enough: bool) -> str:
    return (f"reference: {what}, {r['compared']} positions compared, ids differ at margins "
            f"{sorted(round(m, 4) for m in r['parted'])}; the server's ids lie under the reference's largest logit by "
            f"{r['mean_deficit']:.5f} in the mean over {len(r['deficits'])} positions (limit {reference.MEAN_DEFICIT_TOL}), "
            f"at most {max(r['deficits'], default=0.0):.4f} (limit {reference.NEAR_TIE_MARGIN}); agrees={r['agrees']}, enough={enough}"
            + "".join("; TOO FAR UNDER: " + c for c in r["far"]))


async def check_probes(ctx, server, setup: dict) -> tuple:
    """The probes through `generate`, greedy, all at once; then the reference's score of what
    came back. (agrees, note)."""
    reference, probe = blocks.reference(ctx.config), ctx.traffic["probe"]
    rng, n_new = arrivals.rng_for(ctx.seed, 7), probe["max_tokens"]
    prompts = [arrivals.token_ids(probe["prompt_len"], ctx.model["vocab_size"], rng) for _ in range(reference.MAX_PROBES)]
    t = time.perf_counter()
    outs = await asyncio.gather(*[server.generate(p, max_tokens=n_new, temperature=0.0) for p in prompts])
    setup["probes_s"] = time.perf_counter() - t
    t = time.perf_counter()
    r = score_all(ctx, server, [(p, o["token_ids"]) for p, o in zip(prompts, outs)], n_new,
                  [probe["prompt_len"] + n_new], probe.get("q_block", 256))
    setup["reference_s"] = time.perf_counter() - t
    enough = r["compared"] >= reference.MIN_COMPARED_POSITIONS
    return r["agrees"] and enough, _note(f"{len(prompts)} probes of {probe['prompt_len']} + {n_new} tokens", reference, r, enough)


def check_window(ctx, server, finished: list) -> tuple:
    """A sample of the requests the window finished, scored like the probes: the shortest one
    past `over` tokens (the longest there is, if none is) and the `short` shortest others.
    finished: [(prompt ids, generated ids)]. (agrees, note)."""
    reference, wc = blocks.reference(ctx.config), ctx.traffic["window_check"]
    by_len = sorted((f for f in finished if len(f[1]) >= wc["n_last"]), key=lambda f: len(f[0]) + len(f[1]))
    past = [f for f in by_len if len(f[0]) + len(f[1]) > wc["over"]]
    long = past[:1] or by_len[-1:]
    sample = [f for f in by_len if not (long and f is long[0])][:wc["short"]] + long
    t = time.perf_counter()
    r = score_all(ctx, server, sample, wc["n_last"], wc["lens"], wc["q_block"])
    enough = bool(sample) and r["compared"] >= reference.MIN_COMPARED_POSITIONS
    sizes = ", ".join(f"{len(p)} + {len(g)}" for p, g in sample)
    what = (f"{len(sample)} of the {len(finished)} requests the window finished ({sizes} tokens; the last {wc['n_last']} "
            f"generated positions of each, {time.perf_counter() - t:.1f} s)")
    return r["agrees"] and enough, _note(what, reference, r, enough)


class _Tap:
    """`server.generate` that also keeps the ids it returned, for `check_window`."""

    def __init__(self, server):
        self.server, self.ids = server, None

    async def generate(self, prompt, **kw):
        out = await self.server.generate(prompt, **kw)
        self.ids = out["token_ids"]
        return out


def warm_profiler(ctx) -> None:
    """A machine's first start of the profiler can take seconds in which the whole host stands
    still (call M of PR 28: the window's sleep of 51 s took 57.8 s and the trace, started 3 s
    before the window's end, covered nothing). A traced run takes that in set-up."""
    import shutil

    import jax

    scrap = ctx.trace_dir + ".warm"
    jax.profiler.start_trace(scrap)
    jax.profiler.stop_trace()
    shutil.rmtree(scrap, ignore_errors=True)


async def counters(server, ctx) -> tuple:
    """(`serving.counters`' counts plus, where the program counts them, the expert layers' pairs
    routed and held; the expert counts of the window since the last report, or None)."""
    st = await server.scheduler_stats()
    out = {k: st.get(k, 0) for k in ("iterations", "prefill_tokens", "decode_tokens")}
    out["rejected"] = sum(t.get("rejected", 0) for t in (st.get("tenants") or {}).values())
    out["jax_programs"] = ctx.compiles.programs
    experts = st.get("experts") or {}
    for key in ("pairs_routed", "pairs_held"):
        if key in experts:
            out["expert_" + key] = experts[key]
    return out, experts.get("window")


def run(ctx) -> dict:
    tr, notes, vocab = ctx.traffic, [], ctx.model["vocab_size"]
    set_flags(tr.get("flags", {}))
    from ray_tpu.llm import LLMServer

    setup, config = {}, serving.llm_config(ctx)
    c0 = ctx.compiles.snapshot()
    t = time.perf_counter()
    server = LLMServer(config)
    setup["weights_s"] = time.perf_counter() - t

    order = arrivals.rng_for(tr["order_seed"], 0)
    plens = arrivals.lengths(tr["prompt_len"], tr["pool"], order)
    outs = arrivals.lengths(tr["max_tokens"], tr["pool"], order)

    def request_stream():
        rng = arrivals.rng_for(ctx.seed, 2)
        i = int(tr["phase"])
        while True:
            p, m = plens[i % tr["pool"]], outs[i % tr["pool"]]
            yield dict(i=i, prompt=arrivals.token_ids(p, vocab, rng), max_tokens=int(m),
                       temperature=tr["temperature"], top_k=tr["top_k"])
            i += 1

    async def main():
        ok_ref, note = await check_probes(ctx, server, setup)
        notes.append(note)
        t = time.perf_counter()
        rng = arrivals.rng_for(ctx.seed, 3)
        for n in tr["warmup"]["prompt_lens"]:
            await server.generate(arrivals.token_ids(n, vocab, rng), max_tokens=tr["warmup"]["max_tokens"],
                                  temperature=tr["temperature"], top_k=tr["top_k"])
        if ctx.trace:
            warm_profiler(ctx)
        setup["warmup_s"] = time.perf_counter() - t

        stream = request_stream()
        rows, finished, stop = [], [], asyncio.Event()
        clock0 = time.monotonic()

        async def client():
            while not stop.is_set():
                req, tap = next(stream), _Tap(server)
                row = await serving.timed_request(tap, req, clock0, vocab)
                rows.append(row)
                if row["ok"]:
                    finished.append((row, req["prompt"], tap.ids))

        clients = [asyncio.create_task(client()) for _ in range(tr["clients"])]
        await asyncio.sleep(tr["ramp_seconds"])
        serving.note_compiles(ctx, setup, c0)
        before, _ = await counters(server, ctx)
        setup_s = ctx.since_start()
        w0 = time.monotonic()
        host = hostwatch.start(ticker=not ctx.trace)
        tracer = (asyncio.create_task(serving.trace_span(ctx, w0 + ctx.seconds - float(tr["trace_seconds"])))
                  if ctx.trace else None)
        await asyncio.sleep(ctx.seconds)
        w1 = time.monotonic()
        notes.append(host.stop())
        after, experts = await counters(server, ctx)
        if experts:
            notes.append(f"experts in the window: {experts}")
        stop.set()
        if tracer is not None:
            await tracer
        await server.shutdown()
        await asyncio.gather(*clients)
        ok_win, note = check_window(ctx, server, [(prompt, ids) for row, prompt, ids in finished
                                                  if w0 - clock0 <= row["sent"] + row["latency_s"] < w1 - clock0])
        notes.append(note)
        return serving.finish(rows, w0 - clock0, w1 - clock0, setup, setup_s, ok_ref and ok_win, notes,
                              before, after, slots=tr["slots"])

    return asyncio.run(main())
