"""What the two serve drivers share: the server built in this process from the cell's
files, the reference probes on the cold path, the warm-up, one timed request, and the
counters read round the window. Only public entry points of the program are used:
`load_model`, `LLMServer` and its `generate`, `scheduler_stats` and `shutdown`.
"""

from __future__ import annotations

import asyncio
import gc
import time

import numpy as np

from lib import arrivals, blocks
from lib.rows import anchor

PROBE_PROMPT_TOKENS = 64
PROBE_NEW_TOKENS = 16


def llm_config(ctx):
    from ray_tpu.llm import LLMConfig

    tr = ctx.traffic
    return LLMConfig(
        model_id=ctx.cell["config"], model_config=ctx.model_config(max_seq=tr["max_seq"]),
        num_slots=tr["slots"], max_seq=tr["max_seq"], seed=ctx.seed % (2**31 - 1),
    )


def reference_probes(ctx, config, setup: dict) -> list:
    """Greedy ids and margins of the plain reference for seeded probe prompts, on the
    parameters `load_model` gives for this configuration and seed. The parameters are
    freed before the server builds its own (same seed, same weights)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import load_model

    reference = blocks.reference(ctx.config)
    t = time.perf_counter()
    _, params = load_model(config)
    params = reference.plain_tree(params)
    jax.block_until_ready(params)
    setup["reference_weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    rng = arrivals.rng_for(ctx.seed, 7)
    prompts = [arrivals.token_ids(PROBE_PROMPT_TOKENS, ctx.model["vocab_size"], rng)
               for _ in range(reference.MAX_PROBES)]
    # every seed prepares the same number of probes, in one call: how many the server
    # is sent depends on where its ids part from these (`check_probes`)
    greedy = jax.jit(jax.vmap(lambda p, prompt: reference.greedy(p, ctx.model, prompt, PROBE_NEW_TOKENS),
                              in_axes=(None, 0)))
    ids, margins = greedy(params, jnp.asarray(prompts, jnp.int32))
    probes = [{"prompt": p, "ids": i, "margins": m}
              for p, i, m in zip(prompts, np.asarray(ids).tolist(), np.asarray(margins).tolist())]
    del params, greedy
    gc.collect()
    setup["reference_s"] = time.perf_counter() - t
    return probes


def build(ctx) -> tuple:
    """Set-up before the event loop: the reference's probes, then the server. Returns
    (server, probes, setup, compile snapshot at the start)."""
    from ray_tpu.llm import LLMServer

    setup = {}
    config = llm_config(ctx)
    c0 = ctx.compiles.snapshot()
    probes = reference_probes(ctx, config, setup)
    t = time.perf_counter()
    server = LLMServer(config)
    setup["weights_s"] = time.perf_counter() - t
    return server, probes, setup, c0


async def prepare(ctx, server, probes: list, setup: dict, notes: list, prompt_lens) -> bool:
    """The probes on the cold path, then the warm-up. Whether the reference agrees."""
    t = time.perf_counter()
    ok_ref, note = await check_probes(server, probes, blocks.reference(ctx.config))
    notes.append(note)
    setup["probes_s"] = time.perf_counter() - t
    t = time.perf_counter()
    await warm_up(ctx, server, arrivals.rng_for(ctx.seed, 3), prompt_lens)
    setup["warmup_s"] = time.perf_counter() - t
    return ok_ref


def note_compiles(ctx, setup: dict, c0: dict) -> None:
    c1 = ctx.compiles.snapshot()
    setup["compile_s"] = c1["seconds"] - c0["seconds"]
    setup["programs"] = c1["programs"] - c0["programs"]
    setup["ramp_s"] = float(ctx.traffic["ramp_seconds"])


async def check_probes(server, probes: list, reference) -> tuple:
    """The probes through `generate`, greedy, one at a time and before any other
    traffic: the cold prefill path. At least two, and on until enough positions of a
    clear margin are compared, by the block's own `reference` module and its
    tolerances. (agrees, note)."""
    compared, agrees, sent = 0, True, 0
    for p in probes:
        if sent >= 2 and compared >= reference.MIN_COMPARED_POSITIONS:
            break
        out = await server.generate(p["prompt"], max_tokens=PROBE_NEW_TOKENS, temperature=0.0)
        ok, n = reference.compare_greedy(p["ids"], p["margins"], out["token_ids"])
        agrees = agrees and ok and len(out["token_ids"]) == PROBE_NEW_TOKENS
        compared += n
        sent += 1
    enough = compared >= reference.MIN_COMPARED_POSITIONS
    note = (f"reference: {sent} of {len(probes)} probes sent, {compared} positions of margin >= "
            f"{reference.NEAR_TIE_MARGIN} compared, agrees={agrees}, enough={enough}")
    return agrees and enough, note


async def warm_up(ctx, server, rng, prompt_lens) -> None:
    """The cell's own shapes and no others. First one request per listed prompt length,
    one after another on an idle engine, with the cell's sampling: they reach every
    prefill bucket and decode program the traffic uses. Then, together, one short request
    for each distinct count of whole `cover_block`-token blocks among the traffic's
    prompts (`prompt_lens`): after a prefill the engine copies the prompt's whole blocks
    to its prefix cache with slices whose shape is the block count, and each new shape
    is a small program that would otherwise be built inside the window."""
    tr = ctx.traffic
    vocab, warm = ctx.model["vocab_size"], tr["warmup"]
    sampling = dict(temperature=tr["temperature"], top_k=tr["top_k"])
    for n in warm["prompt_lens"]:
        await server.generate(arrivals.token_ids(n, vocab, rng), max_tokens=warm["max_tokens"], **sampling)
    block = int(warm["cover_block"])
    counts = sorted({int(n) // block for n in prompt_lens} - {0})
    await asyncio.gather(*[
        server.generate(arrivals.token_ids(c * block, vocab, rng), max_tokens=2, **sampling)
        for c in counts])


async def timed_request(server, req: dict, clock0: float, vocab: int) -> dict:
    """One request through `generate`, as a row. Times are seconds since `clock0` on the
    monotonic clock; `due` is None in a closed loop."""
    from ray_tpu.llm import EngineOverloadedError

    row = dict(i=req["i"], due=req.get("due"), prompt_len=len(req["prompt"]),
               max_tokens=req["max_tokens"], ok=False, rejected=False, interrupted=False,
               n_out=0, ttft_s=None, latency_s=None, queue_s=None, prefill_s=None)
    row["sent"] = time.monotonic() - clock0
    try:
        out = await server.generate(req["prompt"], max_tokens=req["max_tokens"],
                                    temperature=req["temperature"], top_k=req["top_k"])
    except EngineOverloadedError:
        row["rejected"] = True
        return row
    ids = out["token_ids"]
    if ids and ids[-1] < 0:  # the server was shut down under this request
        row["interrupted"] = True
        ids = ids[:-1]
    row["n_out"] = len(ids)
    row["latency_s"] = out["latency_s"]
    if ids:
        row["ttft_s"] = out["ttft_s"]
    timing = out.get("timing") or {}
    row["queue_s"] = timing.get("queue_s")
    phases = timing.get("phases") or {}
    if "prefill-chunk" in phases:
        row["prefill_s"] = phases["prefill-chunk"]["seconds"]
    row["ok"] = (not row["interrupted"] and len(ids) == req["max_tokens"]
                 and all(0 <= t < vocab for t in ids))
    return row


async def counters(server, ctx) -> dict:
    """The scheduler's counts and JAX's count of programs built, to be read round the window."""
    st = await server.scheduler_stats()
    out = {k: st.get(k, 0) for k in ("iterations", "prefill_tokens", "decode_tokens")}
    out["rejected"] = sum(t.get("rejected", 0) for t in (st.get("tenants") or {}).values())
    out["jax_programs"] = ctx.compiles.programs
    return out


async def trace_span(ctx, t0: float) -> None:
    """In a traced run, the profiler covers `trace_seconds` from `t0` on the monotonic
    clock. It is started and stopped off the event loop, so that the load generator
    keeps time."""
    import jax

    loop = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    await loop.run_in_executor(None, jax.profiler.start_trace, ctx.trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        await asyncio.sleep(max(0.0, t0 + float(ctx.traffic["trace_seconds"]) - time.monotonic()))
    await loop.run_in_executor(None, jax.profiler.stop_trace)


def finish(rows: list, lo: float, hi: float, setup: dict, setup_s: float, correct_ref: bool,
           notes: list, c_before: dict, c_after: dict, **extra) -> dict:
    """The record of a serve run. The window is [lo, hi) on the rows' clock."""
    in_window = [r for r in rows if lo <= anchor(r) < hi]
    failed = [r for r in in_window if r["rejected"] or (not r["ok"] and not r["interrupted"])]
    deltas = {k: c_after[k] - c_before[k] for k in c_after}
    return dict(
        correct=bool(correct_ref) and not failed, attempted=len(in_window), failed=len(failed),
        setup_s=setup_s, setup=setup, window_s=hi - lo, window=[lo, hi], rows=rows,
        counters=deltas, window_compiles=deltas["jax_programs"], notes=notes, **extra)
