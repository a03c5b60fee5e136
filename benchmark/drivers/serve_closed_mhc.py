"""Driver `serve_closed_mhc`: `serve_closed_counts`' run, unchanged, and after it a check of the
mechanism that bfloat16 ids cannot make. It returns `serve_closed`'s record; `correct` needs both.

Why: the cell's block mixes four residual streams through a matrix made doubly stochastic by 20
Sinkhorn steps. In bfloat16 a near-tied expert flips in one of five layers, a position's logits move
by 0.1 to 0.6 of their spread, and the limits that a sound run needs (`MEAN_DEFICIT_TOL` 0.68) also
pass a mixing matrix made with 1 step (0.23 to 0.56: `lib/reference_xing4.py`). So the timed run's
comparison holds the path at the timed sizes to the reference as far as rounding lets it, and this
check holds the mechanism where rounding is out of the way: after the window, when the served engine
is gone, the same block is served again by an `LLMServer` of its own, through the same engine,
scheduler and programs (chunked prefill beside decoding slots, single and multi-step decode, sampling
on the device, both kernels), at every published width and the cell's slots, in float32 with float32
products (`jax_default_matmul_precision` "highest"), cut to the traffic file's `mechanism.n_layers`
layers (the leading dense one and expert layers) so that 4 bytes a parameter fit the chip. It answers
`MAX_PROBES` seeded probes sent together; the reference scores the ids over the very tree served, as
the cell's probes are scored (`drivers/serve_closed_long.py:score_all`), and their mean distance under
the reference's largest logit is held to `MECHANISM_DEFICIT_TOL`. There a sound program reads 0 (it is
the reference to four decimals of a logit) and 1 Sinkhorn step for 20, in the program or in the
reference, reads tens of times the limit (`lib/reference_xing4.py` has the readings;
`tools/calibrate_xing4.py mechanism` takes them).

It runs after the window so that nothing of it (its weights, its programs, the precision it sets and
sets back) stands beside the measured run; its seconds are in no metric and in the note it writes.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import time

from drivers import serve_closed_counts as counts
from drivers import serve_closed_long as loop
from lib import arrivals, blocks, serving

RECORD = "serve_closed"


def check_mechanism(ctx, served=None, scored=None) -> tuple:
    """(agrees, note, the score's readings). `served` are model keys the server alone is built with and
    `scored` keys the reference alone reads (the controls: `hc_sinkhorn_iters=1` on either side has to come
    out as not agreeing)."""
    import jax

    from ray_tpu.llm import LLMServer

    reference, mech = blocks.reference(ctx.config), ctx.traffic["mechanism"]
    model = dict(ctx.model, n_layers=mech["n_layers"], max_seq=mech["max_seq"], dtype="float32", param_dtype="float32")
    traffic = dict(ctx.traffic, max_seq=mech["max_seq"])
    served, scored = served or {}, scored or {}
    of_server = dataclasses.replace(ctx, config=dict(ctx.config, model=dict(model, **served)), traffic=traffic)
    of_reference = dataclasses.replace(ctx, config=dict(ctx.config, model=dict(model, **scored)), traffic=traffic)
    rng, n_new = arrivals.rng_for(ctx.seed, 11), mech["max_tokens"]
    prompts = [arrivals.token_ids(mech["prompt_len"], model["vocab_size"], rng) for _ in range(reference.MAX_PROBES)]

    t = time.perf_counter()
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")  # the engine's stepper is another thread: a context would not reach it
    try:
        server = LLMServer(serving.llm_config(of_server))

        async def answers():
            outs = await asyncio.gather(*[server.generate(p, max_tokens=n_new, temperature=0.0) for p in prompts])
            await server.shutdown()
            return outs

        outs = asyncio.run(answers())
        r = loop.score_all(of_reference, server, [(p, o["token_ids"]) for p, o in zip(prompts, outs)], n_new,
                           [mech["prompt_len"] + n_new], mech["q_block"])
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    del server
    gc.collect()
    enough = r["compared"] >= reference.MIN_COMPARED_POSITIONS and all(len(o["token_ids"]) == n_new for o in outs)
    agrees = enough and r["mean_deficit"] <= reference.MECHANISM_DEFICIT_TOL
    control = "".join(f", the {side} with {k}={v}" for side, keys in (("server", served), ("reference", scored)) for k, v in keys.items())
    note = (f"mechanism: {len(prompts)} probes of {mech['prompt_len']} + {n_new} tokens through {mech['n_layers']} layers served in float32 "
            f"(products 'highest'{control}), {r['compared']} positions compared, ids differ at "
            f"{len(r['parted'])}; the server's ids lie under the reference's largest logit by {r['mean_deficit']:.6f} in the mean "
            f"(limit {reference.MECHANISM_DEFICIT_TOL}), at most {max(r['deficits'], default=0.0):.4f}; agrees={agrees}, enough={enough}, "
            f"{time.perf_counter() - t:.1f} s")
    return agrees, note, r


def run(ctx) -> dict:
    record = counts.run(ctx)
    gc.collect()  # the served engine's weights and cache: nothing holds them after the run
    agrees, note, _ = check_mechanism(ctx)
    record["correct"] = bool(record["correct"]) and agrees
    record.setdefault("notes", []).append(note)
    return record
