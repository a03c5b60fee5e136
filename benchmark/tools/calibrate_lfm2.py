"""Readings for `lib/reference_lfm2.py`'s limits, taken by hand on the chip (PERF.md §6, PR 34):

    python benchmark/tools/calibrate_lfm2.py control --seed <n> [--long]

The cell's server answers the run's 8 probes (and, with `--long`, two requests of the window's
sizes over their last 128 generated positions). The float32 reference then scores the same
sequences three ways: as it is (how far the server's ids lie under its largest logit: sound), with
both operands of every matrix product but the router's rounded to float8 e4m3 (the contract's
control: how far the control's own ids lie under) and with bfloat16 operands (the stated
precision, read against itself in float32, PR 28's lesson), each with its mean, its largest and
its tail (quantiles, and the share of positions further under than a few marks: a router that flips
a near-tied expert moves a position's logits by a large part of their spread, so the largest over a
set is a heavy tail's and the limits are on the mean and on a share). It also says whether the logits are
all but an argmax: their standard deviation, how many standard deviations the input token's own
row scores over the rest (the head is the embedding again), and how often the reference's choice
is the input token. `--tiny` runs on the CPU at the tests' widths.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import numpy as np  # noqa: E402

import run as R  # noqa: E402
from lib import arrivals, blocks, serving  # noqa: E402

CELL = "lfm2-24b-a2b.serve-decode64"


def _read(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def start(seed: int, tiny: bool):
    """(ctx, server, reference) as `drivers/serve_closed_long.py` builds them."""
    if tiny:
        cell = _read("tests", "BENCHMARK.tiny-lfm2.json")["workloads"][0]
        config, traffic = _read("tests", "configs", "tiny-lfm2.json"), _read("tests", "traffic", "tiny-decode.json")
        os.environ.setdefault("RAY_TPU_LLM_PREFILL_BUCKET_MIN", "4")
    else:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"] if w["name"] == CELL)
        config, traffic = _read("configs", "lfm2-24b-a2b.json"), _read("traffic", "decode-closed64.json")
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peaks = (jax.devices(), {}) if tiny else R.require_chip(1)
    ctx = R.Context(cell=cell, config=config, traffic=traffic, seed=seed, seconds=1.0, trace=False, devices=devices,
                    peaks=peaks, compiles=R.CompileWatch(), t_start=time.perf_counter(), trace_dir=os.devnull)
    from drivers import serve_closed_long

    serve_closed_long.set_flags(traffic["flags"])
    from ray_tpu.llm import LLMServer

    return ctx, LLMServer(serving.llm_config(ctx)), blocks.reference(config)


def control(seed: int, tiny: bool, long: bool) -> None:
    import jax
    import jax.numpy as jnp

    ctx, server, reference = start(seed, tiny)
    vocab, probe, wc = ctx.model["vocab_size"], ctx.traffic["probe"], ctx.traffic["window_check"]

    def fp8(a):
        scale = jnp.max(jnp.abs(a)) / 448.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    def bf16(a):
        # not a pair of converts: the chip's compiler keeps the excess precision and drops them
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    variants = (("control (float8 operands)", dict(operand=fp8)), ("bfloat16 operands", dict(operand=bf16)))

    def scorer(n_last, q_block):
        def f(p, seq, n):
            rows = (n - n_last - 1, n_last)
            with jax.default_matmul_precision("highest"):
                lf = reference.forward(p, ctx.model, seq, q_block, rows=rows)
                others = [reference.forward(p, ctx.model, seq, q_block, rows=rows, **kw) for _, kw in variants]
            top2 = jax.lax.top_k(lf, 2)[0]
            fed = jax.lax.dynamic_slice_in_dim(seq, n - n_last - 1, n_last)      # the token each scored position read
            own = jnp.take_along_axis(lf, jax.lax.dynamic_slice_in_dim(seq, n - n_last, n_last)[:, None], axis=-1)[:, 0]
            std = jnp.std(lf, axis=-1)
            self_z = (jnp.take_along_axis(lf, fed[:, None], axis=-1)[:, 0] - jnp.mean(lf, axis=-1)) / std
            under = [top2[:, 0] - jnp.take_along_axis(lf, jnp.argmax(lo, axis=-1)[:, None], axis=-1)[:, 0] for lo in others]
            rms = [jnp.sqrt(jnp.mean((lo - lf) ** 2)) for lo in others]
            return (top2[:, 0] - top2[:, 1], top2[:, 0] - own, jnp.stack(under), jnp.stack(rms), jnp.mean(std), self_z,
                    jnp.argmax(lf, axis=-1) == fed)
        return jax.jit(f)

    def read(tag, seqs, n_last, lens, q_block, params):
        f = scorer(n_last, q_block)
        sound, under, margins, zs, repeats, stds = [], [[] for _ in variants], [], [], [], []
        for prompt, got in seqs:
            seq = list(prompt) + list(got)
            padded = np.zeros((min(n for n in lens if n >= len(seq)),), np.int32)
            padded[:len(seq)] = seq
            t = time.perf_counter()
            mar, ds, du, rms, std, z, rep = (np.asarray(a) for a in f(params, jnp.asarray(padded), jnp.int32(len(seq))))
            sound += ds.tolist()
            margins += mar.tolist()
            zs += z.tolist()
            repeats += rep.tolist()
            stds.append(float(std))
            for k in range(len(variants)):
                under[k] += du[k].tolist()
            print(f"[control] seed {seed} {tag} {len(prompt)}+{len(got)}: {time.perf_counter() - t:.1f}s sound mean {ds.mean():.6f} "
                  f"(at most {ds.max():.5f}); " + "; ".join(f"{name} mean {du[k].mean():.6f} (at most {du[k].max():.5f}, a logit's rms "
                                                            f"{rms[k]:.6f})" for k, (name, _) in enumerate(variants))
                  + f"; a logit's std {std:.5f}", flush=True)
        marks = (0.25, 0.35, 0.5, 0.7, 1.0, 1.5, 2.0, 2.5)

        def tail(values):
            v = np.asarray(values)
            return ("p50 %.4f p90 %.4f p99 %.4f; share further under than " % tuple(np.quantile(v, [0.5, 0.9, 0.99]))
                    + ", ".join(f"{m}: {100 * np.mean(v > m):.1f}%" for m in marks))

        print(f"[control] seed {seed} {tag} TAILS sound: {tail(sound)}", flush=True)
        for k, (name, _) in enumerate(variants):
            print(f"[control] seed {seed} {tag} TAILS {name}: {tail(under[k])}", flush=True)
        q = np.quantile(margins, [0.1, 0.5, 0.9])
        print(f"[control] seed {seed} {tag} ALL {len(sound)} positions: sound mean deficit {np.mean(sound):.6f}, largest {np.max(sound):.5f}; "
              + "; ".join(f"{name} mean deficit {np.mean(under[k]):.6f}, largest {np.max(under[k]):.5f}" for k, (name, _) in enumerate(variants))
              + f"; the reference's margins p10 {q[0]:.5f} p50 {q[1]:.5f} p90 {q[2]:.5f}; a logit's std {np.mean(stds):.5f}; the input token's "
              f"own row: self-token z-score {np.mean(zs):.2f}, the reference's choice at {100 * np.mean(repeats):.1f}% of positions", flush=True)

    async def main():
        rng = arrivals.rng_for(seed, 7)
        prompts = [arrivals.token_ids(probe["prompt_len"], vocab, rng) for _ in range(reference.MAX_PROBES)]
        outs = await asyncio.gather(*[server.generate(p, max_tokens=probe["max_tokens"], temperature=0.0) for p in prompts])
        seqs, longs = [(p, o["token_ids"]) for p, o in zip(prompts, outs)], []
        if long:
            sizes = (20, 60) if tiny else (512, 2048)
            lp = [arrivals.token_ids(n, vocab, rng) for n in sizes]
            lo = await asyncio.gather(*[server.generate(p, max_tokens=wc["n_last"] + (0 if tiny else 256), temperature=0.0) for p in lp])
            longs = [(p, o["token_ids"]) for p, o in zip(lp, lo)]
        await server.shutdown()
        params = reference.plain_tree(server.weights()[1])
        read("probes", seqs, probe["max_tokens"], [probe["prompt_len"] + probe["max_tokens"]], probe.get("q_block", 256), params)
        if longs:
            read("long", longs, wc["n_last"], wc["lens"], wc["q_block"], params)

    asyncio.run(main())


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control",))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    control(args.seed, args.tiny, args.long)
    sys.stdout.flush()
    os._exit(0)  # the engine's stepper is a daemon thread that may still hold the device
