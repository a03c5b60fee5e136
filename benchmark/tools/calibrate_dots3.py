"""Readings for `lib/reference_dots3.py`'s limits, taken by hand on the chip (PERF.md §6, PR 28):

    python benchmark/tools/calibrate_dots3.py control --seed <n> [--long]
    python benchmark/tools/calibrate_dots3.py noise --seed <n>

`control`: the cell's server answers the run's 8 probes (and, with `--long`, two requests of the
window's sizes); the float32 reference and the contract's control (the reference with both operands
of every matrix product rounded to float8 e4m3) score the same sequences: the mean of how far the
server's ids (sound) and the control's ids lie under the reference's largest logit, by set of 128.
`noise`: the engine's own functions (chunked prefill, then decode through the cache) on one request
of the window's size, teacher-forced on their own ids, against the reference logit by logit: in
bfloat16 (how far one flipped router choice moves a difference of two logits, and how often), in
float32 (is the function right at the real size) and in a slot that a longer request left behind
(bit for bit the fresh slot's). `--tiny` runs either on the CPU at the tests' widths.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
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


def _read(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def start(seed: int, tiny: bool):
    """(ctx, server, reference) as `drivers/serve_closed_long.py` builds them."""
    if tiny:
        cell = _read("tests", "BENCHMARK.tiny-dots3.json")["workloads"][0]
        config, traffic = _read("tests", "configs", "tiny-dots3.json"), _read("tests", "traffic", "tiny-longctx.json")
        os.environ.setdefault("RAY_TPU_LLM_PREFILL_BUCKET_MIN", "4")
    else:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"] if w["name"] == "dots3-note-prev.serve-longctx")
        config, traffic = _read("configs", "dots3-note-prev.json"), _read("traffic", "longctx-closed16.json")
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peaks = (jax.devices(), {}) if tiny else R.require_chip(1)
    ctx = R.Context(cell=cell, config=config, traffic=traffic, seed=seed, seconds=1.0, trace=False, devices=devices,
                    peaks=peaks, compiles=R.CompileWatch(), t_start=time.perf_counter(), trace_dir=os.devnull)
    R.load_driver(traffic["driver"])[0].set_flags(traffic["flags"])
    from ray_tpu.llm import LLMServer

    return ctx, LLMServer(serving.llm_config(ctx)), blocks.reference(config)


def fp8(a):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def control(seed: int, tiny: bool, long: bool) -> None:
    import jax
    import jax.numpy as jnp

    ctx, server, reference = start(seed, tiny)
    vocab, probe, wc = ctx.model["vocab_size"], ctx.traffic["probe"], ctx.traffic["window_check"]

    def both(n_last, q_block):
        def f(p, seq, n):
            rows = (n - n_last - 1, n_last)
            with jax.default_matmul_precision("highest"):
                lf = reference.forward(p, ctx.model, seq, q_block, None, rows=rows)
                lc = reference.forward(p, ctx.model, seq, q_block, fp8, rows=rows)
            top2 = jax.lax.top_k(lf, 2)[0]
            own = jnp.take_along_axis(lf, jax.lax.dynamic_slice_in_dim(seq, n - n_last, n_last)[:, None], axis=-1)[:, 0]
            ic = jnp.argmax(lc, axis=-1)
            ctl = jnp.take_along_axis(lf, ic[:, None], axis=-1)[:, 0]
            return (jnp.argmax(lf, axis=-1), top2[:, 0] - top2[:, 1], top2[:, 0] - own, ic, top2[:, 0] - ctl,
                    jnp.sqrt(jnp.mean((lc - lf) ** 2)), jnp.std(lf))
        return jax.jit(f)

    def read(tag, seqs, n_last, lens, q_block, params):
        f = both(n_last, q_block)
        snd, ctl, snd_part, ctl_part = [], [], [], []
        for prompt, got in seqs:
            seq = list(prompt) + list(got)
            padded = np.zeros((min(n for n in lens if n >= len(seq)),), np.int32)
            padded[:len(seq)] = seq
            t = time.perf_counter()
            idf, mar, ds, ic, dc, rms, std = (np.asarray(a) for a in f(params, jnp.asarray(padded), jnp.int32(len(seq))))
            g = np.asarray(got[-n_last:])
            snd += ds.tolist()
            ctl += dc.tolist()
            snd_part += mar[idf != g].tolist()
            ctl_part += mar[idf != ic].tolist()
            print(f"[control] seed {seed} {tag} {len(prompt)}+{len(got)}: {time.perf_counter() - t:.1f}s sound mean {ds.mean():.5f} "
                  f"(at most {ds.max():.4f}) control mean {dc.mean():.5f} (at most {dc.max():.4f}) control rms {rms:.4f} of std {std:.3f}", flush=True)
        for k in range(0, len(snd), 128):
            print(f"[control] seed {seed} {tag} positions {k}..{k + 128}: sound {np.mean(snd[k:k + 128]):.5f} control {np.mean(ctl[k:k + 128]):.5f}")
        print(f"[control] seed {seed} {tag} ALL {len(snd)} positions: sound mean deficit {np.mean(snd):.5f} (ids differ at margins "
              f"{sorted(round(m, 4) for m in snd_part)}); control mean deficit {np.mean(ctl):.5f}, ids differ at {len(ctl_part)}, "
              f"largest margins {sorted(round(m, 3) for m in ctl_part)[-5:]}", flush=True)

    async def main():
        rng = arrivals.rng_for(seed, 7)
        prompts = [arrivals.token_ids(probe["prompt_len"], vocab, rng) for _ in range(reference.MAX_PROBES)]
        outs = await asyncio.gather(*[server.generate(p, max_tokens=probe["max_tokens"], temperature=0.0) for p in prompts])
        seqs, longs = [(p, o["token_ids"]) for p, o in zip(prompts, outs)], []
        if long:
            sizes = (20, 60) if tiny else (6144, 16419)
            lp = [arrivals.token_ids(n, vocab, rng) for n in sizes]
            lo = await asyncio.gather(*[server.generate(p, max_tokens=wc["n_last"], temperature=0.0) for p in lp])
            longs = [(p, o["token_ids"]) for p, o in zip(lp, lo)]
        await server.shutdown()
        params = reference.plain_tree(server.weights()[1])
        read("probes", seqs, probe["max_tokens"], [probe["prompt_len"] + probe["max_tokens"]], probe.get("q_block", 256), params)
        if longs:
            read("long", longs, wc["n_last"], wc["lens"], wc["q_block"], params)

    asyncio.run(main())


def noise(seed: int, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import dots3

    ctx, server, reference = start(seed, tiny)
    cfg, params = server.weights()
    asyncio.run(server.shutdown())
    wc = ctx.traffic["window_check"]
    P, N, CH, T, PA = (41, 12, 16, 128, 60) if tiny else (16419, 384, 1024, 32768, 20480)
    rng = arrivals.rng_for(seed, 2)
    prompt = np.asarray(arrivals.token_ids(P, ctx.model["vocab_size"], rng), np.int32)
    other = np.asarray(arrivals.token_ids(PA, ctx.model["vocab_size"], rng), np.int32)

    def programs(c, precision):
        def pre(p, tok, caches, slot, off, total):
            with jax.default_matmul_precision(precision):
                return dots3.prefill(p, c, tok, caches, slot, off, total)

        def dec(p, last, caches, lens, gate):
            with jax.default_matmul_precision(precision):
                return dots3.decode(p, c, last, caches, lens, gate)
        return jax.jit(pre, donate_argnums=(2,)), jax.jit(dec, donate_argnums=(2,))

    def path(progs, caches, toks, n_new, given=None, slot=1):
        """Prefill `toks` into `slot` in chunks of CH, then n_new - 1 decode steps fed `given` (or the path's own
        argmax): (logits [n_new, V] at the positions that predict the n_new generated tokens, the ids fed, caches)."""
        pre, dec = progs
        out, fed = [], []
        for off in range(0, len(toks), CH):
            pad = np.zeros((1, CH), np.int32)
            n = min(CH, len(toks) - off)
            pad[0, :n] = toks[off:off + n]
            last, caches, _ = pre(params, jnp.asarray(pad), caches, jnp.int32(slot), jnp.int32(off), jnp.int32(len(toks)))
        out.append(np.asarray(last, np.float32))
        lens, gate, lt = np.zeros((2,), np.int32), np.zeros((2,), bool), np.zeros((2,), np.int32)
        gate[slot] = True
        for j in range(n_new - 1):
            fed.append(int(given[j]) if given is not None else int(np.argmax(out[-1])))
            lens[slot], lt[slot] = len(toks) + j, fed[-1]
            logits, caches, _ = dec(params, jnp.asarray(lt), caches, jnp.asarray(lens), jnp.asarray(gate))
            out.append(np.asarray(logits, np.float32)[slot])
        fed.append(int(given[n_new - 1]) if given is not None else int(np.argmax(out[-1])))
        return np.stack(out), fed, caches

    def report(tag, eng, ref):
        d = eng - ref
        top = np.argsort(-ref, axis=-1)[:, :10]
        rows = np.arange(len(ref))[:, None]
        a = np.abs(d[rows, top[:, 1:]] - d[rows, top[:, :1]])  # how far the path moves (candidate - best), nine candidates a position
        margin = ref[rows[:, 0], top[:, 0]] - ref[rows[:, 0], top[:, 1]]
        flips = np.argmax(eng, -1) != top[:, 0]
        worst = np.argsort(-a.max(-1))[:5]
        print(f"[noise] seed {seed} {tag}: {len(ref)} positions, rms of a logit's error {np.sqrt(np.mean(d ** 2)):.5f} (largest position "
              f"{np.sqrt(np.mean(d ** 2, -1)).max():.5f}); move of (candidate - best) over {a.size} pairs: median {np.median(a):.4f} p90 "
              f"{np.quantile(a, .9):.4f} p99 {np.quantile(a, .99):.4f} p99.9 {np.quantile(a, .999):.4f} max {a.max():.4f}; positions whose largest "
              f"move is over 0.1/0.2/0.3: {(a.max(-1) > .1).sum()}/{(a.max(-1) > .2).sum()}/{(a.max(-1) > .3).sum()}; ids differ at "
              f"{int(flips.sum())}, margins {sorted(round(float(m), 4) for m in margin[flips])}; worst positions {worst.tolist()} move "
              f"{[round(float(x), 3) for x in a.max(-1)[worst]]} at a logit's rms {[round(float(x), 4) for x in np.sqrt(np.mean(d[worst] ** 2, -1))]}",
              flush=True)

    bf = programs(cfg, "default")
    eng, fed, caches = path(bf, dots3.init_caches(cfg, 2, T), prompt, N)
    _, _, caches = path(bf, caches, other, min(N, 64))  # a longer request's rows, ring and indexer keys stay in the slot
    again, _, caches = path(bf, caches, prompt, N, given=fed)
    print(f"[noise] seed {seed} a slot taken over from a {PA}-token request: logits equal bit for bit {bool((again == eng).all())}, "
          f"largest difference {np.abs(again - eng).max():.6f}", flush=True)
    del caches, again
    seq = np.zeros((min(n for n in wc["lens"] if n >= P + N),), np.int32)
    seq[:P], seq[P:P + N] = prompt, fed
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, s, first: reference.forward(p, ctx.model, s, wc["q_block"], None, rows=(first, N)))(
            reference.plain_tree(params), jnp.asarray(seq), jnp.int32(P - 1)))
    report("bfloat16 engine functions against the float32 reference", eng, ref)
    c32 = dataclasses.replace(cfg, dtype=jnp.float32)
    e32, _, _ = path(programs(c32, "highest"), dots3.init_caches(c32, 2, T), prompt, min(N, 128), given=fed)
    report("the same functions in float32 against the float32 reference", e32, ref[:len(e32)])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control", "noise"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    control(args.seed, args.tiny, args.long) if args.what == "control" else noise(args.seed, args.tiny)
    sys.stdout.flush()
    os._exit(0)  # the engine's stepper is a daemon thread that may still hold the device
