"""Readings for `lib/reference_laguna.py`'s limits, taken by hand on the chip (PERF.md §6, PR 46):

    python benchmark/tools/calibrate_laguna.py control --seed <n> [--long]

The cell's server answers the run's 8 probes (and, with `--long`, two requests of the window's
sizes, one past 16384 tokens). Then every set of generated positions goes through the comparison
that decides `correct`, by the harness's own code (`calibrate_pangu_moe.py`'s, by import), three ways:

- sound: the server's ids, scored as the cell's driver scores them (`drivers/serve_closed_long.py:score_all`);
- control: the ids of the reference with both operands of every matrix product but the router's
  rounded to float8 e4m3 (each tensor scaled), one precision below the bfloat16 the configuration
  states, on the same sequences; it has to come out `agrees=False`;
- window: the ids of the reference in float32 with a window of 1024 in place of 512 (what a ring
  that kept or showed the wrong rows would read like); it has to come out `agrees=False`.

    python benchmark/tools/calibrate_laguna.py float32 --seed <n>

The block's own programs in float32 against the reference, rounding out of the way (`float32` below).
`--tiny` runs either on the CPU at the tests' widths (a window of 16 for 8).
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
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))

import numpy as np  # noqa: E402

import run as R  # noqa: E402
from calibrate_dots3 import _read, fp8  # noqa: E402
from calibrate_pangu_moe import held_to_the_limits  # noqa: E402
from drivers import serve_closed_long as loop  # noqa: E402
from lib import arrivals, blocks, serving  # noqa: E402

CELL = "laguna-s-2.1.serve-mixedlen24"


def start(seed: int, tiny: bool):
    """(ctx, server, reference) as the cell's driver builds them."""
    if tiny:
        cell = _read("tests", "BENCHMARK.tiny-laguna.json")["workloads"][0]
        config, traffic = _read("tests", "configs", "tiny-laguna.json"), _read("tests", "traffic", "tiny-mixedlen.json")
        os.environ.setdefault("RAY_TPU_LLM_PREFILL_BUCKET_MIN", "4")
    else:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"] if w["name"] == CELL)
        config, traffic = _read("configs", cell["config"] + ".json"), _read("traffic", cell["traffic"] + ".json")
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peaks = (jax.devices(), {}) if tiny else R.require_chip(1)
    ctx = R.Context(cell=cell, config=config, traffic=traffic, seed=seed, seconds=1.0, trace=False, devices=devices,
                    peaks=peaks, compiles=R.CompileWatch(), t_start=time.perf_counter(), trace_dir=os.devnull)
    loop.set_flags(traffic["flags"])
    from ray_tpu.llm import LLMServer

    return ctx, LLMServer(serving.llm_config(ctx)), blocks.reference(config)


def logits_of(reference, model: dict, n_last: int, q_block: int, **kw):
    """A program (tree, padded sequence, its length) -> the reference's logits [n_last, V] at the
    positions that choose the last `n_last` tokens, with `operand` or `window` as `reference.forward` takes them."""
    import jax

    def f(p, seq, n):
        with jax.default_matmul_precision("highest"):
            return reference.forward(p, model, seq, q_block, rows=(n - n_last - 1, n_last), **kw)

    return jax.jit(f)


def control(seed: int, tiny: bool, long: bool) -> int:
    import jax.numpy as jnp

    ctx, server, reference = start(seed, tiny)
    vocab, probe, wc = ctx.model["vocab_size"], ctx.traffic["probe"], ctx.traffic["window_check"]
    as_expected, dump = True, {}  # every set's deficits, position by position, for a reading of another statistic

    def say(tag, what, r, expected: bool):
        nonlocal as_expected
        as_expected = as_expected and r["agrees"] == expected
        print(f"[control] seed {seed} {tag} {what}: mean deficit {r['mean']:.5f} (limit {reference.MEAN_DEFICIT_TOL}), at most "
              f"{r['most']:.4f} (limit {reference.NEAR_TIE_MARGIN}), ids differ at {r['differ']} of {r['compared']}; "
              f"agrees={r['agrees']}" + (f" by {' and '.join(r['failed'])}" if r["failed"] else "")
              + ("" if r["agrees"] == expected else f"  NOT AS EXPECTED ({expected})"), flush=True)

    def read(tag, seqs, n_last, lens, q_block, params):
        sound = loop.score_all(ctx, server, seqs, n_last, lens, q_block)
        d = sound["deficits"]
        dump[f"{tag} sound"] = [round(float(x), 5) for x in d]
        say(tag, f"all {len(d)} positions sound", dict(agrees=sound["agrees"], failed=[], mean=sound["mean_deficit"], most=max(d),
                                                        differ=len(sound["parted"]), compared=sound["compared"]), True)
        programs = {"float32": logits_of(reference, ctx.model, n_last, q_block),
                    "control (float8 e4m3 operands)": logits_of(reference, ctx.model, n_last, q_block, operand=fp8),
                    "window (twice the size)": logits_of(reference, ctx.model, n_last, q_block, window=2 * ctx.model["sliding_window"])}
        got = {what: [] for what in programs}
        for prompt, ids in seqs:
            seq = list(prompt) + list(ids)
            padded = np.zeros((min(n for n in lens if n >= len(seq)),), np.int32)
            padded[:len(seq)] = seq
            for what, f in programs.items():
                got[what].append(np.asarray(f(params, jnp.asarray(padded), jnp.int32(len(seq)))))
        lf = np.concatenate(got.pop("float32"))
        for what, parts in got.items():
            lc = np.concatenate(parts)
            print(f"[control] seed {seed} {tag} {what}: rms {np.sqrt(np.mean((lc - lf) ** 2)):.4f} a logit of std {lf.std():.3f}")
            ids = lc.argmax(-1)
            dump[f"{tag} {what}"] = [round(float(x), 5) for x in lf.max(-1) - lf[np.arange(len(ids)), ids]]
            say(tag, f"{len(lf)} positions {what}", held_to_the_limits(reference, lf, ids), False)

    async def main():
        rng = arrivals.rng_for(seed, 7)
        prompts = [arrivals.token_ids(probe["prompt_len"], vocab, rng) for _ in range(reference.MAX_PROBES)]
        outs = await asyncio.gather(*[server.generate(p, max_tokens=probe["max_tokens"], temperature=0.0) for p in prompts])
        seqs, longs = [(p, o["token_ids"]) for p, o in zip(prompts, outs)], []
        if long:
            sizes = (20, 60) if tiny else (1500, 16419)
            lp = [arrivals.token_ids(n, vocab, rng) for n in sizes]
            lo = await asyncio.gather(*[server.generate(p, max_tokens=wc["n_last"], temperature=0.0) for p in lp])
            longs = [(p, o["token_ids"]) for p, o in zip(lp, lo)]
        await server.shutdown()
        params = reference.plain_tree(server.weights()[1])
        read("probes", seqs, probe["max_tokens"], [probe["prompt_len"] + probe["max_tokens"]], probe.get("q_block", 256), params)
        if longs:
            read("long", longs, wc["n_last"], wc["lens"], wc["q_block"], params)

    asyncio.run(main())
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"calibrate_laguna_{seed}.json"), "w") as f:
        json.dump(dump, f)
    print(f"[control] seed {seed}: every sound set agrees and every control set does not: {as_expected}", flush=True)
    return 0 if as_expected or tiny else 1  # the limits are the cell's: at the tests' widths the readings are shown, not judged


def float32(seed: int, tiny: bool) -> int:
    """The block's own `prefill` and `decode` in float32 with float32 products (on the TPU the decode
    steps' attention is the kernel `cached_attn` over slabs and rings), at the published widths with 8
    of a layer's experts held and 2 slots of 4096 rows so that a float32 tree fits: a prompt of 2304
    tokens in chunks of 1024, 1024 and 256, then 24 decode steps, every logits row against the
    reference's. Rounding is out of the way, so what is left is the function: how to tell a wrong one
    from bfloat16 (PERF.md section 6, PR 46)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import laguna

    config = _read("tests", "configs", "tiny-laguna.json") if tiny else _read("configs", "laguna-s-2.1.json")
    model = dict(config["model"], dtype="float32", param_dtype="float32", max_seq=128 if tiny else 4096,
                 n_routed_experts=8, first_expert=8)
    cfg = R.Context.model_config(type("C", (), {"model": model})())
    reference = blocks.reference(config)
    chunks, steps = ((16, 16, 4), 20) if tiny else ((1024, 1024, 256), 24)
    rng = arrivals.rng_for(seed, 7)
    toks = np.asarray(arrivals.token_ids(sum(chunks) + steps, model["vocab_size"], rng), np.int32)
    with jax.default_matmul_precision("highest"):
        params = laguna.init_params(cfg, jax.random.PRNGKey(seed % (2**31 - 1)))
        caches = laguna.init_caches(cfg, 2, cfg.max_seq)
        prefill = jax.jit(lambda p, t, c, o, n: laguna.prefill(p, cfg, t, c, jnp.int32(1), o, n), donate_argnums=(2,))
        decode = jax.jit(lambda p, t, c, lens, gate: laguna.decode(p, cfg, t, c, lens, gate), donate_argnums=(2,))
        off, got, n_prompt = 0, [], sum(chunks)
        for n in chunks:
            last, caches, _ = prefill(params, jnp.asarray(toks[None, off:off + n]), caches, jnp.int32(off), jnp.int32(n_prompt))
            off += n
        got.append(np.asarray(last))
        for at in range(n_prompt, n_prompt + steps - 1):
            logits, caches, _ = decode(params, jnp.asarray([0, toks[at]], jnp.int32), caches, jnp.asarray([0, at], jnp.int32),
                                       jnp.asarray([False, True]))
            got.append(np.asarray(logits)[1])
        want = np.asarray(jax.jit(lambda p, t: reference.forward(p, model, t, 128 if not tiny else 8,
                                                                 rows=(n_prompt - 1, steps)))(reference.plain_tree(params), jnp.asarray(toks)))
    err = np.abs(np.stack(got) - want)
    print(f"[float32] seed {seed} on {jax.devices()[0].device_kind}: {steps} rows of logits (std {want.std():.3f}) after {chunks} and through "
          f"{steps - 1} decode steps: largest difference {err.max():.6f}, rms {np.sqrt(np.mean(err ** 2)):.6f}; "
          f"by row {[round(float(e), 5) for e in err.max(axis=-1)]}", flush=True)
    return 0 if err.max() < 1e-3 else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control", "float32"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.what == "float32":
        return float32(args.seed, args.tiny)
    return control(args.seed, args.tiny, args.long)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # the engine's stepper is a daemon thread that may still hold the device
