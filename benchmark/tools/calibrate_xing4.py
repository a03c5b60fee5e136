"""Readings for `lib/reference_xing4.py`'s limits, taken by hand on the chip (PERF.md §6, PR 42):

    python benchmark/tools/calibrate_xing4.py control --seed <n> [--long]

The cell's server answers the run's 8 probes (and, with `--long`, two requests of the window's sizes).
Then every set of 128 generated positions goes through the comparison that decides `correct`, by the
harness's own code (`tools/calibrate_pangu_moe.py:held_to_the_limits`), three ways:

- sound: the server's ids, scored as the cell's driver scores them (`drivers/serve_closed_long.py:score_all`);
- control, precision: the ids of the reference with both operands of every matrix product but the
  router's rounded to float8 e4m3 (each tensor scaled), one precision below the bfloat16 the
  configuration states, on the same sequences; it has to come out `agrees=False`;
- control, mechanism: the ids of the reference in float32 with 1 Sinkhorn step where the configuration
  says 20 (a mixing matrix whose rows sum to 1 and whose columns do not yet). A reading and no verdict:
  on the chip it moves a logit by 0.38 to 0.46 rms, which is what bfloat16 moves the engine's by, and ids
  over 128 positions cannot tell the two apart (PERF.md section 6, PR 42); the CPU tests, on float32
  logits, fail it by thousands of times their limit, and on the chip the `mechanism` mode below gives it its verdict.

    python benchmark/tools/calibrate_xing4.py mechanism --seed <n>

The readings behind `MECHANISM_DEFICIT_TOL`, by the cell's own code (`drivers/serve_closed_mhc.py:check_mechanism`:
three layers served in float32 with float32 products, 8 probes, the ids scored over the tree served), three ways:
sound (has to agree); the server built with 1 Sinkhorn step and scored by the reference with the configuration's 20
(what a later change to the program would be); the server as configured and the reference with 1 step (ISSUE 42's
control). Both controls have to come out `agrees=False`; it exits 1 where one does not, or the sound run does not agree.

`--tiny` runs it on the CPU at the tests' widths, where the readings are shown and not judged.
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

CELL = "xing4.0-29b-a4b.serve-sessions-mhc48"


def context(seed: int, tiny: bool):
    """The cell's `Context` as `run.py` makes it, the traffic file's flags set."""
    if tiny:
        cell = _read("tests", "BENCHMARK.tiny-xing4.json")["workloads"][0]
        config, traffic = _read("tests", "configs", "tiny-xing4.json"), _read("tests", "traffic", "tiny-sessions-mhc.json")
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
    return ctx


def start(seed: int, tiny: bool):
    """(ctx, server, reference) as the cell's driver builds them."""
    from ray_tpu.llm import LLMServer

    ctx = context(seed, tiny)
    return ctx, LLMServer(serving.llm_config(ctx)), blocks.reference(ctx.config)


def logits_of(reference, model: dict, n_last: int, q_block: int, **how):
    """A program (tree, padded sequence, its length) -> the reference's logits [n_last, V] at the positions
    that choose the last `n_last` tokens, with `operand` or `sinkhorn_iters` as `reference.forward` takes them."""
    import jax

    def f(p, seq, n):
        with jax.default_matmul_precision("highest"):
            return reference.forward(p, model, seq, q_block, rows=(n - n_last - 1, n_last), **how)

    return jax.jit(f)


def control(seed: int, tiny: bool, long: bool) -> int:
    import jax.numpy as jnp

    ctx, server, reference = start(seed, tiny)
    vocab, probe, wc = ctx.model["vocab_size"], ctx.traffic["probe"], ctx.traffic["window_check"]
    as_expected = True

    def say(tag, what, r, expected: bool):
        nonlocal as_expected
        if expected is None:  # a reading with no verdict asked of it
            expected = r["agrees"]
        as_expected = as_expected and r["agrees"] == expected
        print(f"[control] seed {seed} {tag} {what}: mean deficit {r['mean']:.5f} (limit {reference.MEAN_DEFICIT_TOL}), at most "
              f"{r['most']:.4f}, {r.get('far', '?')} further under than {reference.NEAR_TIE_MARGIN} (limit {reference.FAR_SHARE_TOL} of a "
              f"sequence), ids differ at {r['differ']} of {r['compared']}; "
              f"agrees={r['agrees']}" + (f" by {' and '.join(r['failed'])}" if r["failed"] else "")
              + ("" if r["agrees"] == expected else f"  NOT AS EXPECTED ({expected})"), flush=True)

    def read(tag, seqs, n_last, lens, q_block, params):
        sound = loop.score_all(ctx, server, seqs, n_last, lens, q_block)
        d = sound["deficits"]
        per = n_last if n_last < 128 else 128  # a probe's positions, or a request's last 128: what `compare_scored` is given at a time
        say(tag, f"all {len(d)} positions sound (by 128: {[round(sum(d[k:k + 128]) / len(d[k:k + 128]), 5) for k in range(0, len(d), 128)]}; "
                 f"further under than 0.7 / 1.0 / 2.0: {[round(sum(v > m for v in d) / len(d), 3) for m in (0.7, 1.0, 2.0)]}; most of one sequence's "
                 f"{per}: {max(sum(v > reference.NEAR_TIE_MARGIN for v in d[k:k + per]) for k in range(0, len(d), per))})",
            dict(agrees=sound["agrees"], failed=[], mean=sound["mean_deficit"], most=max(d), differ=len(sound["parted"]),
                 compared=sound["compared"], far=sum(v > reference.NEAR_TIE_MARGIN for v in d)), True)
        expected_of = {"control (float8 e4m3 operands)": False, "control (1 Sinkhorn step)": None}
        programs = {"float32": logits_of(reference, ctx.model, n_last, q_block),
                    "control (float8 e4m3 operands)": logits_of(reference, ctx.model, n_last, q_block, operand=fp8),
                    "control (1 Sinkhorn step)": logits_of(reference, ctx.model, n_last, q_block, sinkhorn_iters=1)}
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
            for k in range(0, len(lf), 128):
                r = held_to_the_limits(reference, lf[k:k + 128], lc[k:k + 128].argmax(-1))
                top = lf[k:k + 128].max(-1) - lf[k:k + 128][np.arange(len(lf[k:k + 128])), lc[k:k + 128].argmax(-1)]
                r["far"] = int((top > reference.NEAR_TIE_MARGIN).sum())
                say(tag, f"positions {k}..{min(k + 128, len(lf))} {what}", r, expected_of[what])

    async def main():
        rng = arrivals.rng_for(seed, 7)
        prompts = [arrivals.token_ids(probe["prompt_len"], vocab, rng) for _ in range(reference.MAX_PROBES)]
        outs = await asyncio.gather(*[server.generate(p, max_tokens=probe["max_tokens"], temperature=0.0) for p in prompts])
        seqs, longs = [(p, o["token_ids"]) for p, o in zip(prompts, outs)], []
        if long:
            lp = [arrivals.token_ids(n, vocab, rng) for n in ((20, 60) if tiny else (1536, 6144))]
            lo = await asyncio.gather(*[server.generate(p, max_tokens=wc["n_last"], temperature=0.0) for p in lp])
            longs = [(p, o["token_ids"]) for p, o in zip(lp, lo)]
        await server.shutdown()
        params = reference.plain_tree(server.weights()[1])
        read("probes", seqs, probe["max_tokens"], [probe["prompt_len"] + probe["max_tokens"]], probe.get("q_block", 256), params)
        if longs:
            read("long", longs, wc["n_last"], wc["lens"], wc["q_block"], params)

    asyncio.run(main())
    print(f"[control] seed {seed}: every sound set agrees and every float8 control set does not: {as_expected}", flush=True)
    return 0 if as_expected or tiny else 1  # the limits are the cell's: at the tests' widths the readings are shown, not judged


def mechanism(seed: int, tiny: bool) -> int:
    from drivers import serve_closed_mhc as mhc

    ctx, as_expected = context(seed, tiny), True
    for what, sides, expected in (("sound", {}, True),
                                  ("control (the server with 1 Sinkhorn step)", {"served": {"hc_sinkhorn_iters": 1}}, False),
                                  ("control (the reference with 1 Sinkhorn step)", {"scored": {"hc_sinkhorn_iters": 1}}, False)):
        agrees, note, r = mhc.check_mechanism(ctx, **sides)
        d = sorted(r["deficits"])
        as_expected = as_expected and agrees == expected
        print(f"[mechanism] seed {seed} {what}: {note}; deficits' median {d[len(d) // 2]:.4f}, further under than 0.1 / 1.0: "
              f"{[sum(v > m for v in d) for m in (0.1, 1.0)]} of {len(d)}" + ("" if agrees == expected else f"  NOT AS EXPECTED ({expected})"),
              flush=True)
    print(f"[mechanism] seed {seed}: the sound run agrees and both controls do not: {as_expected}", flush=True)
    return 0 if as_expected or tiny else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control", "mechanism"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    return mechanism(args.seed, args.tiny) if args.what == "mechanism" else control(args.seed, args.tiny, args.long)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # the engine's stepper is a daemon thread that may still hold the device
