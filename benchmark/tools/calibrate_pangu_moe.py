"""Readings for `lib/reference_pangu_moe.py`'s limits, taken by hand on the chip (PERF.md §6, PR 39):

    python benchmark/tools/calibrate_pangu_moe.py control --seed <n> [--long] [--drop <norm> ...]

The cell's server answers the run's 8 probes (and, with `--long`, two requests of the window's
sizes). Then every set of 128 generated positions goes through the comparison that decides
`correct`, by the harness's own code, three ways:

- sound: the server's ids, scored as the cell's driver scores them (`drivers/serve_closed_long.py:score_all`);
- control: the ids of the reference with both operands of every matrix product but the router's
  rounded to float8 e4m3 (each tensor scaled), one precision below the bfloat16 the configuration
  states, on the same sequences; it has to come out `agrees=False`;
- fault, one for each `--drop` (probes only): the ids of the reference with that one of a layer's
  four norms left out, a wrong function at the cell's own widths; it has to come out `agrees=False`.

A control's ids are held to `reference.compare_scored` (no id further under the float32 reference's
largest logit than `NEAR_TIE_MARGIN`) and to the mean of those deficits (`MEAN_DEFICIT_TOL`), as
`score_all` holds the server's, and the line says which of the two it failed. `--tiny` runs it on
the CPU at the tests' widths.
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
from drivers import serve_closed_long as loop  # noqa: E402
from lib import arrivals, blocks, serving  # noqa: E402

CELL = "openpangu-ultra-moe-718b.serve-longctx-mla"
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def start(seed: int, tiny: bool):
    """(ctx, server, reference) as the cell's driver builds them."""
    if tiny:
        cell = _read("tests", "BENCHMARK.tiny-pangu.json")["workloads"][0]
        config, traffic = _read("tests", "configs", "tiny-pangu.json"), _read("tests", "traffic", "tiny-longctx-mla.json")
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


def logits_of(reference, model: dict, n_last: int, q_block: int, operand=None, drop=None):
    """A program (tree, padded sequence, its length) -> the reference's logits [n_last, V] at the
    positions that choose the last `n_last` tokens, with `operand` or `drop` as `reference.forward` takes them."""
    import jax

    def f(p, seq, n):
        with jax.default_matmul_precision("highest"):
            return reference.forward(p, model, seq, q_block, operand, rows=(n - n_last - 1, n_last), drop=drop)

    return jax.jit(f)


def held_to_the_limits(reference, lf: np.ndarray, ids: np.ndarray) -> dict:
    """`ids` [n] against the float32 reference's logits `lf` [n, V] under the two limits of the
    cell's comparison (`score_all`): `compare_scored`, and the mean of the deficits."""
    top2 = -np.sort(-lf, axis=-1)[:, :2]
    deficits = (top2[:, 0] - lf[np.arange(len(ids)), ids]).tolist()
    near, compared, parted = reference.compare_scored(lf.argmax(-1).tolist(), (top2[:, 0] - top2[:, 1]).tolist(), ids.tolist(), deficits)
    mean = sum(deficits) / len(deficits)
    failed = [name for name, ok in (("NEAR_TIE_MARGIN", near), ("MEAN_DEFICIT_TOL", mean <= reference.MEAN_DEFICIT_TOL)) if not ok]
    return dict(agrees=not failed, failed=failed, mean=mean, most=max(deficits), differ=len(parted), compared=compared)


def control(seed: int, tiny: bool, long: bool, drops: tuple) -> int:
    import jax.numpy as jnp

    ctx, server, reference = start(seed, tiny)
    vocab, probe, wc = ctx.model["vocab_size"], ctx.traffic["probe"], ctx.traffic["window_check"]
    as_expected = True

    def say(tag, what, r, expected: bool):
        nonlocal as_expected
        as_expected = as_expected and r["agrees"] == expected
        print(f"[control] seed {seed} {tag} {what}: mean deficit {r['mean']:.5f} (limit {reference.MEAN_DEFICIT_TOL}), at most "
              f"{r['most']:.4f} (limit {reference.NEAR_TIE_MARGIN}), ids differ at {r['differ']} of {r['compared']}; "
              f"agrees={r['agrees']}" + (f" by {' and '.join(r['failed'])}" if r["failed"] else "")
              + ("" if r["agrees"] == expected else f"  NOT AS EXPECTED ({expected})"), flush=True)

    def read(tag, seqs, n_last, lens, q_block, params, faults):
        sound = loop.score_all(ctx, server, seqs, n_last, lens, q_block)
        d = sound["deficits"]
        say(tag, f"all {len(d)} positions sound (by 128: {[round(sum(d[k:k + 128]) / len(d[k:k + 128]), 5) for k in range(0, len(d), 128)]})",
            dict(agrees=sound["agrees"], failed=[], mean=sound["mean_deficit"], most=max(d), differ=len(sound["parted"]),
                 compared=sound["compared"]), True)
        programs = {"float32": logits_of(reference, ctx.model, n_last, q_block),
                    "control (float8 e4m3 operands)": logits_of(reference, ctx.model, n_last, q_block, operand=fp8)}
        programs.update({f"fault (no {name})": logits_of(reference, ctx.model, n_last, q_block, drop=name) for name in faults})
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
                say(tag, f"positions {k}..{min(k + 128, len(lf))} {what}", held_to_the_limits(reference, lf[k:k + 128], lc[k:k + 128].argmax(-1)), False)

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
        read("probes", seqs, probe["max_tokens"], [probe["prompt_len"] + probe["max_tokens"]], probe.get("q_block", 256), params, drops)
        if longs:
            read("long", longs, wc["n_last"], wc["lens"], wc["q_block"], params, ())

    asyncio.run(main())
    print(f"[control] seed {seed}: every sound set agrees and every control and fault set does not: {as_expected}", flush=True)
    return 0 if as_expected or tiny else 1  # the limits are the cell's: at the tests' widths the readings are shown, not judged


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control",))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--drop", action="append", choices=NORMS, default=[])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    return control(args.seed, args.tiny, args.long, tuple(args.drop))


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # the engine's stepper is a daemon thread that may still hold the device
