"""A replay of `drivers/serve_closed_long.py`'s closed loop on the CPU, with no model: the
scheduler's plan (FCFS, one chunk an iteration beside one decode step of every slot that has
its prompt, a multi-step program while no prompt waits) over times read from the chip
(PERF.md §5, PR 28). It reads `serve_out_tok_s` and `tpot_ms_p90` as the readers do, for each
of the `pool` places in the cycle a run can start from, and their quartile spreads over those
places. It is how PERF.md §6 knows that the cell's spread was the seed's rotation, and that no
order of the cycle, ramp or window of this traffic brings it under half the bounds.

    python benchmark/tools/replay_longctx.py [--ramp S] [--seconds S] [--orders N] [--noise PCT]

`--orders N` replays N other orders of the cycle and prints the smallest spreads found;
`--noise PCT` replays the traffic file's own `phase` 24 times with that much noise an iteration
and a third of it a run. Nothing here is a device number: the times are constants.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
from lib import arrivals, stats  # noqa: E402

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "traffic", "longctx-closed16.json")
CHUNK_MS, CHUNK_MS_PER_KROW, SINGLE_STEP_MS, MULTI_STEP_MS, HOST_MS = 45.0, 7.0, 18.0, 10.0, 5.0


def replay(tr: dict, plens, outs, start: int, ramp: float, seconds: float, noise=None, speed: float = 1.0) -> tuple:
    """(serve_out_tok_s, tpot_ms_p90, requests sent in the window) of one run that enters the cycle at `start`."""
    pool, slots, t, end, i = tr["pool"], tr["slots"], 0.0, ramp + seconds, start
    waiting, prefilling, decoding, rows = [], [], [], []

    def send():
        nonlocal i
        waiting.append(dict(p=int(plens[i % pool]), m=int(outs[i % pool]), sent=t, pre=0, gen=0, first=None, last=None))
        i += 1

    for _ in range(tr["clients"]):
        send()
    while t < end and (waiting or prefilling or decoding):
        while waiting and len(prefilling) + len(decoding) < slots:
            prefilling.append(waiting.pop(0))
        if prefilling:
            r, n = prefilling[0], 1
            grant = min(r["p"] - r["pre"], 1024 if decoding else 2048)
            ms = ((CHUNK_MS + CHUNK_MS_PER_KROW * (r["pre"] + grant) / 1000.0) * max(grant, 256) / 1024.0
                  + (SINGLE_STEP_MS if decoding else 0.0) + HOST_MS)
        else:
            n = 1
            while n * 2 <= min(8, min(d["m"] - d["gen"] for d in decoding)):
                n *= 2
            ms = MULTI_STEP_MS * n + HOST_MS
        t += ms * speed * (1.0 + (noise() if noise is not None else 0.0)) / 1000.0
        done = []
        for d in decoding:
            d["gen"], d["last"] = d["gen"] + n, t
            if d["gen"] >= d["m"]:
                done.append(d)
        if prefilling:
            r["pre"] += grant
            if r["pre"] >= r["p"]:
                r.update(gen=1, first=t, last=t)
                decoding.append(prefilling.pop(0))
        for d in done:
            decoding.remove(d)
            rows.append(d)
            send()
    rows += decoding + prefilling + waiting
    tokens = sum(r["gen"] * max(0.0, min(r["last"], end) - max(r["first"], ramp)) / (r["last"] - r["first"])
                 for r in rows if r["first"] is not None and r["last"] > r["first"])
    sent = [r for r in rows if ramp <= r["sent"] < end]
    tpot = [(r["last"] - r["first"]) / (r["gen"] - 1) * 1e3 for r in sent if r["first"] is not None and r["gen"] >= 2]
    return tokens / seconds, stats.pctl(tpot, 0.9), len(sent)


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def over_starts(tr, plens, outs, ramp, seconds) -> tuple:
    runs = [replay(tr, plens, outs, k, ramp, seconds) for k in range(tr["pool"])]
    return runs, spread([r[0] for r in runs]), spread([r[1] for r in runs])


def under_noise(tr, plens, outs, ramp, seconds, pct: float, n: int = 24) -> tuple:
    """The two spreads over n runs from the traffic file's `phase`, each iteration's time off by pct% (one
    standard deviation) and each run's speed by a third of that."""
    runs = []
    for j in range(n):
        rng = np.random.default_rng(j)
        runs.append(replay(tr, plens, outs, tr["phase"], ramp, seconds, speed=1.0 + rng.normal() * pct / 300.0,
                           noise=lambda rng=rng: rng.normal() * pct / 100.0))
    return spread([r[0] for r in runs]), spread([r[1] for r in runs])


def cycle(tr: dict, order_seed: int) -> tuple:
    order = arrivals.rng_for(order_seed, 0)
    return arrivals.lengths(tr["prompt_len"], tr["pool"], order), arrivals.lengths(tr["max_tokens"], tr["pool"], order)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", default=TRAFFIC)
    ap.add_argument("--ramp", type=float)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--orders", type=int, default=0)
    ap.add_argument("--noise", type=float, default=0.0)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        tr = json.load(f)
    ramp = tr["ramp_seconds"] if args.ramp is None else args.ramp
    plens, outs = cycle(tr, tr["order_seed"])
    runs, s_tok, s_tpot = over_starts(tr, plens, outs, ramp, args.seconds)
    for k, (tok, tpot, n) in enumerate(runs):
        print(f"start {k:2d}: {tok:7.2f} tokens/s, tpot_ms_p90 {tpot:7.2f}, {n} requests sent in the window")
    print(f"over the {tr['pool']} starts: serve_out_tok_s median {statistics.median(r[0] for r in runs):.1f}, quartile spread "
          f"{s_tok:.1%}; tpot_ms_p90 median {statistics.median(r[1] for r in runs):.1f}, spread {s_tpot:.1%}")
    if args.orders:
        found = [over_starts(tr, *cycle(tr, s), ramp, args.seconds)[1:] for s in range(args.orders)]
        print(f"over {args.orders} other orders of the cycle: the smallest spreads are {min(f[0] for f in found):.1%} "
              f"and {min(f[1] for f in found):.1%}")
    if args.noise:
        s_tok, s_tpot = under_noise(tr, plens, outs, ramp, args.seconds, args.noise)
        print(f"start {tr['phase']} under {args.noise}% of noise an iteration, 24 runs: serve_out_tok_s spread {s_tok:.1%}, "
              f"tpot_ms_p90 spread {s_tpot:.1%}")


if __name__ == "__main__":
    main()
