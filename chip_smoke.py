"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: a JaxTrainer worker takes gpt2-125m
                                     steps, then an HTTP /v1/completions replica answers
    python chip_smoke.py --chips 4   four chips, one process: a TP=4 engine against a
                                     TP=1 engine, a fsdp=2 x tp=2 train step against the
                                     single-device step. Nothing else runs.

The parent never imports jax. Each phase is a child process, run one after another,
so exactly one process holds the chip at a time. A phase that fails, finds no TPU or
falls back to a reference path makes the script exit non-zero and print no result.
The last line of a green run is the device the chip-holding process saw:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.

There is no CPU mode and no size option. The phases are functions of (model, sizes):
a scratch script can import this file and rehearse them at `test-tiny` on the CPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
SEED = 0
_RESULT = "PHASE_RESULT "
# Per-phase limits: a cold gpt2-125m compile of the train step and of one engine
# program per prefill bucket fits several times over; the two together stay inside
# the contract's 1200 s.
PHASE_TIMEOUT_S = {"train": 540, "serve": 540, "multichip": 1100}


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# ---------------------------------------------------------------- cluster facts


def _libtpu_pids() -> list[int]:
    """Descendants of this process that have libtpu mapped, i.e. that initialised
    the TPU backend. A process held to the CPU backend never maps it."""
    import psutil

    pids = []
    for p in psutil.Process().children(recursive=True):
        try:
            with open(f"/proc/{p.pid}/maps") as f:
                if "libtpu" in f.read():
                    pids.append(p.pid)
        except OSError:
            pass  # exited between the listing and the read
    return pids


def cluster_facts(platform: str) -> None:
    """What the driver can see of who holds the chip, checked: the driver has no JAX
    backend; one actor holds {"TPU": 1}; on a TPU host that actor's process is the
    only one of the cluster (raylet, GCS, controller, proxy, pooled workers) that has
    the TPU library loaded."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import xprof

    check(not xprof.backend_initialized(), "driver has no JAX backend after ray_tpu.init()")
    with open("/proc/self/maps") as f:
        check("libtpu" not in f.read(), "driver has not loaded libtpu")
    stats = global_worker().raylet_call("node_stats")
    holders = [h for h in stats["resource_holders"] if h["acquired"].get("TPU")]
    check(len(holders) == 1 and holders[0]["acquired"]["TPU"] == 1.0
          and holders[0]["kind"] == "actor",
          f"exactly one actor holds {{'TPU': 1}}: {holders}")
    check(ray_tpu.available_resources().get("TPU", 0) == 0
          and ray_tpu.cluster_resources().get("TPU") == 1.0,
          "the cluster's resource view shows the one chip taken")
    store = stats["store"].get("backend", "python")
    check(store == "native", f"object store is the native one built from shmstore.cpp ({store})")
    if platform == "tpu":
        tpu_pids = _libtpu_pids()
        check(tpu_pids == [holders[0]["pid"]],
              f"only the TPU actor's process (pid {holders[0]['pid']}) loaded libtpu: {tpu_pids}")


# ---------------------------------------------------------------- train phase


def _train_loop(config):
    """Runs in the JaxTrainer worker, the one process of this phase with the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if dev.platform != config["platform"]:
        raise RuntimeError(f"train worker sees {device}, not a {config['platform']} device")
    batch, seq = config["batch"], config["seq"]
    cfg = get_config(config["model"], remat=False, max_seq=seq, attention="flash")
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 1})
    optimizer = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    state, _ = init_state(model, cfg, optimizer, mesh, sample_shape=(batch, seq),
                          rng=jax.random.PRNGKey(config["seed"]))
    step_fn, shardings = build_train_step(model, optimizer, mesh, with_grad_norm=False)
    tokens = jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 1), (batch, seq + 1), 0, cfg.vocab_size)
    data = {"tokens": jax.device_put(tokens[:, :-1], shardings["tokens"]),
            "targets": jax.device_put(tokens[:, 1:], shardings["targets"])}
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    with mesh:
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, data).compile()
        compile_s = time.perf_counter() - t0
        kernel_calls = compiled.as_text().count("tpu_custom_call")
        losses, step_s = [], []
        for _ in range(config["steps"]):
            t0 = time.perf_counter()
            state, metrics = compiled(state, data)
            losses.append(float(metrics["loss"]))  # the host needs the value: it syncs
            step_s.append(time.perf_counter() - t0)
    train.report({
        "device": device, "losses": losses, "kernel_calls": kernel_calls,
        "init_s": init_s, "compile_s": compile_s, "step_s": step_s,
        "params_m": cfg.num_params() / 1e6,
        "widths": {"hidden": cfg.hidden, "n_layers": cfg.n_layers, "vocab_size": cfg.vocab_size},
        "peak_bytes": (dev.memory_stats() or {}).get("peak_bytes_in_use"),
    })


def train_phase(model="gpt2-125m", batch=8, seq=1024, steps=6, platform="tpu") -> dict:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as storage:
            trainer = JaxTrainer(
                _train_loop,
                train_loop_config={"model": model, "batch": batch, "seq": seq, "steps": steps,
                                   "seed": SEED, "platform": platform},
                scaling_config=ScalingConfig(num_workers=1, use_tpu=True, chips_per_host=1),
                run_config=RunConfig(name="chip_smoke", storage_path=storage),
            )
            facts, done = {}, threading.Event()

            def watch():
                """The worker holds the chip only while fit() runs: take the cluster's
                facts as soon as it has the TPU resource and (on a TPU) the library."""
                from ray_tpu._private.worker import global_worker

                while not done.wait(0.5):
                    holders = [h for h in global_worker().raylet_call("node_stats")["resource_holders"]
                               if h["acquired"].get("TPU") and h["kind"] == "actor"]
                    if holders and (platform != "tpu" or _libtpu_pids()):
                        try:
                            cluster_facts(platform)
                            facts["taken"] = True
                        except Exception as e:  # noqa: BLE001 - reported by the check below
                            facts["error"] = repr(e)
                        return

            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()
            try:
                result = trainer.fit()
            finally:
                done.set()
            watcher.join(timeout=60)
        check(result.error is None, f"JaxTrainer.fit() finished without error ({result.error})")
        m = result.metrics
        print(f"  train: {m['widths']} {m['params_m']:.1f}M params, batch {batch} x seq {seq}, "
              f"init {m['init_s']:.1f}s compile {m['compile_s']:.1f}s "
              f"steps {[round(s, 3) for s in m['step_s']]} s, losses "
              f"{[round(x, 4) for x in m['losses']]}, peak HBM {m['peak_bytes']}", flush=True)
        check(m["device"]["platform"] == platform, f"train worker's device is {platform}: {m['device']}")
        check(all(math.isfinite(x) for x in m["losses"]), "every loss is finite")
        check(m["losses"][-1] < m["losses"][0], "loss fell over the steps")
        if platform == "tpu":
            check(m["kernel_calls"] >= 2,
                  f"the compiled step holds the Pallas kernels ({m['kernel_calls']} tpu_custom_call)")
        check(facts == {"taken": True},
              f"the cluster's facts were taken while the worker held the chip: {facts}")
        return {"device": m["device"]}
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------- serve phase


def _prompt(n_bytes: int, salt: int) -> str:
    """`n_bytes` printable ASCII bytes from the seed: one byte is one token."""
    import random

    rng = random.Random(SEED * 1000 + salt)
    return "".join(chr(rng.randrange(32, 127)) for _ in range(n_bytes))


def _post(port: int, payload: dict, timeout: float):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=json.dumps(payload).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.headers.get("Content-Type", ""), resp.read().decode()
    except urllib.error.HTTPError as e:  # the proxy's 500 carries the traceback
        raise SmokeFailure(f"POST /v1/completions -> {e.code}: {e.read().decode(errors='replace')[-3000:]}")


def serve_phase(model="gpt2-125m", widths=(768, 12, 50257), num_slots=8, max_seq=1024,
                prompt_lens=(128, 256, 512, 200), new_tokens=64, platform="tpu") -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import ByteTokenizer, LLMConfig, build_openai_app

    cold_s = PHASE_TIMEOUT_S["serve"] - 60  # a wait that fits a cold compile
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        t0 = time.perf_counter()
        app = build_openai_app([LLMConfig(
            model_id=model, num_slots=num_slots, max_seq=max_seq, seed=SEED,
            accelerator_resources={"TPU": 1})])
        serve.run(app, name="smoke", route_prefix="/", _timeout_s=cold_s)
        print(f"  serve: replica up in {time.perf_counter() - t0:.1f}s", flush=True)
        # The proxy binds and learns its routes on its own clock: wait, with a limit,
        # until it lists the model.
        port, listed, deadline = None, "", time.monotonic() + 90
        while time.monotonic() < deadline and model not in listed:
            port = port or serve.get_proxy_port()
            if port:
                try:
                    with urllib.request.urlopen(f"http://127.0.0.1:{port}/v1/models", timeout=30) as r:
                        listed = r.read().decode()
                except (urllib.error.URLError, OSError) as e:
                    listed = f"{e!r}"
            if model not in listed:
                time.sleep(0.5)
        check(port is not None and model in listed,
              f"the HTTP proxy is bound (:{port}) and lists {model} after "
              f"{time.perf_counter() - t0:.1f}s: {listed[:200]}")

        def complete(prompt, *, stream=False, timeout=cold_s):
            t = time.perf_counter()
            status, ctype, body = _post(
                port, {"model": model, "prompt": prompt, "max_tokens": new_tokens,
                       "stream": stream}, timeout)
            dt = time.perf_counter() - t
            check(status == 200, f"POST /v1/completions -> 200 ({len(prompt)} byte prompt, "
                                 f"stream={stream}, {dt:.2f}s)")
            if not stream:
                out = json.loads(body)
                check(out["usage"]["completion_tokens"] == new_tokens
                      and out["usage"]["prompt_tokens"] == len(prompt),
                      f"usage counts {len(prompt)} prompt + {new_tokens} new tokens")
                return out["choices"][0]["text"]
            check(ctype.startswith("text/event-stream"), "the stream is text/event-stream")
            events = [ln[6:] for ln in body.splitlines() if ln.startswith("data: ")]
            check(events[-1] == "[DONE]", "the stream ends with [DONE]")
            chunks = [json.loads(e) for e in events[:-1]]
            check(chunks[-1]["choices"][0]["finish_reason"] == "length",
                  "the stream finished by length")
            return "".join(c["choices"][0]["text"] for c in chunks)

        # "The same greedy output twice" is checked between two requests that take the
        # same prefill path. A request that hits the prefix cache prefills only its last
        # block, with another program than a cold one: in bfloat16 the two round
        # differently, and among 50257 near-flat logits of random weights the ids part
        # ways (PERF.md, PR 21). So a prompt is asked three times in a row: the second and
        # third both hit the cache, nothing else runs between them, and they must agree
        # to the id. Whether the first agrees too is printed, not required.
        a, b, c, d = (_prompt(n, i) for i, n in enumerate(prompt_lens))
        cold = complete(a)  # compiles the prefill bucket and the decode programs
        hit, again = complete(a), complete(a)
        check(hit == again, "greedy text for one prompt is identical on a second request")
        pair: dict = {}
        threads = [threading.Thread(target=lambda k=k, p=p: pair.update({k: complete(p)}))
                   for k, p in (("b", b), ("c", c))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(cold_s)
        check(set(pair) == {"b", "c"}, "two concurrent requests both answered")
        complete(d)
        streamed = complete(d, stream=True)
        check(streamed == complete(d), "streamed text equals its blocking twin's")

        # Token ids never cross HTTP (random weights mostly emit ids no byte renders),
        # so the identity check is repeated on ids through the deployment's handle.
        handle = serve.get_deployment_handle(f"LLMServer-{model}", "smoke")
        ids = [handle.generate.remote(a, max_tokens=new_tokens).result(timeout_s=cold_s)["token_ids"]
               for _ in range(3)]
        check(len(ids[1]) == new_tokens and ids[1] == ids[2],
              f"greedy token ids identical on a second request ({new_tokens} ids): "
              f"{ids[1:] if ids[1] != ids[2] else ''}")
        check(ByteTokenizer().decode(ids[1]) == hit, "the HTTP text is those ids, decoded")
        print(f"  serve: cold and cache-hit requests agree on the text: {cold == hit}, "
              f"on the ids: {ids[0] == ids[1]} (not required)", flush=True)

        stats = handle.scheduler_stats.remote().result(timeout_s=120)
        devs = stats["memory"]["devices"]
        check(bool(devs) and devs[0]["platform"] == platform,
              f"the replica's own report names a {platform} device: {devs[:1]}")
        served = stats["model"]
        check((served["hidden"], served["n_layers"], served["vocab_size"]) == tuple(widths)
              and served["num_slots"] == num_slots and served["max_seq"] == max_seq,
              f"the replica serves the asked widths, slots and max_seq: {served}")
        totals = stats["programs"]["totals"]
        peak = (devs[0].get("memory_stats") or {}).get("peak_bytes_in_use")
        print(f"  serve: {totals['programs']} programs, {totals['compiles_total']} compiles in "
              f"{totals['compile_s_total']:.1f}s, {totals['recompiles_total']} recompiles, "
              f"peak HBM {peak}", flush=True)
        cluster_facts(platform)
        return {"device": {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                           "count": len(devs)}}
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------- four-chip phase


def _spread(tree, n_devices: int, what: str) -> int:
    """Check that the big leaves of `tree` are split over `n_devices` devices, none
    whole on one; returns the most bytes any device holds."""
    import jax

    per_device: dict = {}
    whole = []
    leaves = jax.tree_util.tree_leaves(tree)
    for x in leaves:
        shards = x.addressable_shards
        for s in shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) + s.data.nbytes
        if x.nbytes >= 1 << 20 and (len({s.device.id for s in shards}) < n_devices
                                    or shards[0].data.nbytes * 2 > x.nbytes):
            whole.append((x.shape, str(x.sharding)))
    total = sum(x.nbytes for x in leaves)
    check(len(per_device) == n_devices and not whole,
          f"{what}: every leaf of 1 MiB or more is split over {n_devices} devices "
          f"(unsplit: {whole[:3]})")
    check(max(per_device.values()) < 0.6 * total,
          f"{what}: no device holds most of the {total / 2**20:.0f} MiB "
          f"(per device MiB: {sorted(v // 2**20 for v in per_device.values())})")
    return max(per_device.values())


def multichip_phase(model="llama3-1b", overrides=None, n_layers=2, n_devices=4, num_slots=4, max_seq=512,
                    prompt_lens=(64, 200, 330), new_tokens=32, batch=4, seq=1024, steps=3,
                    platform="tpu") -> dict:
    """One process, all four devices. (a) a TP=4 DecodeEngine against a TP=1 engine on
    the same seed; (b) a fsdp=2 x tp=2 train step against the single-device step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.llm._engine import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    check(device["platform"] == platform and device["count"] == n_devices,
          f"this process drives {n_devices} {platform} devices: {device}")
    overrides = overrides or {}  # a rehearsal's widths; the smoke passes none
    full = get_config(model, **overrides)
    print(f"  cut: {model} depth {full.n_layers} -> {n_layers} layers; widths kept (hidden "
          f"{full.hidden}, {full.n_heads} heads, {full.n_kv_heads} KV heads, mlp {full.mlp_dim}, "
          f"vocab {full.vocab_size}); engine max_seq {full.max_seq} -> {max_seq}", flush=True)
    for name, n in (("n_heads", full.n_heads), ("n_kv_heads", full.n_kv_heads),
                    ("mlp_dim", full.mlp_dim), ("vocab_size", full.vocab_size)):
        check(n % n_devices == 0, f"{name}={n} divides by {n_devices}: the rule shards it")

    # (a) Serving. Compute in true float32 for the comparison (float32 activations and
    # "highest" matmul precision: the TPU's default multiplies in bfloat16 whatever the
    # dtype, and one flipped rounding moves a logit by 1e-3 of its scale). The two
    # engines then differ only by the order of float32 sums, so equal greedy tokens are
    # not a coin toss at a near-tie among 128k logits. Widths and parameters unchanged.
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = get_config(model, **overrides, n_layers=n_layers, scan_layers=False, remat=False,
                     dtype=jnp.float32)
    params = Transformer(cfg).init(jax.random.PRNGKey(SEED), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in prompt_lens]

    def generate(engine, prompt):
        acc, done = [], threading.Event()

        def on_token(tok, fin):
            acc.append(tok)
            if fin:
                done.set()

        engine.submit(prompt, SamplingParams(max_tokens=new_tokens), on_token)
        check(done.wait(PHASE_TIMEOUT_S["multichip"] / 2), f"a {len(prompt)}-token request finished")
        return acc

    def probe(engine):
        """Next-token logits from the engine's own decode program, over the KV that
        generation left behind. The write gate is closed: nothing changes, but the program
        consumes the caches it is given, so the engine takes the returned ones (its stepper is idle)."""
        lens = np.full((engine.B,), min(prompt_lens), np.int32)
        last = np.arange(engine.B, dtype=np.int32) + 7
        _, logits, engine._caches, _, engine._sample_key = engine._jit_decode(
            engine.params, engine._lora_tables(), jnp.asarray(engine._adapter_ids),
            jnp.asarray(last), engine._caches, jnp.asarray(lens),
            jnp.zeros((engine.B,), bool), engine._temps_dev, engine._sample_key)
        return np.asarray(logits, np.float32)

    out = {}
    for tp in (1, n_devices):
        t0 = time.perf_counter()
        engine = DecodeEngine(cfg, params, num_slots=num_slots, max_seq=max_seq, seed=SEED, tp=tp)
        try:
            if tp > 1:
                _spread(engine.params, n_devices, f"TP={tp} engine parameters")
                _spread(engine._caches, n_devices, f"TP={tp} engine KV pool")
            toks = [generate(engine, p) for p in prompts]
            out[tp] = (toks, probe(engine))
            if tp > 1:
                text = engine._jit_decode.lower(
                    engine.params, engine._lora_tables(), jnp.asarray(engine._adapter_ids),
                    jnp.asarray(engine._last_token), engine._caches, jnp.asarray(engine._lens),
                    jnp.zeros((engine.B,), bool), engine._temps_dev, engine._sample_key).compile().as_text()
                check("all-reduce" in text, "the TP decode program holds an all-reduce")
        finally:
            engine.shutdown()
        print(f"  serve TP={tp}: {len(prompts)} requests x {new_tokens} tokens in "
              f"{time.perf_counter() - t0:.1f}s (compiles included)", flush=True)
    (toks1, logits1), (toksn, logitsn) = out[1], out[n_devices]
    check(toks1 == toksn, f"greedy tokens equal, TP={n_devices} against TP=1")
    err = float(np.max(np.abs(logits1 - logitsn)))
    scale = float(np.max(np.abs(logits1)))
    # float32 sums in another order: 1e-4 of the largest logit is a wide margin.
    check(err <= 1e-4 * max(scale, 1.0), f"decode logits agree: max |diff| {err:.3g} at scale {scale:.3g}")
    del out, params

    # (b) Training, in the model's own bfloat16 and the default precision.
    jax.config.update("jax_default_matmul_precision", None)
    cfg = get_config(model, **overrides, n_layers=n_layers, remat=False, max_seq=seq,
                     attention="flash")
    net = Transformer(cfg)
    optimizer = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (batch, seq + 1), 0, cfg.vocab_size)
    losses = {}
    for name, axes, n in (("single", {"dp": 1}, 1), ("sharded", {"fsdp": 2, "tp": 2}, n_devices)):
        mesh = mesh_lib.create_mesh(axes, devices=devs[:n])
        state, _ = init_state(net, cfg, optimizer, mesh, sample_shape=(batch, seq),
                              rng=jax.random.PRNGKey(SEED))
        step_fn, shardings = build_train_step(net, optimizer, mesh, with_grad_norm=False)
        data = {"tokens": jax.device_put(tokens[:, :-1], shardings["tokens"]),
                "targets": jax.device_put(tokens[:, 1:], shardings["targets"])}
        with mesh:
            t0 = time.perf_counter()
            compiled = step_fn.lower(state, data).compile()
            text = compiled.as_text()
            if n > 1:
                _spread(state.params, n, "fsdp=2 x tp=2 parameters")
                check(any(op in text for op in ("all-reduce", "reduce-scatter", "all-gather")),
                      "the sharded step holds collectives")
            if platform == "tpu":
                check("tpu_custom_call" in text, f"the {name} step holds the Pallas kernel")
            losses[name] = []
            for _ in range(steps):
                state, metrics = compiled(state, data)
                losses[name].append(float(metrics["loss"]))
        print(f"  train {name} {axes}: losses {[round(x, 4) for x in losses[name]]} in "
              f"{time.perf_counter() - t0:.1f}s (compile included)", flush=True)
        del state, compiled
    # bfloat16 keeps 8 bits: 2**-8 per rounding, averaged over batch x seq targets.
    worst = max(abs(a - b) / abs(a) for a, b in zip(losses["single"], losses["sharded"]))
    check(worst <= 5e-3, f"sharded loss equals single-device loss for {steps} steps "
                         f"(worst relative diff {worst:.2e})")
    return {"device": device}


# ---------------------------------------------------------------- parent


def _run_phase(name: str) -> dict:
    """Run one phase as a child that owns the chip for its lifetime; its output is
    passed through, and its result line parsed."""
    t0 = time.perf_counter()
    print(f"== phase {name}", flush=True)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
        start_new_session=True)  # its own process group: a timeout kills the whole cluster
    killer = threading.Timer(PHASE_TIMEOUT_S[name], lambda: os.killpg(proc.pid, 9))
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(_RESULT):
                result = json.loads(line[len(_RESULT):])
            else:
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        killer.cancel()
        try:
            os.killpg(proc.pid, 9)  # whatever the phase left running
        except ProcessLookupError:
            pass
    if rc != 0 or result is None:
        raise SmokeFailure(f"phase {name} failed (exit code {rc}) after {time.perf_counter() - t0:.0f}s")
    result["wall_s"] = time.perf_counter() - t0
    print(f"== phase {name} passed in {result['wall_s']:.1f}s", flush=True)
    return result


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _dump_logs(since: float):
    """The machine is thrown away after the call: show the ends of this run's session
    logs and keep whole copies where the chip tool brings them back."""
    logs = sorted((p for p in glob.glob(os.path.join(
        tempfile.gettempdir(), "ray_tpu", "session_*", "logs", "*")) if os.path.getmtime(p) >= since),
        key=os.path.getmtime)
    dest = os.path.join(OUT_DIR, "chip_smoke_logs")
    os.makedirs(dest, exist_ok=True)
    for path in logs[-40:]:
        if not os.path.isfile(path):
            continue
        shutil.copy(path, os.path.join(dest, os.path.basename(path)))
        with open(path, errors="replace") as f:
            tail = f.read()[-1500:]
        if tail.strip():
            print(f"--- {path}\n{tail}", file=sys.stderr, flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--phase", choices=sorted(PHASE_TIMEOUT_S), help=argparse.SUPPRESS)
    args = parser.parse_args()

    from ray_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # children, and the workers they start, inherit it
    if args.phase:  # a child: owns the chip until it exits
        phase = {"train": train_phase, "serve": serve_phase, "multichip": multichip_phase}[args.phase]
        print(_RESULT + json.dumps(phase()), flush=True)
        return 0

    t0, started = time.perf_counter(), time.time()
    entries = _cache_entries(cache_dir)
    print(f"chip_smoke: --chips {args.chips}, compile cache {cache_dir} ({entries} entries)", flush=True)
    try:
        results = [_run_phase(p) for p in (("train", "serve") if args.chips == 1 else ("multichip",))]
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        _dump_logs(started)
        return 1
    assert "jax" not in sys.modules, "the parent imported jax"
    devices = [r["device"] for r in results]
    if any(d != devices[0] for d in devices) or devices[0]["platform"] != "tpu" \
            or devices[0]["count"] != args.chips:
        print(f"chip_smoke: FAILED: devices {devices}", file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f}s; compile cache "
          f"{entries} -> {_cache_entries(cache_dir)} entries", flush=True)
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:  # a child's own failure: the parent reports and dumps logs
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
