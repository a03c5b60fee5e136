"""Llama-3-8B-shaped FSDP measurement (BASELINE.md north star
"Llama-3-8B FSDP MFU").

Two artifacts, written to BENCH_LLAMA8B.json:

1. `proxy_mfu` (runs on the real chip): a single v5e chip cannot hold the full
   8B train state, so the per-layer cost is measured directly — the exact 8B
   layer geometry (hidden 4096, mlp 14336, 32q/8kv heads, flash attention,
   remat policy "selective": save attention-side tensors, recompute the wide
   gate/up matmuls — ~100 MB/layer saved activations at b1/s2048, the
   memory/speed point that fits an fsdp=8 v5e pod) at depths 1 and 2. Per-layer
   step cost = t2 - t1; depth-independent cost (embed + fused-CE head, measured
   at a reduced vocab) scales linearly with vocab to 128256. Projected
   full-model step time = fixed*scale + 32*per_layer; MFU uses the true 8B
   parameter count. A secondary `upper_bound` row records the same measurement
   under dots_saveable (save every matmul output — faster, but its activation
   footprint only suits chips with more HBM headroom). Assumptions are
   recorded in the JSON.

2. `fsdp8_memory` (virtual 8-device mesh, subprocess): the FULL 8B config
   (32 layers, 128256 vocab) jitted over an fsdp=8 mesh and AOT-compiled —
   XLA's memory analysis certifies per-device residency (the dryrun path's
   memory-feasibility check, without needing 8 real chips or 80 GB of host
   RAM to materialize the state).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

LLAMA8B = dict(
    vocab_size=128256, hidden=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    mlp_dim=14336, max_seq=8192, tie_embeddings=False,
)


def true_param_count() -> int:
    h, mlp, v, L = 4096, 14336, 128256, 32
    head_dim = h // 32
    attn = h * (32 * head_dim) + 2 * h * (8 * head_dim) + (32 * head_dim) * h
    mlp_p = 3 * h * mlp
    norms = 2 * h
    return L * (attn + mlp_p + norms) + 2 * v * h + h  # embed + lm_head + final norm


def measure_step(n_layers: int, vocab: int, batch: int, seq: int, iters: int = 8,
                 remat_policy: str = "selective"):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import ModelConfig, Transformer
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    cfg = ModelConfig(
        vocab_size=vocab, hidden=4096, n_layers=n_layers, n_heads=32,
        n_kv_heads=8, mlp_dim=14336, max_seq=seq, remat=True,
        remat_policy=remat_policy, scan_layers=True,
        attention="flash" if jax.default_backend() == "tpu" else "reference",
    )
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 1})
    opt = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    state, _ = init_state(model, cfg, opt, mesh, sample_shape=(batch, seq))
    step_fn, shard = build_train_step(model, opt, mesh, with_grad_norm=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0, vocab)
    data = {"tokens": jax.device_put(tokens, shard["tokens"]),
            "targets": jax.device_put(tokens, shard["targets"])}
    with mesh:
        state, m = step_fn(state, data)
        _ = float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step_fn(state, data)
        _ = float(m["loss"])
        return (time.perf_counter() - t0) / iters


def _project(t1, t2, batch, seq, vocab):
    from bench import peak_flops_per_chip

    per_layer = max(t2 - t1, 1e-9)
    fixed = max(t1 - per_layer, 0.0)
    # The depth-independent cost is dominated by the fused-CE head (linear in
    # vocab); scale it from the measured vocab to the real one.
    fixed_full = fixed * (LLAMA8B["vocab_size"] / vocab)
    t_full = fixed_full + 32 * per_layer
    n_params = true_param_count()
    attn_flops = 12 * 32 * 4096 * seq  # per token, causal-averaged
    flops_per_token = 6 * n_params + attn_flops
    tokens_per_sec = batch * seq / t_full
    mfu = tokens_per_sec * flops_per_token / peak_flops_per_chip()
    return {
        "projected_step_s": round(t_full, 4),
        "projected_tokens_per_s": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "measured": {
            "t_1layer_s": round(t1, 4), "t_2layer_s": round(t2, 4),
            "per_layer_s": round(per_layer, 5), "fixed_s": round(fixed, 4),
            "batch": batch, "seq": seq, "proxy_vocab": vocab,
        },
    }


def proxy_mfu():
    import jax

    on_tpu = jax.default_backend() == "tpu"
    # Depths 1 and 2: a 4-layer probe (~1B params + f32 adam) overflows a
    # 16 GiB v5e; the 2-vs-1 delta isolates the same per-layer cost.
    batch, seq, vocab = (2, 2048, 16384) if on_tpu else (1, 128, 1024)
    n_params = true_param_count()
    rows = {}
    for name, policy, b in (("primary", "selective", batch),
                            ("batch1", "selective", 1),
                            ("upper_bound_dots", "dots", batch)):
        t1 = measure_step(1, vocab, b, seq, remat_policy=policy)
        t2 = measure_step(2, vocab, b, seq, remat_policy=policy)
        rows[name] = _project(t1, t2, b, seq, vocab)
        rows[name]["remat_policy"] = policy
    out = {
        "metric": "llama8b_proxy_mfu_per_chip",
        **rows["primary"],
        "rows": rows,
        "assumptions": [
            "exact 8B layer geometry; per-layer cost from 2-vs-1 layer delta",
            "depth-independent cost scaled linearly in vocab (fused-CE head)",
            f"true 8B param count {n_params:,} used for FLOPs",
            "primary row: remat_policy=selective (saves post-rope q/k/v, attn "
            "out, o/down projections, pre-MLP norm; recomputes the wide "
            "gate/up matmuls) — ~100 MB/layer saved activations at b1/s2048, "
            "sized for an fsdp=8 v5e pod; upper_bound_dots saves every matmul "
            "output (~330 MB/layer) and needs more HBM headroom per chip",
            "per-chip batch 2 (primary): at pod scale this is global batch 16 "
            "over fsdp=8",
        ],
    }
    return out


_FSDP8_CHILD = "_LLAMA8B_FSDP8_CHILD"


def fsdp8_memory():
    """AOT-compile the full 8B train step over an fsdp=8 virtual mesh."""
    if not os.environ.get(_FSDP8_CHILD):
        env = dict(os.environ)
        env[_FSDP8_CHILD] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "fsdp8"],
            env=env, capture_output=True, text=True, timeout=3600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            return {"metric": "llama8b_fsdp8_memory", "ok": False,
                    "error": proc.stderr[-800:]}
        return json.loads(proc.stdout.strip().splitlines()[-1])

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import ModelConfig, Transformer
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import (
        TrainState,
        build_train_step,
        state_shardings,
    )

    cfg = ModelConfig(remat=True, remat_policy="selective", scan_layers=True,
                      attention="reference", **LLAMA8B)
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"fsdp": 8})
    opt = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    batch, seq = 8, 4096
    shardings = state_shardings(model, cfg, opt, mesh, None, (batch, seq))
    # Abstract state: shapes/dtypes via eval_shape — nothing materializes.
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def make(rng):
        variables = model.init(rng, jnp.zeros((batch, seq), jnp.int32))
        params = mesh_lib.unbox(variables["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    state_avals = jax.eval_shape(make, jax.random.PRNGKey(0))
    state_avals = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state_avals, shardings,
    )
    step_fn, batch_shardings = build_train_step(model, opt, mesh,
                                                with_grad_norm=False)
    batch_avals = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                       sharding=batch_shardings["tokens"]),
        "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                        sharding=batch_shardings["targets"]),
    }
    with mesh:
        compiled = step_fn.lower(state_avals, batch_avals).compile()
    mem = compiled.memory_analysis()
    gib = 1 << 30
    out = {
        "metric": "llama8b_fsdp8_memory",
        "ok": True,
        "mesh": "fsdp=8",
        "batch": batch, "seq": seq,
        "per_device_gib": {
            "arguments": round(mem.argument_size_in_bytes / gib, 2),
            "outputs": round(mem.output_size_in_bytes / gib, 2),
            "temp_cpu_backend_upper_bound": round(
                mem.temp_size_in_bytes / gib, 2
            ),
        },
        # The real feasibility signal: the SHARDED train state (params f32 +
        # adam mu bf16/nu f32) resident per device. 10 GiB/chip of state
        # leaves ~6 GiB of a v5e for activations under remat.
        "sharded_state_fits_v5e_16gib": mem.argument_size_in_bytes < 16 * gib,
        "note": "AOT compile of the FULL 8B config over 8 virtual devices "
                "certifies the fsdp sharding end to end; `arguments` is the "
                "per-device resident train state. The temp figure is the CPU "
                "backend's buffer plan — an upper bound that lacks the TPU "
                "compiler's scheduling/fusion, not a TPU HBM prediction.",
    }
    print(json.dumps(out))
    return out


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "all"
    if mode == "fsdp8" and os.environ.get(_FSDP8_CHILD):
        fsdp8_memory()
        return
    results = {"bench": "llama8b"}
    if os.path.exists("BENCH_LLAMA8B.json"):
        # Partial reruns (proxy-only / fsdp8-only) merge over prior results.
        with open("BENCH_LLAMA8B.json") as f:
            results.update(json.load(f))
    import jax

    results["backend"] = jax.default_backend()
    results["device"] = str(jax.devices()[0].device_kind)
    if mode in ("all", "proxy"):
        results["proxy_mfu"] = proxy_mfu()
    if mode in ("all", "fsdp8"):
        results["fsdp8_memory"] = fsdp8_memory()
    with open("BENCH_LLAMA8B.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
