"""Benchmark: flagship-model training throughput on the available TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: gpt2-125m causal-LM training tokens/sec on one chip (bf16, flash attention,
adamw, remat off at this size). vs_baseline is measured model-FLOPs utilization (MFU)
divided by 0.40 — the MFU a tuned A100 torch/FSDP stack typically reaches on GPT-2-class
models (the reference framework's GPU training path; BASELINE.md north-star row
"FSDP->shard_map MFU vs A100 FSDP"). vs_baseline >= 1.0 means we match that bar.

Timing methodology: the train state is threaded through consecutive steps (step N+1
consumes step N's output), so the measured wall time covers real execution; a final
device_get syncs the chain.

A device measurement: it fails where JAX finds no TPU, and on a chip whose published
peak is not in `PEAK_BF16_FLOPS`. It has no CPU size.
"""

from __future__ import annotations

import json
import time


# Published bf16 peak per chip, keyed by `jax.devices()[0].device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM, 819 GB/s).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops_per_chip() -> float:
    """bf16 peak of the local chip; a device that is not in the table is an error."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX found {dev.platform!r} ({dev.device_kind})")
    if dev.device_kind not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"no published peak for device_kind {dev.device_kind!r}; add it to "
            "PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[dev.device_kind]


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    peak = peak_flops_per_chip()  # first: fails off-TPU before anything compiles
    batch, seq = 8, 1024
    cfg = get_config("gpt2-125m", remat=False, max_seq=seq, attention="flash")
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 1})  # single chip; dp>1 when more are visible
    # First-moment state in bf16 (mu_dtype): halves one optimizer-state stream's
    # HBM traffic; nu and params stay f32 (standard practice, e.g. T5X).
    optimizer = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)

    state, _ = init_state(model, cfg, optimizer, mesh, sample_shape=(batch, seq))
    step_fn, batch_shardings = build_train_step(
        model, optimizer, mesh, with_grad_norm=False
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab_size)
    data = {
        "tokens": jax.device_put(tokens, batch_shardings["tokens"]),
        "targets": jax.device_put(tokens, batch_shardings["targets"]),
    }

    with mesh:
        state, metrics = step_fn(state, data)  # compile + warm
        _ = float(metrics["loss"])
        iters = 20
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = step_fn(state, data)
        _ = float(metrics["loss"])  # sync the chain
        dt = (time.perf_counter() - t0) / iters

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step / dt
    n_params = cfg.num_params()
    # Training FLOPs/token ~= 6N (fwd 2N + bwd 4N); attention term added explicitly.
    attn_flops = 12 * cfg.n_layers * cfg.hidden * seq  # per token, causal-averaged
    flops_per_token = 6 * n_params + attn_flops
    mfu = tokens_per_sec * flops_per_token / peak
    vs_baseline = mfu / 0.40

    print(json.dumps({
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 3),
        "extra": {
            "mfu": round(mfu, 4),
            "step_ms": round(dt * 1e3, 2),
            "batch": batch,
            "seq": seq,
            "params_m": round(n_params / 1e6, 1),
            "device": {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind,
                       "count": len(jax.devices())},
        },
    }))


if __name__ == "__main__":
    main()
