"""Component-level profile of the flagship single-chip train step.

Decomposes bench.py's gpt2-125m step (batch 8, seq 1024, bf16, flash
attention) into its pipeline stages and measures each in isolation on the
real chip, so docs/perf.md can account for every millisecond between the
MXU-peak floor and the measured step.

Methodology: each component body is repeated N times inside ONE jitted
lax.scan (true data dependence through the carry) and the call syncs on a
scalar device_get — per-call dispatch latency (enough to swamp a 1 ms kernel
measured call-by-call) is paid once per N, not once per iteration. bench.py's
own number uses host-side chaining; the two agree at step granularity.

Usage: python bench_profile.py [component ...]
Components: step grad fwd opt attn attnbwd mlp head embed
"""

from __future__ import annotations

import functools
import json
import sys
import time


def scan_time(body, init, *, iters=16, warm=1, reps=3):
    """Per-iteration time of `body` via TWO-POINT scan timing.

    body: carry -> carry (pure). Runs jit(scan(body)) at two lengths (iters
    and 4*iters) and reports (t_long - t_short) / (3*iters): the fixed
    per-call cost — dispatch, the sync round-trip, argument refresh —
    cancels in the subtraction. Syncs via device_get of a scalar folded from
    the carry. Not measured in this round: a profiler trace is the instrument
    for kernel times (ROADMAP S1, S7).
    """
    import jax
    import jax.numpy as jnp

    def make(length):
        @jax.jit
        def run(init):
            def step(carry, _):
                return body(carry), ()

            final, _ = jax.lax.scan(step, init, None, length=length)
            # Fold ONE element of EVERY leaf into the sync scalar: anything
            # less and XLA dead-code-eliminates the parts of the chain that
            # don't reach the scalar (a step counter as first leaf once made
            # the whole train chain disappear and "measure" 0 ms).
            return sum(
                jnp.sum(leaf.astype(jnp.float32).ravel()[:1])
                for leaf in jax.tree_util.tree_leaves(final)
            )

        return run

    short, long_ = make(iters), make(4 * iters)
    for _ in range(warm):
        _ = float(short(init))
        _ = float(long_(init))
    pers = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _ = float(short(init))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        _ = float(long_(init))
        t_long = time.perf_counter() - t0
        pers.append((t_long - t_short) / (3 * iters))
    pers.sort()
    return max(pers[len(pers) // 2], 1e-9)  # median: robust to host-load spikes


def dispatch_overhead():
    """One near-empty jitted call, synced: the per-call floor."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny(x):
        return x + 1.0

    x = jnp.zeros(())
    _ = float(tiny(x))
    t0 = time.perf_counter()
    for _ in range(5):
        x = tiny(x)
    _ = float(x)
    return (time.perf_counter() - t0) / 5


def build():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    on_tpu = jax.default_backend() == "tpu"
    batch, seq = (8, 1024) if on_tpu else (2, 128)
    cfg = get_config("gpt2-125m", remat=False, max_seq=seq,
                     attention="flash" if on_tpu else "reference")
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 1})
    opt = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    state, _ = init_state(model, cfg, opt, mesh, sample_shape=(batch, seq))
    step_fn, shard = build_train_step(model, opt, mesh, with_grad_norm=False,
                                      donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                                cfg.vocab_size)
    data = {"tokens": jax.device_put(tokens, shard["tokens"]),
            "targets": jax.device_put(tokens, shard["targets"])}
    return model, cfg, opt, mesh, state, step_fn, data, batch, seq


def main():
    import jax
    import jax.numpy as jnp
    import optax

    want = set(sys.argv[1:]) or {
        "step", "grad", "fwd", "opt", "attn", "attnbwd", "mlp", "head", "embed"
    }
    model, cfg, opt, mesh, state, step_fn, data, B, S = build()
    H, E, D = cfg.n_heads, cfg.hidden, cfg.head_dim
    res = {"batch": B, "seq": S}
    res["dispatch_ms"] = 1e3 * dispatch_overhead()

    from ray_tpu.models.transformer import cross_entropy_loss

    def loss_of(params):
        logits = model.apply({"params": params}, data["tokens"])
        return cross_entropy_loss(logits, data["targets"])

    with mesh:
        if "step" in want:
            res["full_step_ms"] = 1e3 * scan_time(
                lambda st: step_fn(st, data)[0], state, iters=3)

        if "grad" in want:
            def grad_body(params):
                _, g = jax.value_and_grad(loss_of)(params)
                # Chain: params' = params + 0*g keeps true dependence without
                # drifting the values.
                return jax.tree.map(lambda p, gg: p + 0.0 * gg.astype(p.dtype),
                                    params, g)

            res["value_and_grad_ms"] = 1e3 * scan_time(
                grad_body, state.params, iters=8)

        if "fwd" in want:
            def loss_of_tokens(params, tokens):
                logits = model.apply({"params": params}, tokens)
                return cross_entropy_loss(logits, data["targets"])

            def fwd_body(carry):
                # Tokens must evolve with the carry or XLA hoists the whole
                # forward out of the scan as loop-invariant (measured 0.06 ms).
                tokens, acc = carry
                loss = loss_of_tokens(state.params, tokens)
                nxt = (tokens + loss.astype(jnp.int32) + 1) % cfg.vocab_size
                return nxt, acc + loss

            res["forward_loss_ms"] = 1e3 * scan_time(
                fwd_body, (data["tokens"], jnp.zeros(())), iters=6)

        if "opt" in want:
            _, grads = jax.jit(jax.value_and_grad(loss_of))(state.params)

            def opt_body(carry):
                params, opt_state = carry
                updates, new_opt = opt.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), new_opt

            res["optimizer_ms"] = 1e3 * scan_time(
                opt_body, (state.params, state.opt_state), iters=8)

        if "attn" in want or "attnbwd" in want:
            from ray_tpu.ops.attention import flash_attention
            k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
            q = jax.random.normal(k1, (B, S, H, D), jnp.bfloat16)
            k = jax.random.normal(k2, (B, S, H, D), jnp.bfloat16)
            v = jax.random.normal(k3, (B, S, H, D), jnp.bfloat16)

        if "attn" in want:
            def attn_body(q):
                return flash_attention(q, k, v, True)

            t = scan_time(attn_body, q, iters=24)
            res["attn_fwd_ms_x12"] = 12e3 * t
            attn_fwd_flops = 2 * 2 * B * H * S * S * D / 2  # causal half
            res["attn_fwd_tflops"] = attn_fwd_flops / t / 1e12

        if "attnbwd" in want:
            def attn_loss(q):
                return jnp.sum(flash_attention(q, k, v, True)
                               .astype(jnp.float32))

            def attnbwd_body(q):
                g = jax.grad(attn_loss)(q)
                return q + 0.0 * g.astype(q.dtype)

            t = scan_time(attnbwd_body, q, iters=16)
            res["attn_fwdbwd_ms_x12"] = 12e3 * t

        if "attnbhsd" in want:
            # Transpose-free layout: same kernel, operands already [B,H,S,D].
            from ray_tpu.ops.attention import flash_attention_bhsd

            k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
            qh = jax.random.normal(k1, (B, H, S, D), jnp.bfloat16)
            kh = jax.random.normal(k2, (B, H, S, D), jnp.bfloat16)
            vh = jax.random.normal(k3, (B, H, S, D), jnp.bfloat16)

            def bhsd_body(qh):
                return flash_attention_bhsd(qh, kh, vh, True)

            t = scan_time(bhsd_body, qh, iters=24)
            res["attnbhsd_fwd_ms_x12"] = 12e3 * t

            def bhsd_loss(qh):
                return jnp.sum(flash_attention_bhsd(qh, kh, vh, True)
                               .astype(jnp.float32))

            def bhsd_bwd_body(qh):
                g = jax.grad(bhsd_loss)(qh)
                return qh + 0.0 * g.astype(qh.dtype)

            t = scan_time(bhsd_bwd_body, qh, iters=16)
            res["attnbhsd_fwdbwd_ms_x12"] = 12e3 * t

        if "attnlib" in want:
            # The jax-shipped tuned TPU flash kernel (public pallas ops), as a
            # candidate replacement for ops/attention.py's custom kernel.
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as lib_fa,
            )
            import math as _math

            k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
            qh = jax.random.normal(k1, (B, H, S, D), jnp.bfloat16)
            kh = jax.random.normal(k2, (B, H, S, D), jnp.bfloat16)
            vh = jax.random.normal(k3, (B, H, S, D), jnp.bfloat16)
            sc = 1.0 / _math.sqrt(D)

            def lib_body(qh):
                return lib_fa(qh, kh, vh, causal=True, sm_scale=sc)

            t = scan_time(lib_body, qh, iters=24)
            res["attnlib_fwd_ms_x12"] = 12e3 * t
            res["attnlib_fwd_tflops"] = (2 * 2 * B * H * S * S * D / 2) / t / 1e12

            def lib_loss(qh):
                return jnp.sum(lib_fa(qh, kh, vh, causal=True, sm_scale=sc)
                               .astype(jnp.float32))

            def lib_bwd_body(qh):
                g = jax.grad(lib_loss)(qh)
                return qh + 0.0 * g.astype(qh.dtype)

            t = scan_time(lib_bwd_body, qh, iters=16)
            res["attnlib_fwdbwd_ms_x12"] = 12e3 * t

        if "mlp" in want:
            # The per-layer dense matmuls (q,k,v,o + gate,up,down) as one
            # chained program: achievable MXU efficiency at model shapes.
            x = jax.random.normal(jax.random.PRNGKey(2), (B * S, E), jnp.bfloat16)
            wq = jax.random.normal(jax.random.PRNGKey(3), (E, E), jnp.bfloat16)
            wg = jax.random.normal(jax.random.PRNGKey(4), (E, cfg.mlp_dim), jnp.bfloat16)
            wd = jax.random.normal(jax.random.PRNGKey(5), (cfg.mlp_dim, E), jnp.bfloat16)

            def mlp_body(x):
                mm = lambda a, b: jax.lax.dot(  # noqa: E731
                    a, b, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
                for _ in range(4):  # q k v o
                    x = mm(x, wq)
                g = mm(x, wg)
                u = mm(x, wg)
                return mm((g * u).astype(jnp.bfloat16), wd)

            t = scan_time(mlp_body, x, iters=24)
            flops = 2 * B * S * (4 * E * E + 3 * E * cfg.mlp_dim)
            res["dense_matmuls_ms_x12"] = 12e3 * t
            res["dense_matmul_tflops"] = flops / t / 1e12

        if "head" in want:
            hidden0 = jax.random.normal(jax.random.PRNGKey(6), (B, S, E),
                                        jnp.bfloat16)
            table0 = jax.random.normal(jax.random.PRNGKey(7),
                                       (cfg.vocab_size, E), jnp.float32)

            def head_loss(hidden, table):
                logits = jax.lax.dot_general(
                    hidden, table.astype(jnp.bfloat16),
                    (((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return cross_entropy_loss(logits, data["targets"])

            def head_body(carry):
                hidden, table = carry
                gh, gt = jax.grad(head_loss, argnums=(0, 1))(hidden, table)
                return hidden + 0.0 * gh.astype(hidden.dtype), \
                    table + 0.0 * gt.astype(table.dtype)

            res["head_ce_fwdbwd_ms"] = 1e3 * scan_time(
                head_body, (hidden0, table0), iters=8)

        if "embed" in want:
            table0 = jax.random.normal(jax.random.PRNGKey(8),
                                       (cfg.vocab_size, E), jnp.float32)

            def embed_body(carry):
                table, acc = carry
                x = table[data["tokens"]].astype(jnp.bfloat16)
                return table, acc + jnp.sum(x.astype(jnp.float32))

            res["embed_gather_ms"] = 1e3 * scan_time(
                embed_body, (table0, jnp.zeros(())), iters=16)

    # Roofline context.
    import bench
    peak = bench.peak_flops_per_chip()
    n_params = cfg.num_params()
    attn_flops = 12 * cfg.n_layers * cfg.hidden * S
    step_flops = (6 * n_params + attn_flops) * B * S
    res["model_flops_per_step_T"] = round(step_flops / 1e12, 3)
    res["mxu_floor_ms"] = round(1e3 * step_flops / peak, 2)
    for k, v in list(res.items()):
        if isinstance(v, float):
            res[k] = round(v, 3)
    print(json.dumps(res, indent=1))

    # Artifact, same convention as BENCH_SERVE.json: environment metadata +
    # one row per measured component so docs/perf.md can link a committed
    # snapshot instead of a pasted blob.
    context_keys = ("batch", "seq", "model_flops_per_step_T", "mxu_floor_ms")
    rows = [
        {"component": k, "per_iteration_ms": v}
        for k, v in res.items()
        if k not in context_keys and not k.endswith("_tflops")
    ]
    for k, v in res.items():
        if k.endswith("_tflops"):
            base = k[: -len("_tflops")]
            for row in rows:
                if row["component"].startswith(base):
                    row["tflops"] = v
    out = {
        "bench": "train_step_profile",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0].device_kind),
        "context": {k: res[k] for k in context_keys if k in res},
        "methodology": (
            "two-point scan timing: each component repeated inside one "
            "jitted lax.scan at lengths N and 4N, per-iteration ms = "
            "(t_long - t_short) / 3N so the fixed per-call cost (dispatch, "
            "sync round-trip) cancels; median of 3 reps; synced via "
            "device_get of a scalar folded from every carry leaf"
        ),
        "results": rows,
    }
    with open("BENCH_PROFILE.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
