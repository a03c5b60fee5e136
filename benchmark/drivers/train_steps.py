"""Driver `train_steps`: the SPMD train step, as `bench.py` calls it, on the mesh the
traffic file gives: `mesh` is an object of axis sizes as `parallel/mesh.create_mesh`
takes them (`{"fsdp": 4}`; absent: `{"dp": 1}`, one device), built over the cell's
chips. Where the product of the axes is not the cell's `chips` there is no run. `batch`
is the global count of sequences a step, placed with the shardings `build_train_step`
returns.

Set-up: `init_state` under jit from the seed, the plain reference's loss at every token
of the first batch (before the first step consumes the parameters), the program's own
forward pass on one sequence a chip, whose tokens' losses must agree with the
reference's, then one step, which compiles and whose loss must agree with the
reference's mean. Window: steps with the state threaded
through them, a new seeded batch each made by numpy while the device runs, the host
never more than `dispatch_ahead` steps in front, one sync at the end. The traced run
blocks every step so that each has a host time, and takes a profiler window at the end.
"""

from __future__ import annotations

import math
import time

import numpy as np


def _optimizer(spec: dict):
    import jax.numpy as jnp
    import optax

    if spec["name"] != "adamw":
        raise ValueError(f"train_steps knows the optimizer 'adamw', not {spec['name']!r}")
    return optax.adamw(spec["lr"], weight_decay=spec["weight_decay"],
                       mu_dtype=getattr(jnp, spec["mu_dtype"]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from lib import arrivals, blocks
    from ray_tpu.models.transformer import Transformer
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, eval_logits_fn, init_state

    tr = ctx.traffic
    batch, seq = tr["batch"], tr["seq"]
    chips, axes = ctx.cell["chips"], dict(tr.get("mesh") or {"dp": 1})
    if math.prod(axes.values()) != chips:
        raise SystemExit(f"the traffic's mesh {axes} is not the cell's {chips} chips")
    reference = blocks.reference(ctx.config)
    cfg = ctx.model_config(attention=tr["attention"])
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh(axes, devices=ctx.devices[:chips])
    optimizer = _optimizer(tr["optimizer"])
    setup = {}

    t = time.perf_counter()
    c0 = ctx.compiles.snapshot()
    state, _ = init_state(model, cfg, optimizer, mesh,
                          rng=jax.random.PRNGKey(ctx.seed % (2**31 - 1)),
                          sample_shape=(batch, seq))
    jax.block_until_ready(state.params)
    setup["weights_s"] = time.perf_counter() - t

    step_fn, shardings = build_train_step(model, optimizer, mesh, with_grad_norm=False)
    rng = arrivals.rng_for(ctx.seed, 1)

    def make_batch():
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            ids = rng.integers(0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)
            return {"tokens": jax.device_put(ids[:, :-1], shardings["tokens"]),
                    "targets": jax.device_put(ids[:, 1:], shardings["targets"])}

    # The reference's loss at every token of the first batch, with the state's own
    # parameters (sharded as the state holds them: the reference runs under jit on that
    # tree), before the first step donates them.
    t = time.perf_counter()
    first = make_batch()
    ref_fn = jax.jit(lambda p, x, y: reference.token_losses(p, ctx.model, x, y))
    ref_tokens = np.stack([np.asarray(ref_fn(state.params, first["tokens"][b], first["targets"][b]))
                           for b in range(batch)])
    ref_loss = float(ref_tokens.mean())
    setup["reference_s"] = time.perf_counter() - t

    # The program's own forward pass (`eval_logits_fn`, the module the step differentiates)
    # on one sequence a chip, token by token against the reference: a mean over a batch
    # hides a precision that a token's loss shows (`TOKEN_LOSS_RMS_TOL`).
    t = time.perf_counter()
    forward = eval_logits_fn(model)

    def own_token_losses(params, tokens, targets):
        logits = forward(params, tokens).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    with mesh:
        own_tokens = np.asarray(jax.jit(own_token_losses)(state.params, first["tokens"][:chips], first["targets"][:chips]))
    token_rms = float(np.sqrt(np.mean((own_tokens - ref_tokens[:chips]) ** 2)))
    setup["forward_s"] = time.perf_counter() - t

    t = time.perf_counter()
    with mesh:
        state, metrics = step_fn(state, first)
        step_loss = float(metrics["loss"])
    setup["first_step_s"] = time.perf_counter() - t
    diff = abs(step_loss - ref_loss)
    agrees = (bool(np.isfinite(step_loss)) and diff <= reference.LOSS_ABS_TOL
              and token_rms <= reference.TOKEN_LOSS_RMS_TOL)
    notes = [f"reference loss {ref_loss:.6f} step loss {step_loss:.6f} |diff| {diff:.2e} "
             f"(tolerance {reference.LOSS_ABS_TOL:.1e}); forward pass against the reference, rms of the "
             f"difference of a token's loss over {own_tokens.size} tokens {token_rms:.3e} "
             f"(tolerance {reference.TOKEN_LOSS_RMS_TOL:.1e}) agrees={agrees}"]

    # One more step outside the window: the first call of a donated program can leave
    # a second layout to settle; after it every step is the steady one.
    t = time.perf_counter()
    with mesh:
        state, metrics = step_fn(state, make_batch())
        float(metrics["loss"])
    setup["warmup_s"] = time.perf_counter() - t
    c1 = ctx.compiles.snapshot()
    setup["compile_s"] = c1["seconds"] - c0["seconds"]
    setup["programs"] = c1["programs"] - c0["programs"]

    ahead = int(tr["dispatch_ahead"])
    trace_s = float(tr["trace_seconds"]) if ctx.trace else 0.0
    losses, step_rows, done_at = [], [], []
    tracing, window_span = False, None
    setup_s = ctx.since_start()
    w0 = time.perf_counter()
    with mesh:
        while True:
            now = time.perf_counter() - w0
            if now >= ctx.seconds:
                break
            if ctx.trace and not tracing and now >= ctx.seconds - trace_s:
                jax.profiler.start_trace(ctx.trace_dir)
                # made after the profiler starts: an annotation made before it records nothing
                window_span = jax.profiler.TraceAnnotation("bench.window")
                window_span.__enter__()
                tracing = True
            t_step = time.perf_counter()
            data = make_batch()
            with jax.profiler.TraceAnnotation("bench.step"):
                state, metrics = step_fn(state, data)
            losses.append(metrics["loss"])
            with jax.profiler.TraceAnnotation("bench.wait"):
                if ctx.trace:
                    jax.block_until_ready(metrics["loss"])
                    step_rows.append({"ms": (time.perf_counter() - t_step) * 1e3, "traced": tracing})
                elif len(losses) > ahead:
                    jax.block_until_ready(losses[-1 - ahead])
                    done_at.append(time.perf_counter())
        jax.block_until_ready(losses[-1])
        window_s = time.perf_counter() - w0
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    c2 = ctx.compiles.snapshot()

    gaps = np.diff(done_at) * 1e3
    if len(gaps) > 20:  # a run that reads far off shows here whether all of it was slow or a part
        notes.append(f"ms between step completions: median {np.median(gaps):.2f} max {gaps.max():.2f} "
                     f"first 10 {gaps[:10].mean():.2f} last 10 {gaps[-10:].mean():.2f} "
                     f"over 1.2x median: {int((gaps > 1.2 * np.median(gaps)).sum())} of {len(gaps)}")
    host_losses = [float(x) for x in jax.device_get(losses)]
    bad = sum(1 for x in host_losses if not np.isfinite(x))
    return {
        "correct": agrees and bad == 0,
        "attempted": len(host_losses),
        "failed": bad,
        "setup_s": setup_s,
        "setup": setup,
        "window_s": window_s,
        "steps": len(host_losses),
        "tokens_per_step": batch * seq,
        "seq": seq,
        "mesh": axes,
        "step_rows": step_rows,
        "losses": host_losses,
        "window_compiles": c2["programs"] - c1["programs"],
        "notes": notes,
    }
