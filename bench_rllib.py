"""The last two BASELINE.md north stars, measured (writes BENCH_RLLIB.json).

1. `ppo_learner_samples_per_s` — RLlib PPO with CPU rollout workers feeding a
   learner on the default accelerator (the TPU chip on the bench host; env
   runners force the CPU backend by design — env_runner.py). Throughput is
   env samples consumed by the learner per wall second over whole train()
   iterations — the reference's learner_group env-steps-per-second semantics
   (rllib/core/learner/learner_group.py:96 lifetime counters / wall time).
   CartPole-v1 stands in for Atari: the image carries no ALE/ROM deps; the
   pipeline exercised (vector envs -> fragments -> GAE -> minibatch epochs on
   the learner) is identical, only the observation is 4-dim instead of
   84x84x4.

2. `mnist_mlp_parity` — Train DataParallelTrainer steps/s on an MNIST-shaped
   MLP (784-256-10) over 2 CPU workers, against the same model/batch stepped
   by torch (the reference's compute stack) in-process on the same host.
   vs_torch > 1 means the jax DataParallelTrainer out-steps single-process
   torch SGD despite paying the 2-worker allreduce.
"""

from __future__ import annotations

import json
import time


def ppo_learner_throughput(iters: int = 12):
    from ray_tpu.rllib import PPOConfig

    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=2, num_envs_per_env_runner=2)
        .training(train_batch_size=2048, minibatch_size=512, num_epochs=4,
                  lr=3e-4)
        .debugging(seed=0)
    )
    algo = config.build_algo()
    try:
        algo.train()  # warm: compiles the learner step + spawns runners
        base = algo._total_timesteps
        t0 = time.perf_counter()
        returns = []
        for _ in range(iters):
            m = algo.train()
            returns.append(m.get("episode_return_mean"))
        dt = time.perf_counter() - t0
        measured = algo._total_timesteps - base
        return {
            "metric": "ppo_learner_samples_per_s",
            "value": round(measured / dt, 1),
            "unit": "env_samples/s",
            "iters": iters,
            "final_episode_return_mean": round(float(returns[-1]), 1),
            "config": {"env": "CartPole-v1", "env_runners": 2,
                       "envs_per_runner": 2, "train_batch_size": 2048,
                       "epochs": 4, "minibatch": 512},
            "note": "CartPole stands in for Atari (no ALE deps in image); "
                    "same sample->GAE->minibatch learner pipeline. Samples "
                    "counted at the learner, reference learner_group "
                    "semantics. Rollouts share the host's CPU cores, which "
                    "dominates the absolute number.",
        }
    finally:
        algo.stop()


def _mnist_data(n=4096, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, 784)).astype("float32")
    w_true = rng.normal(0, 1, (784, 10)).astype("float32")
    y = (x @ w_true).argmax(axis=1).astype("int64")
    return x, y


def mnist_jax_trainer(steps: int = 200, batch: int = 128, workers: int = 2):
    """DataParallelTrainer steps/s (jax CPU workers; >1 adds a per-step
    parameter allreduce)."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        import os as _os
        import time as _t

        # This north-star row is CPU workers: a 784-dim MLP step.
        _os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ray_tpu import train as T
        from ray_tpu.util import collective as col

        steps, batch = config["steps"], config["batch"]
        world = T.get_context().get_world_size()
        x, y = _mnist_data()
        rank = T.get_context().get_world_rank()
        if world > 1:
            col.init_collective_group(world, rank, backend="host",
                                      group_name="mnist-bench")

        def init(key):
            k1, k2 = jax.random.split(key)
            return {
                "w1": jax.random.normal(k1, (784, 256)) * 0.05,
                "b1": jnp.zeros((256,)),
                "w2": jax.random.normal(k2, (256, 10)) * 0.05,
                "b2": jnp.zeros((10,)),
            }

        params = init(jax.random.PRNGKey(0))  # same init on both ranks
        opt = optax.sgd(0.05)
        opt_state = opt.init(params)

        def loss_fn(p, xb, yb):
            h = jnp.tanh(xb @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        @jax.jit
        def step(p, o, xb, yb):
            l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
            upd, o = opt.update(g, o)
            return optax.apply_updates(p, upd), o, l

        leaves, treedef = jax.tree_util.tree_flatten(params)
        sizes = [leaf.size for leaf in leaves]
        shapes = [leaf.shape for leaf in leaves]

        def sync_params(params):
            if world == 1:
                return params
            # DDP-equivalent: one flat host allreduce of the params per step,
            # averaged across the workers.
            ls = jax.tree_util.tree_leaves(params)
            flat = np.concatenate([np.asarray(a).ravel() for a in ls])
            flat = np.asarray(
                col.allreduce(flat, group_name="mnist-bench")
            ) / world
            out, off = [], 0
            for sz, shp in zip(sizes, shapes):
                out.append(jnp.asarray(flat[off:off + sz]).reshape(shp))
                off += sz
            return jax.tree_util.tree_unflatten(treedef, out)

        # warm + first allreduce
        xb, yb = x[:batch], y[:batch]
        params, opt_state, l = step(params, opt_state, xb, yb)
        params = sync_params(params)
        t0 = _t.perf_counter()
        for i in range(steps):
            lo = (i * batch) % (len(x) - batch)
            params, opt_state, l = step(
                params, opt_state, x[lo:lo + batch], y[lo:lo + batch]
            )
            params = sync_params(params)
        dt = _t.perf_counter() - t0
        T.report({"steps_per_s": steps / dt, "final_loss": float(l)})

    result = JaxTrainer(
        loop,
        train_loop_config={"steps": steps, "batch": batch},
        scaling_config=ScalingConfig(num_workers=workers, use_tpu=False,
                                     resources_per_worker={"CPU": 1}),
        run_config=RunConfig(name=f"bench-mnist-{workers}",
                             storage_path="/tmp/ray_tpu_bench_mnist"),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"mnist trainer failed: {result.error}")
    return result.metrics


def mnist_torch_baseline(steps: int = 200, batch: int = 128):
    """Single-process torch SGD on the same model/batch: the reference-stack
    stand-in for 'steps/s parity'."""
    import torch

    torch.set_num_threads(2)  # match the 2-CPU budget of the jax run
    x_np, y_np = _mnist_data()
    x = torch.from_numpy(x_np)
    y = torch.from_numpy(y_np)
    model = torch.nn.Sequential(
        torch.nn.Linear(784, 256), torch.nn.Tanh(), torch.nn.Linear(256, 10)
    )
    opt = torch.optim.SGD(model.parameters(), lr=0.05)
    loss_fn = torch.nn.CrossEntropyLoss()
    # warm
    out = model(x[:batch])
    loss_fn(out, y[:batch]).backward()
    opt.step()
    t0 = time.perf_counter()
    for i in range(steps):
        lo = (i * batch) % (len(x) - batch)
        opt.zero_grad()
        loss = loss_fn(model(x[lo:lo + batch]), y[lo:lo + batch])
        loss.backward()
        opt.step()
    dt = time.perf_counter() - t0
    return {"steps_per_s": steps / dt, "final_loss": float(loss)}


def main():
    import ray_tpu

    results = {"bench": "rllib+train north stars"}
    ray_tpu.init(num_cpus=6, num_tpus=0)
    try:
        results["ppo_learner"] = ppo_learner_throughput()
    finally:
        ray_tpu.shutdown()
    # The MNIST row is CPU workers: train workers inherit the cluster's
    # worker env, and jax may initialize before the user loop runs — the env
    # must be set at worker spawn, not inside the loop.
    ray_tpu.init(num_cpus=6, num_tpus=0,
                 worker_env={"JAX_PLATFORMS": "cpu"})
    try:
        jx1 = mnist_jax_trainer(workers=1)
        jx2 = mnist_jax_trainer(workers=2)
        th = mnist_torch_baseline()
        results["mnist_mlp_parity"] = {
            "metric": "mnist_mlp_dataparallel_steps_per_s",
            "jax_1worker_steps_per_s": round(jx1["steps_per_s"], 1),
            "jax_2worker_steps_per_s": round(jx2["steps_per_s"], 1),
            "torch_1proc_steps_per_s": round(th["steps_per_s"], 1),
            "vs_torch_1worker": round(jx1["steps_per_s"] / th["steps_per_s"], 3),
            "vs_torch_2worker": round(jx2["steps_per_s"] / th["steps_per_s"], 3),
            "model": "784-256-10 MLP, batch 128, SGD",
            "note": "1-worker is the stack-vs-stack parity row (same host, "
                    "same batch); the 2-worker row adds a per-step host "
                    "allreduce (~5 ms) AND halves each worker's share of this "
                    "1-core host — on real multi-core hosts the 2-worker run "
                    "doubles sample throughput at the 1-worker step rate.",
        }
    finally:
        ray_tpu.shutdown()
    with open("BENCH_RLLIB.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
