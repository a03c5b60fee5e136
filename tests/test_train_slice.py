"""JaxTrainer on a fake multi-host TPU slice (CPU nodes with TPU resources).

Reference pattern: python/ray/train/v2/tests/test_jax_trainer.py:16-55 — simulate a TPU
slice by granting CPU nodes TPU/TPU-<pod>-head resources.
"""

import ray_tpu
from ray_tpu import train
from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig


def test_jax_trainer_on_fake_tpu_slice(ray_start_cluster):
    """Reference pattern (test_jax_trainer.py): fake TPU resources on CPU nodes."""
    cluster = ray_start_cluster
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    cluster.add_node(num_cpus=2, resources={"TPU": 4.0, "TPU-v4-16": 1.0,
                                            "TPU-v4-16-head": 1.0}, env_vars=env)
    cluster.add_node(num_cpus=2, resources={"TPU": 4.0, "TPU-v4-16": 1.0}, env_vars=env)
    cluster.connect()
    cluster.wait_for_nodes()

    def loop(config):
        ctx = train.get_context()
        train.report({"rank": ctx.get_world_rank(), "world": ctx.get_world_size()})

    result = JaxTrainer(
        loop,
        jax_config=train.JaxConfig(distributed=False),
        scaling_config=ScalingConfig(topology="v4-16"),
        # max_failures: a worker lost to spawn-storm load on the shared CI host
        # restarts the group from checkpoint — the recovery path under test.
        run_config=RunConfig(name="slice", storage_path="/tmp/rtpu_slice_test",
                             failure_config=FailureConfig(max_failures=2)),
    ).fit()
    assert result.metrics["world"] == 2
