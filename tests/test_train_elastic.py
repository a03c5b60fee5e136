"""Elastic Train scaling: restarts resize the world to live cluster capacity.

Parity: reference python/ray/train/v2/_internal/execution/scaling_policy/ —
lost node -> continue at N-1 from checkpoint; capacity back -> scale up again.
"""

import time

import ray_tpu
from ray_tpu import train
from ray_tpu.train import DataParallelTrainer, FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train._internal.failure_policy import ElasticScalingPolicy

_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def test_elastic_policy_math():
    class _Fake:
        num_workers = 4

        @property
        def _resources_per_worker_not_none(self):
            return {"trainslot": 1.0}

    policy = ElasticScalingPolicy(_Fake(), min_workers=2)
    # First attempt always tries the configured size.
    assert policy.world_size_for_attempt(0) == 4

    import ray_tpu as rt

    real_nodes = rt.nodes

    def fake_nodes(avail_counts):
        return [
            {"alive": True, "resources_total": {"trainslot": float(c)}}
            for c in avail_counts
        ]

    try:
        # Capacity for 1 -> clamped up to min_workers.
        rt.nodes = lambda: fake_nodes([1])
        assert policy.world_size_for_attempt(1) == 2
        # Capacity for 3 -> shrink to 3.
        rt.nodes = lambda: fake_nodes([1, 1, 1])
        assert policy.world_size_for_attempt(1) == 3
        # Capacity restored -> re-expand to the configured size.
        rt.nodes = lambda: fake_nodes([2, 2])
        assert policy.world_size_for_attempt(2) == 4
        # Dead nodes don't count.
        rt.nodes = lambda: [
            {"alive": False, "resources_total": {"trainslot": 8.0}}
        ] + fake_nodes([1, 1])
        assert policy.world_size_for_attempt(1) == 2
    finally:
        rt.nodes = real_nodes


def test_elastic_shrinks_on_node_loss_then_reexpands(tmp_path):
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _ENV})
    cluster.add_node(num_cpus=1, resources={"trainslot": 1.0}, env_vars=_ENV)
    n2 = cluster.add_node(num_cpus=1, resources={"trainslot": 1.0}, env_vars=_ENV)
    cluster.connect()
    cluster.wait_for_nodes()
    try:
        marker_dir = str(tmp_path)

        def loop(config):
            import os

            ctx = train.get_context()
            world = ctx.get_world_size()
            rank = ctx.get_world_rank()
            open(os.path.join(config["markers"], f"started_{world}_{rank}"), "w").write("x")
            if world == 2:
                # Full-size attempt: park until the driver kills a node out
                # from under one of us (the recovery path under test).
                time.sleep(600)
            train.report({"world": world, "rank": rank})

        trainer = DataParallelTrainer(
            loop,
            train_loop_config={"markers": marker_dir},
            scaling_config=ScalingConfig(
                num_workers=2, min_workers=1,
                resources_per_worker={"trainslot": 1.0},
            ),
            run_config=RunConfig(
                name="elastic", storage_path=str(tmp_path / "storage"),
                failure_config=FailureConfig(max_failures=3),
            ),
        )

        import threading

        result_box = {}

        def fit():
            result_box["result"] = trainer.fit()

        t = threading.Thread(target=fit)
        t.start()
        # Wait for both full-size workers to start, then take a node down.
        import os

        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            started = [f for f in os.listdir(marker_dir) if f.startswith("started_2_")]
            if len(started) >= 2:
                break
            time.sleep(0.2)
        assert len([f for f in os.listdir(marker_dir) if f.startswith("started_2_")]) >= 2
        cluster.remove_node(n2)
        t.join(timeout=300)
        assert not t.is_alive(), "trainer did not finish after node loss"
        result = result_box["result"]
        assert result.error is None, result.error
        # The restarted attempt ran at the reduced world size.
        assert result.metrics["world"] == 1

        # Capacity returns: a new run expands back to the full size.
        cluster.add_node(num_cpus=1, resources={"trainslot": 1.0}, env_vars=_ENV)
        cluster.wait_for_nodes()

        def quick_loop(config):
            ctx = train.get_context()
            train.report({"world": ctx.get_world_size()})

        result2 = DataParallelTrainer(
            quick_loop,
            scaling_config=ScalingConfig(
                num_workers=2, min_workers=1,
                resources_per_worker={"trainslot": 1.0},
            ),
            run_config=RunConfig(name="elastic2",
                                 storage_path=str(tmp_path / "storage2")),
        ).fit()
        assert result2.error is None
        assert result2.metrics["world"] == 2
    finally:
        cluster.shutdown()
