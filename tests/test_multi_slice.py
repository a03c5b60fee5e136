"""Multi-slice (DCN) training: hybrid meshes, k-slice gang scheduling, JaxTrainer.

Reference precedent: `python/ray/_private/accelerators/tpu.py:482-547` multi-slice
gang scheduling; the hybrid mesh follows
`jax.experimental.mesh_utils.create_hybrid_device_mesh` semantics (DCN axes vary
across slice groups, ICI axes within a slice).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig


def test_hybrid_mesh_layout_and_collectives():
    """dcn_axes build a slice-major mesh: the dp axis crosses fake slices, the
    ici axes stay within one, and collectives over both are correct."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import mesh as mesh_lib
    from jax import shard_map

    m = mesh_lib.create_mesh({"fsdp": 2, "tp": 2}, dcn_axes={"dp": 2})
    assert m.shape["dp"] == 2 and m.shape["fsdp"] == 2 and m.shape["tp"] == 2
    ids = np.vectorize(lambda d: d.id)(m.devices).reshape(2, 2, 2)
    # slice 0 (devices 0-3) fills dp=0; slice 1 (4-7) fills dp=1
    assert set(ids[0].flatten().tolist()) == {0, 1, 2, 3}
    assert set(ids[1].flatten().tolist()) == {4, 5, 6, 7}

    f = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "dp"), mesh=m, in_specs=P("dp"), out_specs=P()
        )
    )
    np.testing.assert_allclose(np.asarray(f(jnp.arange(2.0))), [1.0])

    # -1 absorbs the per-slice remainder, not the global one.
    m2 = mesh_lib.create_mesh({"tp": -1}, dcn_axes={"dp": 2})
    assert m2.shape["tp"] == 4 and m2.shape["dp"] == 2


def test_hybrid_mesh_rejects_bad_factorings():
    from ray_tpu.parallel import mesh as mesh_lib

    with pytest.raises(ValueError):
        mesh_lib.create_mesh({"tp": 3}, dcn_axes={"dp": 2})  # 3 doesn't divide 4
    with pytest.raises(ValueError):
        mesh_lib.create_mesh({}, dcn_axes={"dp": 3})  # 8 devices % 3 != 0


def test_scaling_config_multi_slice_bundles():
    """k slices => k slice-head bundles, one per slice's host block."""
    sc = ScalingConfig(topology="v4-16", num_slices=2)
    assert sc.num_workers == 4  # 2 hosts/slice x 2 slices
    bundles = sc.bundles()
    heads = [i for i, b in enumerate(bundles) if "TPU-v4-16-head" in b]
    assert heads == [0, 2]
    with pytest.raises(ValueError):
        ScalingConfig(num_slices=2)  # needs a topology
    with pytest.raises(ValueError):
        # an explicit worker count that under-provisions the gang must not
        # silently reserve fewer slices
        ScalingConfig(topology="v4-16", num_slices=2, num_workers=2)


def test_jax_trainer_two_fake_slices_dp_across_dcn(ray_start_cluster):
    """Two fake single-host slices (distinct slice names): the gang spans both
    (one head bundle per slice) and the loop trains data-parallel across the
    DCN tier — per-slice grads allreduced via the host collective group, every
    slice ending with identical params."""
    cluster = ray_start_cluster
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    for name in ("sliceA", "sliceB"):
        cluster.add_node(
            num_cpus=2,
            resources={"TPU": 4.0, "TPU-v4-8": 1.0, "TPU-v4-8-head": 1.0,
                       f"TPU-{name}": 1.0},
            env_vars=env,
        )
    cluster.connect()
    assert cluster.wait_for_nodes()

    def loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu.parallel import mesh as mesh_lib
        from ray_tpu.util import collective

        ctx = train.get_context()
        rank, world = ctx.get_world_rank(), ctx.get_world_size()
        # Local (per-slice) mesh: fsdp x tp over this host's virtual devices.
        mesh = mesh_lib.create_mesh({"fsdp": 2, "tp": 2})
        assert mesh.shape["fsdp"] == 2

        collective.init_collective_group(world, rank, backend="host",
                                         group_name="dcn-dp")
        # Each slice sees different data; DP-across-DCN averages the grads.
        w = jnp.zeros((4,))
        data = jnp.full((4,), float(rank + 1))

        def lossf(w):
            return jnp.sum((w - data) ** 2)

        for _ in range(3):
            g = jax.grad(lossf)(w)
            g = collective.allreduce(np.asarray(g), group_name="dcn-dp",
                                     op=collective.ReduceOp.MEAN)
            w = w - 0.25 * jnp.asarray(g)
        train.report({"rank": rank, "world": world,
                      "w0": float(w[0]), "loss": float(lossf(w))})

    result = JaxTrainer(
        loop,
        jax_config=train.JaxConfig(distributed=False),
        scaling_config=ScalingConfig(topology="v4-8"),
        num_slices=2,
        run_config=RunConfig(name="dcn", storage_path="/tmp/rtpu_dcn_test"),
    ).fit()
    assert result.metrics["world"] == 2
    # grads of sum((w-d)^2) with d=1,2 average to pull w toward 1.5
    assert abs(result.metrics["w0"] - 1.5) < 0.2
