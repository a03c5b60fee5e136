"""Collective API tests (reference: python/ray/util/collective/tests/ — gloo-backend
suite run on CPU; here the HOST backend plays that role, and the XLA tier runs on the
virtual 8-device CPU mesh)."""

import numpy as np
import pytest

import ray_tpu


@ray_tpu.remote
class Member:
    def __init__(self, world_size, rank, group_name):
        from ray_tpu.util import collective as col

        self.rank = rank
        col.init_collective_group(world_size, rank, backend="host", group_name=group_name)

    def do_allreduce(self, value):
        from ray_tpu.util import collective as col

        return col.allreduce(np.array(value, dtype=np.float32), group_name=self.group())

    def group(self):
        return "g-allreduce"

    def do_barrier(self):
        from ray_tpu.util import collective as col

        col.barrier(group_name=self.group())
        return self.rank

    def do_verbs(self):
        """One member runs the whole verb sequence; all members must call in lockstep."""
        from ray_tpu.util import collective as col

        g = self.group()
        out = {}
        out["allgather"] = col.allgather(np.array([self.rank]), group_name=g)
        out["bcast"] = col.broadcast(
            np.array([42.0]) if self.rank == 0 else np.array([0.0]), src_rank=0, group_name=g
        )
        out["reduce"] = col.reduce(np.array([1.0]), dst_rank=1, group_name=g)
        chunks = [np.array([float(self.rank * 10 + i)]) for i in range(col.get_collective_group_size(g))]
        out["rs"] = col.reducescatter(chunks, group_name=g)
        return out


@pytest.fixture(scope="module")
def members(ray_start_regular):
    ws = 3
    actors = [Member.remote(ws, r, "g-allreduce") for r in range(ws)]
    ray_tpu.get([a.do_barrier.remote() for a in actors])  # ensure init done
    return actors


def test_allreduce(members):
    outs = ray_tpu.get([a.do_allreduce.remote([1.0, float(i)]) for i, a in enumerate(members)])
    for out in outs:
        np.testing.assert_allclose(out, [3.0, 0.0 + 1.0 + 2.0])


def test_verbs(members):
    outs = ray_tpu.get([a.do_verbs.remote() for a in members])
    for rank, out in enumerate(outs):
        gathered = out["allgather"]
        assert [int(x[0]) for x in gathered] == [0, 1, 2]
        np.testing.assert_allclose(out["bcast"], [42.0])
        if rank == 1:
            np.testing.assert_allclose(out["reduce"], [3.0])
        else:
            assert out["reduce"] is None
        # reducescatter: rank r gets sum over src of chunk r = sum_src(src*10 + r)
        np.testing.assert_allclose(out["rs"], [0 + 10 + 20 + 3 * rank])


@ray_tpu.remote
class P2P:
    def __init__(self, world_size, rank):
        from ray_tpu.util import collective as col

        self.rank = rank
        col.init_collective_group(world_size, rank, backend="host", group_name="p2p")

    def ping(self):
        from ray_tpu.util import collective as col

        col.send(np.array([7.0]), dst_rank=1, group_name="p2p")
        return True

    def pong(self):
        from ray_tpu.util import collective as col

        return col.recv(src_rank=0, group_name="p2p")


def test_send_recv(ray_start_regular):
    a = P2P.remote(2, 0)
    b = P2P.remote(2, 1)
    r_pong = b.pong.remote()
    assert ray_tpu.get(a.ping.remote())
    np.testing.assert_allclose(ray_tpu.get(r_pong), [7.0])


def test_declarative_group(ray_start_regular):
    from ray_tpu.util import collective as col

    @ray_tpu.remote
    class Worker:
        def reduce_it(self, v):
            from ray_tpu.util import collective as col

            return col.allreduce(np.array([v], np.float32), group_name="decl")

    actors = [Worker.remote() for _ in range(2)]
    col.create_collective_group(actors, 2, [0, 1], backend="host", group_name="decl")
    outs = ray_tpu.get([a.reduce_it.remote(float(i + 1)) for i, a in enumerate(actors)])
    for out in outs:
        np.testing.assert_allclose(out, [3.0])


def test_destroy_and_recreate(ray_start_regular):
    @ray_tpu.remote
    class W:
        def join(self, ws, rank):
            from ray_tpu.util import collective as col

            col.init_collective_group(ws, rank, backend="host", group_name="dg")
            return True

        def reduce_it(self, v, ws):
            from ray_tpu.util import collective as col

            out = col.allreduce(np.array([v], np.float32), group_name="dg")
            assert col.get_collective_group_size("dg") == ws
            return out

        def leave(self):
            from ray_tpu.util import collective as col

            col.destroy_collective_group("dg")
            return True

    actors = [W.remote() for _ in range(2)]
    ray_tpu.get([a.join.remote(2, i) for i, a in enumerate(actors)])
    ray_tpu.get([a.reduce_it.remote(1.0, 2) for a in actors])
    ray_tpu.get([a.leave.remote() for a in actors])
    # Re-create under the same name with a different world size.
    actors3 = [W.remote() for _ in range(3)]
    ray_tpu.get([a.join.remote(3, i) for i, a in enumerate(actors3)])
    outs = ray_tpu.get([a.reduce_it.remote(1.0, 3) for a in actors3])
    for out in outs:
        np.testing.assert_allclose(out, [3.0])


def test_xla_tier():
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.util.collective import ReduceOp, xla
    from jax import shard_map

    mesh = create_mesh({"dp": 4})
    group = xla.MeshGroup(mesh, "dp")
    stacked = np.arange(4 * 3, dtype=np.float32).reshape(4, 3)
    np.testing.assert_allclose(group.allreduce(stacked), stacked.sum(0))
    np.testing.assert_allclose(group.allreduce(stacked, ReduceOp.MAX), stacked.max(0))
    np.testing.assert_allclose(group.allreduce(stacked, ReduceOp.MEAN), stacked.mean(0))

    # In-graph verbs under shard_map.
    def step(x):
        y = xla.allreduce(x, "dp")
        z = xla.send_next(x, "dp")
        return y, z

    f = jax.jit(
        shard_map(
            step, mesh=mesh, in_specs=P("dp"), out_specs=(P(None), P("dp"))
        )
    )
    x = np.arange(4, dtype=np.float32).reshape(4, 1)
    y, z = f(x)
    np.testing.assert_allclose(np.asarray(y), [[6.0]])
    np.testing.assert_allclose(np.asarray(z).ravel(), [3.0, 0.0, 1.0, 2.0])
