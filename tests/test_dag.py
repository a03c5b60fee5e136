"""Compiled graph (aDAG) tests.

Shape parity with the reference suite (python/ray/dag/tests/): interpreted
execution, single-actor compiled chains, multi-actor pipelines, MultiOutputNode
fan-out, error propagation through pinned loops, repeated executes (channel reuse),
teardown, and a throughput sanity check vs regular actor calls.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode


@pytest.fixture(scope="module", autouse=True)
def _cluster(ray_start_regular):
    yield


@ray_tpu.remote
class Worker:
    def __init__(self, bias: int = 0):
        self._bias = bias
        self._calls = 0

    def inc(self, x):
        self._calls += 1
        return x + 1 + self._bias

    def double(self, x):
        return x * 2

    def add(self, a, b):
        return a + b

    def boom(self, x):
        raise ValueError("dag boom")

    def calls(self):
        return self._calls


def test_interpreted_execute():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.double.bind(w.inc.bind(inp))
    assert dag.execute(5) == 12  # (5+1)*2


def test_compiled_single_actor_chain():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.double.bind(w.inc.bind(inp))
    compiled = dag.experimental_compile()
    try:
        for i in range(10):
            assert compiled.execute(i).get() == (i + 1) * 2
    finally:
        compiled.teardown()


def test_compiled_multi_actor_pipeline():
    a = Worker.remote(bias=0)
    b = Worker.remote(bias=0)
    with InputNode() as inp:
        dag = b.double.bind(a.inc.bind(inp))
    compiled = dag.experimental_compile()
    try:
        results = [compiled.execute(i) for i in range(5)]
        assert [r.get() for r in results] == [(i + 1) * 2 for i in range(5)]
    finally:
        compiled.teardown()


def test_multi_output():
    a = Worker.remote()
    b = Worker.remote()
    with InputNode() as inp:
        dag = MultiOutputNode([a.inc.bind(inp), b.double.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        r1, r2 = compiled.execute(10)
        assert r1.get() == 11
        assert r2.get() == 20
    finally:
        compiled.teardown()


def test_fan_in():
    a = Worker.remote()
    b = Worker.remote()
    c = Worker.remote()
    with InputNode() as inp:
        dag = c.add.bind(a.inc.bind(inp), b.double.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get() == (3 + 1) + (3 * 2)
    finally:
        compiled.teardown()


def test_error_propagates_and_loop_survives():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.boom.bind(inp)
    compiled = dag.experimental_compile()
    try:
        with pytest.raises(ValueError, match="dag boom"):
            compiled.execute(1).get()
        # Loop must still be alive for the next execute.
        with pytest.raises(ValueError, match="dag boom"):
            compiled.execute(2).get()
    finally:
        compiled.teardown()


def test_numpy_payloads():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.double.bind(inp)
    compiled = dag.experimental_compile()
    try:
        x = np.arange(10000, dtype=np.float32)
        out = compiled.execute(x).get()
        np.testing.assert_allclose(out, x * 2)
    finally:
        compiled.teardown()


def test_dag_array_payloads_ride_tensor_fastpath():
    """Compiled-DAG edges carrying arrays move them as raw-buffer tensor
    frames — cloudpickle never sees the array bytes (round 11; counted via
    the per-process transport stats on the driver's input/output edges)."""
    from ray_tpu.experimental import tensor_transport as tt

    w = Worker.remote()
    with InputNode() as inp:
        dag = w.double.bind(inp)
    compiled = dag.experimental_compile()
    try:
        x = np.arange(10000, dtype=np.float32)
        compiled.execute(x).get()  # warm the loop off-stats
        tt.reset_transport_stats()
        out = compiled.execute(x).get()
        np.testing.assert_allclose(out, x * 2)
        s = tt.transport_stats()
        # Driver wrote the input edge and read the output edge as tensor
        # frames (actor-side edges run the same code path in-process).
        assert s["tensor_frames_written"] >= 1, s
        assert s["tensor_frames_read"] >= 1, s
        assert s["tensor_bytes_written"] >= x.nbytes, s

        # Scalar payloads still pickle (the fast path is size-gated).
        tt.reset_transport_stats()
        assert compiled.execute(3).get() == 6
        s = tt.transport_stats()
        assert s["tensor_frames_written"] == 0, s
        assert s["pickle_frames_written"] >= 1, s
    finally:
        compiled.teardown()


def test_input_attribute_access():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.add.bind(inp["a"], inp["b"])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute({"a": 4, "b": 7}).get() == 11
    finally:
        compiled.teardown()


def test_compiled_faster_than_actor_calls():
    w = Worker.remote()
    n = 200

    def time_actor():
        t0 = time.monotonic()
        for i in range(n):
            ray_tpu.get(w.inc.remote(i))
        return time.monotonic() - t0

    ray_tpu.get(w.inc.remote(0))  # warm up the regular path
    actor_time = min(time_actor(), time_actor())

    with InputNode() as inp:
        dag = w.inc.bind(inp)
    compiled = dag.experimental_compile()

    def time_dag():
        t0 = time.monotonic()
        for i in range(n):
            compiled.execute(i).get()
        return time.monotonic() - t0

    try:
        compiled.execute(0).get()  # warm up
        # Best-of-two on BOTH paths: a single load spike (shared CI host)
        # must not flip a 5x structural gap into a flake.
        dag_time = min(time_dag(), time_dag())
    finally:
        compiled.teardown()
    # The pinned-loop path must beat the submit-per-call path comfortably.
    assert dag_time < actor_time, (dag_time, actor_time)


def test_same_node_passed_twice():
    w = Worker.remote()
    v = Worker.remote()
    with InputNode() as inp:
        x = w.inc.bind(inp)
        dag = v.add.bind(x, x)  # one node consumed twice by one bind
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(4).get() == 10  # (4+1) + (4+1)
    finally:
        compiled.teardown()


def test_input_passed_twice():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.add.bind(inp, inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(6).get() == 12
    finally:
        compiled.teardown()


def test_teardown_with_inflight_executions():
    @ray_tpu.remote
    class Slow:
        def work(self, x):
            time.sleep(3.0)
            return x

    w = Slow.remote()
    with InputNode() as inp:
        dag = w.work.bind(inp)
    # Rings are sized to max_inflight (reference: num_shm_buffers =
    # max_inflight_executions), so a bound-respecting driver can't wedge a
    # writer; teardown safety is exercised with the loop mid-compute and
    # unconsumed results in flight.
    compiled = dag.experimental_compile(max_inflight_executions=4)
    try:
        for i in range(4):
            compiled.execute(i)
        time.sleep(0.2)  # loop is inside work() with 3 more queued
    finally:
        compiled.teardown()  # must not hang or leave the actor wedged



def test_max_inflight_capacity_raises():
    """Past max_inflight_executions, execute() raises instead of wedging
    (reference compiled_dag_node.py:2223 RayCgraphCapacityExceeded)."""
    from ray_tpu.exceptions import RayCgraphCapacityExceeded

    w = Worker.remote()
    with InputNode() as inp:
        dag = w.inc.bind(inp)
    compiled = dag.experimental_compile(max_inflight_executions=2)
    try:
        r0 = compiled.execute(0)
        compiled.execute(1)
        with pytest.raises(RayCgraphCapacityExceeded):
            compiled.execute(2)
        assert r0.get(timeout=60) == 1  # consuming a result frees a slot
        r2 = compiled.execute(2)
        assert r2.get(timeout=60) == 3
    finally:
        compiled.teardown()


def test_execute_async_overlaps_inflight():
    """execute_async pipelines: the second submission lands while the first
    result is still unread, and awaiting runs off the event loop — a
    concurrent ticker task keeps ticking while results are pending
    (reference compiled_dag_node.py execute_async :2627)."""
    import asyncio

    @ray_tpu.remote
    class Paced:
        def work(self, x):
            time.sleep(0.4)
            return x * 10

    w = Paced.remote()
    with InputNode() as inp:
        dag = w.work.bind(inp)
    compiled = dag.experimental_compile(max_inflight_executions=4)

    async def drive():
        ticks = 0
        stop = asyncio.Event()

        async def ticker():
            nonlocal ticks
            while not stop.is_set():
                ticks += 1
                await asyncio.sleep(0.02)

        t = asyncio.create_task(ticker())
        t0 = time.monotonic()
        f1 = await compiled.execute_async(1)
        f2 = await compiled.execute_async(2)  # in flight before f1 is read
        submit_time = time.monotonic() - t0
        v1 = await f1
        v2 = await f2
        stop.set()
        await t
        return submit_time, v1, v2, ticks

    try:
        submit_time, v1, v2, ticks = asyncio.run(drive())
        assert (v1, v2) == (10, 20)
        # Submissions don't wait for results (two 0.4s computes pending).
        assert submit_time < 0.3, f"submit blocked: {submit_time:.2f}s"
        # The event loop stayed live while ~0.8s of compute drained.
        assert ticks >= 10, f"event loop starved: {ticks} ticks"
    finally:
        compiled.teardown()


def test_execute_async_error_propagates():
    import asyncio

    w = Worker.remote()
    with InputNode() as inp:
        dag = w.boom.bind(inp)
    compiled = dag.experimental_compile()

    async def drive():
        fut = await compiled.execute_async(1)
        with pytest.raises(ValueError, match="dag boom"):
            await fut

    try:
        asyncio.run(drive())
    finally:
        compiled.teardown()



def test_collective_allreduce_node():
    """In-graph allreduce: each participant's loop reduces every peer's
    contribution (reference: dag/collective_node.py + allreduce.bind)."""
    import numpy as np

    from ray_tpu.dag import InputNode, MultiOutputNode, collective

    @ray_tpu.remote
    class Shard:
        def __init__(self, scale):
            self.scale = scale

        def grads(self, x):
            return np.full(4, float(x) * self.scale)

        def apply(self, reduced):
            return float(reduced.sum())

    a, b, c = Shard.remote(1.0), Shard.remote(10.0), Shard.remote(100.0)
    with InputNode() as inp:
        contribs = [a.grads.bind(inp), b.grads.bind(inp), c.grads.bind(inp)]
        reduced = collective.allreduce.bind(contribs, op="sum")
        # Each participant consumes ITS copy of the reduced tensor.
        outs = MultiOutputNode([
            a.apply.bind(reduced[0]),
            b.apply.bind(reduced[1]),
            c.apply.bind(reduced[2]),
        ])
    dag = outs.experimental_compile()
    try:
        for x in (2.0, 3.0):
            refs = dag.execute(x)
            expect = 4 * x * (1 + 10 + 100)
            vals = [r.get(timeout=120) for r in refs]
            assert vals == [expect] * 3, vals
    finally:
        dag.teardown()


def test_collective_mean_and_validation():
    import numpy as np

    from ray_tpu.dag import InputNode, MultiOutputNode, collective

    @ray_tpu.remote
    class W:
        def val(self, x):
            return np.asarray([float(x)])

    w1, w2 = W.remote(), W.remote()
    with InputNode() as inp:
        n1, n2 = w1.val.bind(inp), w2.val.bind(inp)
        r = collective.allreduce.bind([n1, n2], op="mean")
        outs = MultiOutputNode(r)
    dag = outs.experimental_compile()
    try:
        refs = dag.execute(8.0)
        assert [float(x.get(timeout=120)[0]) for x in refs] == [8.0, 8.0]
    finally:
        dag.teardown()

    with pytest.raises(ValueError, match="distinct actors"):
        with InputNode() as inp:
            n = w1.val.bind(inp)
            collective.allreduce.bind([n, n])
    with pytest.raises(ValueError, match="reduce op"):
        with InputNode() as inp:
            collective.allreduce.bind(
                [w1.val.bind(inp), w2.val.bind(inp)], op="xor"
            )


def test_dropped_refs_release_capacity():
    """Fire-and-forget execute() past max_inflight must NOT wedge the DAG:
    refs dropped unread mark their slot consumable and the next capacity-bound
    submit drains them (reference: CompiledDAGRef.__del__ consumes unread
    results)."""
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.inc.bind(inp)
    compiled = dag.experimental_compile(max_inflight_executions=3)
    try:
        # 3x the bound, every ref dropped on the floor.
        for i in range(9):
            compiled.execute(i)  # raylint: disable=RL501 (the wedge under test)
        # The graph still works and the next read sees the newest round.
        ref = compiled.execute(100)
        assert ref.get(timeout=60) == 101
    finally:
        compiled.teardown()


def test_released_ref_cannot_be_read():
    w = Worker.remote()
    with InputNode() as inp:
        dag = w.inc.bind(inp)
    compiled = dag.experimental_compile(max_inflight_executions=2)
    try:
        ref = compiled.execute(1)
        ref.release()
        with pytest.raises(ValueError):
            ref.get(timeout=5)
        # The released round's capacity comes back.
        for i in range(4):
            r = compiled.execute(i)
            r.release()
        ref2 = compiled.execute(7)
        assert ref2.get(timeout=60) == 8
    finally:
        compiled.teardown()


def test_dropped_multi_output_refs_release_capacity():
    """Abandoning only ONE of a round's outputs must also free the round once
    the other output is read (per-output consumption accounting)."""
    a, b = Worker.remote(), Worker.remote(bias=10)
    with InputNode() as inp:
        dag = MultiOutputNode([a.inc.bind(inp), b.inc.bind(inp)])
    compiled = dag.experimental_compile(max_inflight_executions=2)
    try:
        for i in range(5):
            r1, _r2 = compiled.execute(i)  # _r2 dropped every round
            assert r1.get(timeout=60) == i + 1
            del _r2
        r1, r2 = compiled.execute(50)
        assert r2.get(timeout=60) == 61
        r1.release()
    finally:
        compiled.teardown()



def test_compiled_dag_across_two_nodes():
    """A compiled DAG pins loops on actors on TWO nodes: cross-node edges ride
    RpcChannel (ring in the writer, readers pull over direct worker conns) and
    same-node edges stay on shm — selection is automatic (VERDICT #6;
    reference: cross-node mutable-object channels,
    experimental_mutable_object_provider.h:143)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.dag import InputNode

    ray_tpu.shutdown()
    env = {"JAX_PLATFORMS": "cpu"}
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1, "env_vars": env})
    cluster.add_node(num_cpus=1, resources={"stage2": 1.0}, env_vars=env)
    cluster.connect()
    cluster.wait_for_nodes()
    try:
        @ray_tpu.remote(num_cpus=0)
        class A:
            def double(self, x):
                return x * 2

        @ray_tpu.remote(num_cpus=0, resources={"stage2": 0.1})
        class B:
            def add_one(self, x):
                return x + 1

        a, b = A.remote(), B.remote()
        with InputNode() as inp:
            mid = a.double.bind(inp)      # head node
            out = b.add_one.bind(mid)     # second node: cross-node edge
        dag = out.experimental_compile()
        try:
            from ray_tpu.experimental.channel import RpcChannel

            # The a->b edge and the b->driver edge must be RPC channels; the
            # driver->a input edge stays local (driver and A share the head).
            kinds = [type(ch).__name__ for ch in dag._channels]
            assert "RpcChannel" in kinds, kinds
            for i in range(5):
                assert dag.execute(i).get(timeout=120) == i * 2 + 1
        finally:
            dag.teardown()
    finally:
        cluster.shutdown()


def test_compiled_dag_overlap_and_profiling():
    """Overlap scheduling: a two-stage cross-node DAG pipelines channel I/O
    with compute, so busy-time (read+compute) exceeds wall time on the second
    stage — measured via the new per-op profile (VERDICT r2 #8; reference:
    dag_node_operation.py READ/COMPUTE/WRITE reordering +
    compiled_dag_node.py op profiling)."""
    import time

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.dag import InputNode

    ray_tpu.shutdown()
    env = {"JAX_PLATFORMS": "cpu"}
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1, "env_vars": env})
    cluster.add_node(num_cpus=1, resources={"stage2": 1.0}, env_vars=env)
    cluster.connect()
    cluster.wait_for_nodes()
    try:
        @ray_tpu.remote(num_cpus=0)
        class Producer:
            def slow(self, x):
                time.sleep(0.05)
                return x

        @ray_tpu.remote(num_cpus=0, resources={"stage2": 0.1})
        class Consumer:
            def work(self, x):
                time.sleep(0.05)
                return x + 1

        a, b = Producer.remote(), Consumer.remote()
        with InputNode() as inp:
            out = b.work.bind(a.slow.bind(inp))
        dag = out.experimental_compile(max_inflight_executions=16)
        try:
            assert dag.execute(0).get(timeout=120) == 1  # warm both loops
            K = 12
            t0 = time.monotonic()
            refs = [dag.execute(i) for i in range(1, K + 1)]
            vals = [r.get(timeout=120) for r in refs]
            elapsed = time.monotonic() - t0
            assert vals == [i + 1 for i in range(1, K + 1)]
            # Serial (no overlap) would cost K * (producer + consumer) >= 1.2s
            # on the consumer's critical path; pipelining bounds it near
            # K * max(stage) + one pipeline fill.
            assert elapsed < K * 0.1 * 0.9, f"no pipelining: {elapsed:.2f}s"

            # Per-op profile: the consumer overlapped its reads (waiting on the
            # producer) with its own compute, so busy time exceeds wall time.
            deadline = time.monotonic() + 30
            prof = {}
            while time.monotonic() < deadline:
                prof = dag.op_profile()
                # Emission is windowed: half the iterations is enough signal.
                done = [p for p in prof.values() if p.get("iters", 0) >= K // 2]
                if len(done) >= 2:
                    break
                time.sleep(1.0)
            assert len(prof) >= 2, prof
            busy = sum(p.get("read_s", 0) + p.get("compute_s", 0)
                       for p in prof.values())
            assert busy > elapsed * 1.2, (
                f"no measured overlap: busy {busy:.2f}s vs wall {elapsed:.2f}s "
                f"({prof})"
            )
        finally:
            dag.teardown()
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
