"""Chaos harness: random worker kills against long-running workloads.

Shape parity: reference `python/ray/tests/chaos/` — a resource killer runs
beside a real workload, SIGKILLing worker processes on a cadence, and the
workload must still complete CORRECTLY (retries + lineage reconstruction +
actor restarts absorbing the failures). This is the systematic concurrency/
failure stressor beyond targeted fault-injection tests.
"""

import os
import random
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def chaos_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_BORROW_AUDIT_INTERVAL_S", "2")
    from ray_tpu._private.config import CONFIG

    CONFIG._reset()
    ray_tpu.init(
        num_cpus=4, num_tpus=0,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "RAY_TPU_BORROW_AUDIT_INTERVAL_S": "2",
        },
    )
    yield
    ray_tpu.shutdown()
    monkeypatch.delenv("RAY_TPU_BORROW_AUDIT_INTERVAL_S")
    CONFIG._reset()


class _WorkerKiller(threading.Thread):
    """SIGKILL a random live task-worker pid every `period_s` (reference:
    chaos killer actors). Runs in the driver for determinism of teardown."""

    def __init__(self, get_pids, period_s: float = 1.5, seed: int = 0):
        super().__init__(daemon=True)
        self._get_pids = get_pids
        self._period = period_s
        self._rng = random.Random(seed)
        self._halt = threading.Event()
        self.kills = 0

    def run(self):
        while not self._halt.wait(self._period):
            pids = [p for p in self._get_pids() if p and p != os.getpid()]
            if not pids:
                continue
            victim = self._rng.choice(pids)
            try:
                os.kill(victim, signal.SIGKILL)
                self.kills += 1
            except ProcessLookupError:
                pass

    def stop(self):
        self._halt.set()


def test_tasks_survive_random_worker_kills(chaos_cluster):
    """200 retriable tasks complete with correct results while a killer
    SIGKILLs a random worker every 1.5s."""
    seen_pids = set()
    pid_lock = threading.Lock()

    @ray_tpu.remote(max_retries=10)
    def work(i):
        time.sleep(0.1)
        return i * i, os.getpid()

    def snapshot_pids():
        # The killer thread must read under the lock: an unlocked set copy
        # racing update() raises mid-iteration and silently kills the killer.
        with pid_lock:
            return list(seen_pids)

    killer = _WorkerKiller(snapshot_pids, period_s=1.5)
    killer.start()
    try:
        results = []
        for wave in range(10):
            refs = [work.remote(wave * 20 + i) for i in range(20)]
            out = ray_tpu.get(refs, timeout=300)
            with pid_lock:
                seen_pids.update(p for _v, p in out)
            results.extend(v for v, _p in out)
        expected = [i * i for i in range(200)]
        assert sorted(results) == sorted(expected)
    finally:
        killer.stop()
        killer.join(timeout=5)
    assert killer.kills >= 2, "chaos never actually killed anyone"


def test_restartable_actor_pipeline_survives_kills(chaos_cluster):
    """A restartable stateful actor keeps serving (reconstructing its state
    from constructor args) while being SIGKILLed mid-stream; owned objects
    referenced across the kills stay readable via lineage/borrow machinery."""

    @ray_tpu.remote(max_restarts=20, max_retries=10)
    class Accumulator:
        def __init__(self):
            self.pid = os.getpid()

        def process(self, arr):
            time.sleep(0.15)  # long enough that kills land mid-workload
            return float(np.asarray(arr).sum()), os.getpid()

    acc = Accumulator.remote()
    data_refs = [ray_tpu.put(np.full(50_000, i, np.float64)) for i in range(8)]
    first_sum, first_pid = ray_tpu.get(
        acc.process.remote(data_refs[0]), timeout=120
    )
    assert first_sum == 0.0
    pids = {first_pid}
    latest = [first_pid]  # killer targets the LIVE incarnation, not ghosts
    killer = _WorkerKiller(lambda: [latest[0]], period_s=2.0, seed=7)
    killer.start()
    def call_with_retry(make_ref, attempts=10):
        # Chaos-workload idiom: a kill can land mid-call; the caller resubmits
        # against the restarted actor (reference chaos tests do the same).
        last = None
        for _ in range(attempts):
            try:
                return ray_tpu.get(make_ref(), timeout=120)
            except Exception as e:  # noqa: BLE001 - actor died mid-call
                last = e
                time.sleep(1.0)
        raise AssertionError(f"call never succeeded through chaos: {last}")

    try:
        totals = []
        for round_i in range(6):
            for ref in data_refs:
                s, pid = call_with_retry(lambda r=ref: acc.process.remote(r))
                totals.append(s)
                pids.add(pid)
                latest[0] = pid
        expected = [i * 50_000.0 for i in range(8)] * 6
        assert totals == expected
    finally:
        killer.stop()
        killer.join(timeout=5)
    assert killer.kills >= 2
    assert len(pids) >= 2, "actor was never actually restarted"


# ---------------------------------------------------------------- node chaos

_NODE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def test_tasks_survive_node_kill():
    """SIGKILL a whole worker NODE (raylet + its workers) mid-wave: retriable
    tasks that were running there re-execute elsewhere and every result is
    still correct (reference: RayletKiller chaos,
    python/ray/_private/test_utils.py:1479)."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    n2 = cluster.add_node(num_cpus=2, env_vars=_NODE_ENV)
    cluster.connect()
    cluster.wait_for_nodes()
    try:
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        @ray_tpu.remote(max_retries=10)
        def work(i):
            time.sleep(0.3)
            return i * i, ray_tpu.get_runtime_context().get_node_id()

        n2_id = next(n["node_id"] for n in ray_tpu.nodes()
                     if n["node_id"].hex() == n2.node_id_hex)
        # SOFT affinity to node 2: tasks start there, and their retries may
        # reschedule anywhere once the node is gone.
        on_n2 = work.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(n2_id, soft=True)
        )

        # Warm a first wave and require node 2 to actually execute work —
        # otherwise killing it proves nothing.
        first = ray_tpu.get([on_n2.remote(i) for i in range(4)], timeout=300)
        nodes_seen = {n.hex() for _v, n in first}
        assert n2.node_id_hex in nodes_seen, f"work never ran on node 2: {nodes_seen}"

        # Launch a big wave biased onto node 2, then kill the node while much
        # of it is in flight.
        refs = [(on_n2 if i % 2 else work).remote(i) for i in range(40)]
        time.sleep(0.8)  # several tasks are mid-sleep on n2 right now
        cluster.kill_node(n2)
        out = ray_tpu.get(refs, timeout=300)
        assert sorted(v for v, _n in out) == sorted(i * i for i in range(40))
        # Everything after the kill ran on the surviving node(s).
        alive = {n["node_id"].hex() for n in ray_tpu.nodes() if n["alive"]}
        assert n2.node_id_hex not in alive
    finally:
        cluster.shutdown()


def test_elastic_trainer_survives_node_kill_and_reexpands(tmp_path):
    """An elastic JaxTrainer run loses a NODE to SIGKILL mid-attempt, resumes
    at N-1, then re-expands to full size IN THE SAME RUN once capacity
    returns (reference: chaos suite + elastic scaling policy)."""
    import os
    import threading

    from ray_tpu import train
    from ray_tpu.train import (
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    cluster.add_node(num_cpus=1, resources={"trainslot": 1.0},
                     env_vars=_NODE_ENV)
    n2 = cluster.add_node(num_cpus=1, resources={"trainslot": 1.0},
                          env_vars=_NODE_ENV)
    cluster.connect()
    cluster.wait_for_nodes()
    marker_dir = str(tmp_path)
    try:
        def loop(config):
            import os as _os

            ctx = train.get_context()
            world = ctx.get_world_size()
            rank = ctx.get_world_rank()
            mk = config["markers"]
            open(_os.path.join(mk, f"started_{world}_{rank}"), "w").write("x")
            if world == 2 and not _os.path.exists(
                _os.path.join(mk, "expanded")
            ):
                # First full-size attempt: park until the driver SIGKILLs a
                # node out from under one of us.
                time.sleep(600)
            if world == 1:
                # Shrunk attempt: wait for the driver to restore capacity,
                # then fail ONCE so the elastic policy re-evaluates and
                # re-expands the SAME run.
                deadline = time.monotonic() + 240
                while not _os.path.exists(_os.path.join(mk, "capacity_back")):
                    if time.monotonic() > deadline:
                        break
                    time.sleep(0.5)
                open(_os.path.join(mk, "expanded"), "w").write("x")
                raise RuntimeError("chaos: trigger elastic re-expansion")
            train.report({"world": world, "rank": rank})

        trainer = JaxTrainer(
            loop,
            train_loop_config={"markers": marker_dir},
            scaling_config=ScalingConfig(
                num_workers=2, min_workers=1, use_tpu=False,
                resources_per_worker={"trainslot": 1.0},
            ),
            run_config=RunConfig(
                name="node-chaos", storage_path=str(tmp_path / "storage"),
                failure_config=FailureConfig(max_failures=4),
            ),
        )

        result_box = {}

        def fit():
            result_box["result"] = trainer.fit()

        t = threading.Thread(target=fit)
        t.start()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if len([f for f in os.listdir(marker_dir)
                    if f.startswith("started_2_")]) >= 2:
                break
            time.sleep(0.2)
        assert len([f for f in os.listdir(marker_dir)
                    if f.startswith("started_2_")]) >= 2

        cluster.kill_node(n2)  # SIGKILL raylet + its workers, mid-attempt

        # The run shrinks to world 1; then we restore capacity.
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if any(f.startswith("started_1_") for f in os.listdir(marker_dir)):
                break
            time.sleep(0.5)
        assert any(f.startswith("started_1_") for f in os.listdir(marker_dir)), (
            "run never resumed at N-1 after the node kill"
        )
        cluster.add_node(num_cpus=1, resources={"trainslot": 1.0},
                         env_vars=_NODE_ENV)
        cluster.wait_for_nodes()
        open(os.path.join(marker_dir, "capacity_back"), "w").write("x")

        t.join(timeout=420)
        assert not t.is_alive(), "trainer did not finish after node chaos"
        result = result_box["result"]
        assert result.error is None, result.error
        # The final attempt re-expanded to the full world size.
        assert result.metrics["world"] == 2
    finally:
        cluster.shutdown()


# ------------------------------------------------------- control-plane chaos
#
# Reference shape: python/ray/tests/chaos/ also kills the HEAD services under
# live workloads. The contract here (docs/fault_tolerance.md): the GCS and the
# serve/train controllers are restartable without dropping live work — data
# plane traffic rides cached handles and direct connections, control state
# recovers from the persistent store / GCS KV.


def test_serve_traffic_rides_through_gcs_kill():
    """SIGKILL the GCS under a deployed serve app with live HTTP traffic:
    zero replica processes die, traffic keeps flowing during the outage
    (routers and proxies ride cached handles + direct connections), and after
    the GCS restarts responses are identical to pre-kill responses for the
    same prompts."""
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    try:
        cluster.connect()

        @serve.deployment(num_replicas=2)
        class Echo:
            def pid(self):
                return os.getpid()

            def __call__(self, request):
                p = request.query_params.get("p", "")
                return {"out": f"{p}::{len(p)}"}

        serve.run(Echo.bind(), name="gcs-chaos", route_prefix="/")
        port = serve.get_proxy_port()

        def ask(p, timeout=10):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/?p={p}", timeout=timeout
            ) as r:
                return json.loads(r.read())["out"]

        prompts = [f"prompt-{i}" for i in range(4)]
        baseline = {p: ask(p) for p in prompts}
        pid_handle = serve.DeploymentHandle("gcs-chaos", "Echo", "pid")
        pids_before = sorted(pid_handle.broadcast())
        assert len(pids_before) == 2

        ok_during: list = []
        errors: list = []
        halt = threading.Event()

        def traffic():
            i = 0
            while not halt.is_set():
                p = prompts[i % len(prompts)]
                i += 1
                try:
                    ok_during.append((p, ask(p, timeout=5)))
                except Exception as e:  # noqa: BLE001 - tallied, asserted below
                    errors.append(repr(e))
                time.sleep(0.05)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        time.sleep(1.0)  # warm: routes cached, direct connections established
        n_before_kill = len(ok_during)
        cluster.head.kill_gcs()
        time.sleep(3.0)  # the GCS is DOWN for this whole window
        n_during_kill = len(ok_during)
        cluster.head.restart_gcs()
        # Raylets re-register; the driver's gcs_call reconnects with backoff.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if [n for n in ray_tpu.nodes() if n["alive"]]:
                    break
            except Exception:
                time.sleep(0.5)
        time.sleep(1.0)
        halt.set()
        t.join(timeout=30)

        # Traffic flowed WHILE the GCS was down, not just after recovery.
        assert n_during_kill - n_before_kill >= 10, (
            f"only {n_during_kill - n_before_kill} requests succeeded during "
            f"the outage ({len(errors)} errors: {errors[:3]})"
        )
        # Every response that succeeded — before, during, after — is correct.
        for p, out in ok_during:
            assert out == baseline[p], f"divergent response for {p!r}"
        # Post-recovery responses are token-identical to pre-kill responses.
        post = {p: ask(p, timeout=30) for p in prompts}
        assert post == baseline
        # Zero replica processes died across the GCS restart.
        pids_after = sorted(pid_handle.broadcast())
        assert pids_after == pids_before
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_serve_controller_sigkill_recovers_and_adopts(chaos_cluster):
    """SIGKILL the serve controller under a deployed app: calls keep serving
    off cached routing tables, a new incarnation recovers the app table from
    GCS KV, RE-ADOPTS the live replicas (same pids, same count — no
    double-create), and a replayed deploy of the same app is a no-op."""
    from ray_tpu import serve
    from ray_tpu.serve._common import CONTROLLER_NAME, SERVE_NAMESPACE

    @serve.deployment(num_replicas=2)
    class Stable:
        def pid(self):
            return os.getpid()

        def __call__(self, x):
            return x * 3

    handle = serve.run(Stable.bind(), name="ctrl-chaos", route_prefix=None)
    assert handle.remote(7).result(timeout_s=60) == 21
    pid_handle = serve.DeploymentHandle("ctrl-chaos", "Stable", "pid")
    pids_before = sorted(pid_handle.broadcast())
    assert len(pids_before) == 2

    controller = ray_tpu.get_actor(CONTROLLER_NAME, namespace=SERVE_NAMESPACE)
    ctrl_pid = ray_tpu.get(controller.health.remote(), timeout=30)["pid"]
    os.kill(ctrl_pid, signal.SIGKILL)

    # Live replicas keep serving through the controller outage: the router's
    # cached table needs no controller round-trip.
    assert handle.remote(9).result(timeout_s=60) == 27

    # A new incarnation restarts (max_restarts=-1) and answers from a new pid.
    deadline = time.monotonic() + 90
    new_pid = None
    while time.monotonic() < deadline:
        try:
            h = ray_tpu.get(controller.health.remote(), timeout=10)
            if h["pid"] != ctrl_pid:
                new_pid = h["pid"]
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert new_pid is not None, "controller never restarted"

    # The app table recovered from GCS KV...
    status = serve.status()
    assert "ctrl-chaos" in status
    # ...and the live replicas were ADOPTED, not restarted (same pids) and not
    # double-created (same count).
    info = ray_tpu.get(
        controller.get_replicas.remote("ctrl-chaos", "Stable"), timeout=60
    )
    assert len(info["replicas"]) == 2
    assert info["exists"]
    pids_after = sorted(pid_handle.broadcast())
    assert pids_after == pids_before, "recovery restarted live replicas"

    # Replayed deploy_app of the identical app (the checkpoint-idempotency
    # contract, mirroring the GCS bundle-reservation replay guard): replicas
    # stay in place.
    serve.run(Stable.bind(), name="ctrl-chaos", route_prefix=None)
    assert sorted(pid_handle.broadcast()) == pids_before
    assert handle.remote(5).result(timeout_s=60) == 15
    serve.shutdown()


def test_train_run_rides_through_gcs_kill(tmp_path):
    """SIGKILL the GCS mid-train: workers keep stepping on their raylets, the
    (detached) controller's monitor loop tolerates the control-plane outage
    instead of declaring workers dead, and the run completes with a result
    bitwise-equal to an undisturbed run."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import DataParallelTrainer, RunConfig, ScalingConfig

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    marker = str(tmp_path / "mid_run")
    try:
        cluster.connect()

        def loop(config):
            import os as _os

            from ray_tpu import train as _train

            total = 0.0
            for step in range(30):
                total += float((step * 7 + 3) % 11) * 0.5
                if step == 3:
                    open(config["marker"], "w").write("x")
                time.sleep(0.25)
                _train.report({"step": step, "total": total})

        result_box = {}

        def fit():
            result_box["result"] = DataParallelTrainer(
                loop,
                train_loop_config={"marker": marker},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(
                    name="gcs-chaos-train", storage_path=str(tmp_path / "storage")
                ),
            ).fit()

        t = threading.Thread(target=fit, daemon=True)
        t.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not os.path.exists(marker):
            time.sleep(0.1)
        assert os.path.exists(marker), "run never reached mid-flight"

        cluster.head.kill_gcs()
        time.sleep(2.0)  # several training steps happen with the GCS DOWN
        cluster.head.restart_gcs()

        t.join(timeout=240)
        assert not t.is_alive(), "trainer did not finish after GCS chaos"
        result = result_box["result"]
        assert result.error is None, result.error
        expected = 0.0
        for step in range(30):
            expected += float((step * 7 + 3) % 11) * 0.5
        # Bitwise-equal to an undisturbed run: same float accumulation order.
        assert result.metrics["total"] == expected
        assert result.metrics["step"] == 29
    finally:
        cluster.shutdown()


@pytest.mark.slow
def test_detached_train_controller_sigkill_resumes_from_checkpoint(
    chaos_cluster, tmp_path
):
    """SIGKILL the detached train controller mid-run: a new incarnation
    detects its run-in-progress marker, recovers COMMITTED sharded
    checkpoints from storage, and resumes the run from the newest one instead
    of restarting from scratch."""
    import numpy as np

    import ray_tpu.checkpoint as ckpt
    from ray_tpu.train import (
        DataParallelTrainer,
        FailureConfig,
        RunConfig,
        ScalingConfig,
    )

    storage = str(tmp_path / "storage")
    attempts = str(tmp_path / "attempts")
    os.makedirs(attempts, exist_ok=True)

    def loop(config):
        import os as _os

        import numpy as _np

        from ray_tpu import train as _train

        start = 0
        prev = _train.get_checkpoint()
        if prev is not None:
            start = int(prev.to_pytree()["step"]) + 1
        open(_os.path.join(config["attempts"], f"start_{start}"), "w").write("x")
        import jax.numpy as _jnp

        for step in range(start, 6):
            _train.report(
                {"step": step, "resumed_from": start},
                checkpoint=ckpt.ShardedState(
                    {"step": _np.int64(step), "w": _jnp.full((4,), float(step))}
                ),
            )
            if step == 3 and start == 0:
                # First attempt parks here until the controller is killed.
                time.sleep(600)

    result_box = {}

    def fit():
        result_box["result"] = DataParallelTrainer(
            loop,
            train_loop_config={"attempts": attempts},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="ctrl-kill-train", storage_path=storage,
                failure_config=FailureConfig(max_failures=2),
            ),
        ).fit()

    t = threading.Thread(target=fit, daemon=True)
    t.start()

    # Wait for the first attempt to reach step 3 with checkpoint_3 COMMITTED.
    manifest = os.path.join(storage, "ctrl-kill-train", "checkpoint_000003",
                            "MANIFEST.json")
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline and not os.path.exists(manifest):
        time.sleep(0.2)
    assert os.path.exists(manifest), "checkpoint_3 never committed"

    runner = ray_tpu.get_actor("TRAIN_CONTROLLER:ctrl-kill-train",
                               namespace="_train")
    ctrl_pid = ray_tpu.get(runner.status.remote(), timeout=30)["pid"]
    os.kill(ctrl_pid, signal.SIGKILL)

    t.join(timeout=300)
    assert not t.is_alive(), "driver never got a result after controller kill"
    result = result_box["result"]
    assert result.error is None, result.error
    # The resumed attempt started from the latest committed checkpoint, not 0.
    assert result.metrics["resumed_from"] >= 1
    assert result.metrics["step"] == 5
    starts = sorted(os.listdir(attempts))
    assert "start_0" in starts
    assert any(s != "start_0" for s in starts), "run never resumed"
    tree = result.checkpoint.to_pytree()
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.full((4,), 5.0))


# ------------------------------------------------- replicated-GCS chaos
#
# Quorum-HA contract (docs/fault_tolerance.md): with gcs_replicas=3 the GCS
# primary majority-acks every durable mutation to follower candidates and
# holds a time-bounded lease; SIGKILLing the PRIMARY promotes the most
# caught-up follower within ~2x the lease window, every majority-acked
# record survives, clients fail over transparently inside gcs_call's
# backoff/deadline machinery, and a deposed primary's stragglers are
# epoch-fenced. gcs_replicas=1 (the default) is byte-for-byte the classic
# single-process GCS.


def _wait_new_gcs_primary(head, old_primary_idx, old_epoch, timeout=25.0):
    """(index, status, seconds-to-promotion) of the follower that took over."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        for i in range(len(head.gcs_procs)):
            if i == old_primary_idx:
                continue
            st = head.gcs_candidate_status(i)
            if st and st.get("role") == "primary" and st["epoch"] > old_epoch:
                return i, st, time.monotonic() - t0
        time.sleep(0.1)
    raise AssertionError("no follower promoted itself in time")


def test_serve_traffic_rides_through_gcs_primary_kill(monkeypatch):
    """SIGKILL the GCS *primary* (of 3 candidates) under a deployed serve app
    with live HTTP traffic: a follower promotes within ~2x the lease window,
    every majority-acked KV/actor/serve-target record survives (verified by a
    known key set written immediately before the kill), HTTP responses stay
    token-identical, a fenced old-epoch write is provably rejected, and the
    failover is observable through the control-plane stats report path."""
    import asyncio
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu._private import rpc as rpclib
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RAY_TPU_GCS_REPLICAS", "3")
    monkeypatch.setenv("RAY_TPU_GCS_LEASE_S", "1.5")
    CONFIG._reset()
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    try:
        cluster.connect()
        w = ray_tpu.global_worker()
        assert len(cluster.head.gcs_procs) == 3

        @serve.deployment(num_replicas=2)
        class Echo:
            def pid(self):
                return os.getpid()

            def __call__(self, request):
                p = request.query_params.get("p", "")
                return {"out": f"{p}::{len(p)}"}

        serve.run(Echo.bind(), name="gcs-ha-chaos", route_prefix="/")
        port = serve.get_proxy_port()

        def ask(p, timeout=10):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/?p={p}", timeout=timeout
            ) as r:
                return json.loads(r.read())["out"]

        prompts = [f"prompt-{i}" for i in range(4)]
        baseline = {p: ask(p) for p in prompts}
        pid_handle = serve.DeploymentHandle("gcs-ha-chaos", "Echo", "pid")
        pids_before = sorted(pid_handle.broadcast())
        assert len(pids_before) == 2

        @ray_tpu.remote(name="ha-counter")
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        counter = Counter.remote()
        assert ray_tpu.get(counter.incr.remote(), timeout=60) == 1

        ok_during: list = []
        errors: list = []
        halt = threading.Event()

        def traffic():
            i = 0
            while not halt.is_set():
                p = prompts[i % len(prompts)]
                i += 1
                try:
                    ok_during.append((p, ask(p, timeout=5)))
                except Exception as e:  # noqa: BLE001 - tallied below
                    errors.append(repr(e))
                time.sleep(0.05)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        time.sleep(1.0)  # warm: routes cached, direct connections live

        # The known key set, written (and majority-acked) RIGHT before the
        # kill: every one of these must survive the primary's death.
        for i in range(30):
            w.gcs_kv_put("ha", f"k{i}".encode(), str(i).encode())

        primary_idx = cluster.head.gcs_primary_index()
        old_st = cluster.head.gcs_candidate_status(primary_idx)
        n_before_kill = len(ok_during)
        cluster.head.kill_gcs_candidate(primary_idx)  # SIGKILL the primary

        new_idx, new_st, promote_s = _wait_new_gcs_primary(
            cluster.head, primary_idx, old_st["epoch"])
        lease_s = CONFIG.gcs_lease_s
        # ~2x the lease window: one window of silence detection + the
        # election round (+ scheduler slack for the subprocess probes).
        assert promote_s <= 2.0 * lease_s + 2.0, (
            f"promotion took {promote_s:.2f}s with lease {lease_s}s")

        # Every majority-acked record survives, read through the client's
        # transparent failover path.
        for i in range(30):
            assert w.gcs_kv_get("ha", f"k{i}".encode()) == str(i).encode(), (
                f"majority-acked key k{i} lost in failover")
        # Actor table survived (replicated spec + raylet re-report)...
        h = ray_tpu.get_actor("ha-counter")
        assert ray_tpu.get(h.incr.remote(), timeout=120) == 2
        # ...and so did the serve controller's target state.
        assert "gcs-ha-chaos" in serve.status()

        # A fenced old-primary straggler is provably rejected: an append
        # stamped with the dead primary's epoch bounces off the quorum.
        async def fenced_write():
            conn = await rpclib.connect(
                *cluster.head.gcs_addrs[new_idx], name="fence-probe")
            try:
                return await conn.call(
                    "repl_append", old_st["epoch"],
                    [(new_st["seq"] + 1,
                      ("put", "kv", ("ha", b"fenced"), b"x"))],
                    primary_idx,
                )
            finally:
                await conn.close()

        reply = asyncio.run(fenced_write())
        assert reply["ok"] is False and reply["promised"] > old_st["epoch"]
        assert w.gcs_kv_get("ha", b"fenced") is None

        time.sleep(1.0)
        halt.set()
        t.join(timeout=30)

        # Traffic kept flowing across the failover window, token-identical.
        assert len(ok_during) - n_before_kill >= 5, (
            f"only {len(ok_during) - n_before_kill} requests succeeded "
            f"through the failover ({len(errors)} errors: {errors[:3]})"
        )
        for p, out in ok_during:
            assert out == baseline[p], f"divergent response for {p!r}"
        post = {p: ask(p, timeout=30) for p in prompts}
        assert post == baseline
        assert sorted(pid_handle.broadcast()) == pids_before, (
            "failover restarted live serve replicas")

        # Observability rides the report path ONLY: calling it surfaces the
        # store/replication series (PR 9 leaksan deadlock lesson).
        from ray_tpu.util import metrics as util_metrics
        from ray_tpu.util.state import control_plane_stats

        stats = control_plane_stats()
        assert stats["repl"]["role"] == "primary"
        assert stats["repl"]["failovers"] >= 1
        assert stats["store"]["appends"] > 0
        names = {m["name"] for m in util_metrics.collect_all()}
        for name in ("gcs_store_append_seconds", "gcs_store_log_bytes",
                     "gcs_store_compactions_total", "gcs_repl_lag_records",
                     "gcs_failovers_total"):
            assert name in names, name
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()
        CONFIG._reset()


def test_train_run_rides_through_gcs_primary_kill(tmp_path, monkeypatch):
    """SIGKILL the GCS *primary* mid-train (3 candidates, NO restart): the
    promoted follower takes over the control plane, workers keep stepping,
    and the run completes with a result bitwise-equal to an undisturbed
    run."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import DataParallelTrainer, RunConfig, ScalingConfig

    monkeypatch.setenv("RAY_TPU_GCS_REPLICAS", "3")
    monkeypatch.setenv("RAY_TPU_GCS_LEASE_S", "1.5")
    CONFIG._reset()
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "env_vars": _NODE_ENV})
    marker = str(tmp_path / "mid_run")
    try:
        cluster.connect()

        def loop(config):
            from ray_tpu import train as _train

            total = 0.0
            for step in range(24):
                total += float((step * 7 + 3) % 11) * 0.5
                if step == 3:
                    open(config["marker"], "w").write("x")
                time.sleep(0.25)
                _train.report({"step": step, "total": total})

        result_box = {}

        def fit():
            result_box["result"] = DataParallelTrainer(
                loop,
                train_loop_config={"marker": marker},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(
                    name="gcs-ha-train", storage_path=str(tmp_path / "storage")
                ),
            ).fit()

        t = threading.Thread(target=fit, daemon=True)
        t.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not os.path.exists(marker):
            time.sleep(0.1)
        assert os.path.exists(marker), "run never reached mid-flight"

        primary_idx = cluster.head.gcs_primary_index()
        old_st = cluster.head.gcs_candidate_status(primary_idx)
        cluster.head.kill_gcs_candidate(primary_idx)
        # The dead candidate is NOT restarted: the promoted follower owns the
        # control plane for the rest of the run.
        _wait_new_gcs_primary(cluster.head, primary_idx, old_st["epoch"])

        t.join(timeout=240)
        assert not t.is_alive(), "trainer did not finish after primary kill"
        result = result_box["result"]
        assert result.error is None, result.error
        expected = 0.0
        for step in range(24):
            expected += float((step * 7 + 3) % 11) * 0.5
        # Bitwise-equal to an undisturbed run: same float accumulation order.
        assert result.metrics["total"] == expected
        assert result.metrics["step"] == 23
    finally:
        cluster.shutdown()
        CONFIG._reset()


def test_single_candidate_gcs_mode_unchanged(monkeypatch):
    """gcs_replicas=1 (set explicitly) is today's behavior: ONE GCS process
    over the classic store dir, reporting itself primary with no quorum
    machinery, and the restart-recovery path works exactly as before."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RAY_TPU_GCS_REPLICAS", "1")
    CONFIG._reset()
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1, "env_vars": _NODE_ENV})
    try:
        cluster.connect()
        w = ray_tpu.global_worker()
        assert len(cluster.head.gcs_procs) == 1
        assert os.path.basename(cluster.head.gcs_store_dir) == "gcs_store"
        st = cluster.head.gcs_candidate_status(0)
        assert st["role"] == "primary" and st["replicas"] == 1
        assert st["epoch"] == 0, "single mode must not run the lease protocol"

        w.gcs_kv_put("solo", b"k", b"v1")
        cluster.head.kill_gcs()
        time.sleep(0.5)
        cluster.head.restart_gcs()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if [n for n in ray_tpu.nodes() if n["alive"]]:
                    break
            except Exception:
                time.sleep(0.5)
        assert w.gcs_kv_get("solo", b"k") == b"v1"

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(f.remote(41), timeout=120) == 42
    finally:
        cluster.shutdown()
        CONFIG._reset()


# ---------------------------------------------------------- autopilot chaos

# The autopilot flag + timing knobs must reach the controller process (CONFIG
# reads env per process): tiny hysteresis so pressure resolves in test time.
_AUTOPILOT_ENV = {
    **_NODE_ENV,
    "RAY_TPU_SERVE_AUTOPILOT": "1",
    "RAY_TPU_SERVE_AUTOPILOT_INTERVAL_S": "0.1",
    "RAY_TPU_SERVE_AUTOPILOT_SUSTAIN_TICKS": "2",
    "RAY_TPU_SERVE_AUTOPILOT_UPSCALE_COOLDOWN_S": "0.2",
    "RAY_TPU_SERVE_AUTOPILOT_DOWNSCALE_COOLDOWN_S": "0.5",
    "RAY_TPU_SERVE_AUTOPILOT_COLD_START_GUARD_S": "1.0",
    "RAY_TPU_SERVE_AUTOPILOT_QUEUE_HIGH": "8",
}


def _wait_until(pred, timeout_s=60.0, interval_s=0.2):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(interval_s)
    return None


def _serve_replica_count(app, deployment):
    from ray_tpu import serve

    try:
        st = serve.status()
    except Exception:
        return -1
    return (st.get(app, {}).get("deployments", {})
            .get(deployment, {}).get("num_replicas", 0))


def test_autopilot_scaleup_rides_through_gcs_kill():
    """SIGKILL the GCS in the middle of an autopilot scale-up: the scale-op
    either completes once the GCS returns or rolls back cleanly — and no
    replica PROCESS is orphaned (every pid the deployment ever started is
    either in the final registered replica set or dead)."""
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 4,
                                      "env_vars": _AUTOPILOT_ENV})
    try:
        cluster.connect()

        @ray_tpu.remote
        class Box:
            def __init__(self):
                self._sig = {"queued": 0, "running": 1, "burn_rate": 0.0}
                self._pids = []

            def set_pressure(self, **kw):
                self._sig.update(kw)

            def signals(self):
                return dict(self._sig)

            def note_pid(self, pid):
                self._pids.append(pid)

            def pids(self):
                return list(self._pids)

        box = Box.remote()

        @serve.deployment(autoscaling_config={
            "min_replicas": 1, "max_replicas": 3,
            "target_ongoing_requests": 1e9,
        })
        class Engine:
            def __init__(self, b):
                self._box = b
                ray_tpu.get(b.note_pid.remote(os.getpid()))

            def pid(self):
                return os.getpid()

            def autopilot_signals(self):
                sig = ray_tpu.get(self._box.signals.remote())
                sig["role"] = "engine"
                return sig

            def __call__(self, x):
                return x

        handle = serve.run(Engine.bind(box), name="ap-gcs", route_prefix=None)
        assert handle.remote(1).result(timeout_s=60) == 1

        # Hot pressure, then kill the GCS right as the sustain window (2
        # ticks at 0.25s loop interval) is about to fire the scale-up.
        ray_tpu.get(box.set_pressure.remote(queued=30, burn_rate=3.0))
        time.sleep(0.4)
        cluster.head.kill_gcs()
        time.sleep(3.0)
        cluster.head.restart_gcs()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if [n for n in ray_tpu.nodes() if n["alive"]]:
                    break
            except Exception:
                time.sleep(0.5)

        # Pressure is still hot: the scale-up must COMPLETE once the control
        # plane is back (a rolled-back op re-fires on a later tick).
        assert _wait_until(
            lambda: _serve_replica_count("ap-gcs", "Engine") >= 2,
            timeout_s=90), "scale-up never completed after GCS recovery"
        ray_tpu.get(box.set_pressure.remote(queued=0, running=1,
                                            burn_rate=0.0))
        time.sleep(1.0)

        # No orphans: every pid this deployment ever started is either a
        # currently-registered replica or a dead process.
        pid_handle = serve.DeploymentHandle("ap-gcs", "Engine", "pid")
        registered = set(pid_handle.broadcast())
        started = set(ray_tpu.get(box.pids.remote()))
        orphans = []
        for pid in started - registered:
            try:
                os.kill(pid, 0)
                orphans.append(pid)
            except (ProcessLookupError, PermissionError):
                pass
        assert not orphans, f"orphan replica processes: {orphans}"
        # Registered count agrees with the serve status view (consistency:
        # the op committed; no half-applied target left behind).
        assert len(registered) == _serve_replica_count("ap-gcs", "Engine")
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_autopilot_absorbs_poisson_rate_step_surge():
    """3x Poisson rate step against a single-slot engine: the SLO burn rate
    (measured by the replicas themselves) must trigger an autopilot
    scale-up, and goodput (fraction of requests under the 0.5s SLO) must
    recover within the deadline after the fleet widens."""
    import asyncio
    from collections import deque as _deque

    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6, num_tpus=0, worker_env=_AUTOPILOT_ENV)
    try:

        @serve.deployment(autoscaling_config={
            "min_replicas": 1, "max_replicas": 3,
            "target_ongoing_requests": 1e9,
        })
        class SurgeEngine:
            """One request slot per replica (0.04s service time); queue wait
            shows up as latency, latency breaches show up as burn."""

            def __init__(self):
                self._sem = asyncio.Semaphore(1)
                self._waiting = 0
                self._lat = _deque(maxlen=64)

            def autopilot_signals(self):
                lat = list(self._lat)
                # Burn = breach fraction / error budget (SLO 0.2s in-replica,
                # 1% budget): one sustained breach saturates the signal.
                breaches = sum(1 for x in lat if x > 0.2)
                burn = (breaches / len(lat)) / 0.01 if lat else 0.0
                return {"role": "engine", "queued": self._waiting,
                        "running": 1, "burn_rate": burn}

            async def __call__(self, _x):
                t0 = time.monotonic()
                self._waiting += 1
                async with self._sem:
                    self._waiting -= 1
                    await asyncio.sleep(0.04)
                self._lat.append(time.monotonic() - t0)
                return 0

        handle = serve.run(SurgeEngine.bind(), name="ap-surge",
                           route_prefix=None)
        rng = random.Random(7)
        lock = threading.Lock()
        done = []  # (t_completed, latency_s)
        halt = threading.Event()

        def fire():
            t0 = time.monotonic()
            try:
                handle.remote(0).result(timeout_s=60)
                with lock:
                    done.append((time.monotonic(), time.monotonic() - t0))
            except Exception:
                with lock:
                    done.append((time.monotonic(), float("inf")))

        def traffic(rate_fn):
            while not halt.is_set():
                threading.Thread(target=fire, daemon=True).start()
                time.sleep(rng.expovariate(rate_fn()))

        # Warm phase at 10 rps (utilization 0.4 on one slot), step to 30 rps.
        t_start = time.monotonic()
        step_at = t_start + 2.0

        def rate():
            return 10.0 if time.monotonic() < step_at else 30.0

        t = threading.Thread(target=traffic, args=(rate,), daemon=True)
        t.start()
        try:
            assert _wait_until(
                lambda: _serve_replica_count("ap-surge", "SurgeEngine") >= 2,
                timeout_s=45), "burn rate never triggered a scale-up"
            t_scaled = time.monotonic()

            def goodput_recovered():
                with lock:
                    recent = [lat for (ts, lat) in done
                              if ts > time.monotonic() - 2.0]
                return (len(recent) >= 20
                        and sum(1 for x in recent if x < 0.5) / len(recent)
                        >= 0.7)

            assert _wait_until(goodput_recovered, timeout_s=45), \
                "goodput did not recover after the scale-up"
            assert t_scaled - step_at < 45.0
        finally:
            halt.set()
            t.join(timeout=10)
        time.sleep(0.5)  # let in-flight fire() threads drain
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def test_autopilot_scale_to_zero_and_first_request_cold_start():
    """min_replicas=0 round trip: the deployment drains to ZERO replicas
    when idle, the first request wakes it (handle -> controller wake path),
    completes, and the cold-start guard keeps the fresh replica alive long
    enough to serve before the idle law may retire it again."""
    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0, worker_env=_AUTOPILOT_ENV)
    try:

        @serve.deployment(autoscaling_config={
            "min_replicas": 0, "max_replicas": 2,
            "target_ongoing_requests": 1e9,
        })
        class ColdEngine:
            def autopilot_signals(self):
                return {"role": "engine", "queued": 0, "running": 0,
                        "burn_rate": 0.0}

            def __call__(self, x):
                return x * 2

        handle = serve.run(ColdEngine.bind(), name="ap-cold",
                           route_prefix=None)
        assert _serve_replica_count("ap-cold", "ColdEngine") == 0

        # First request: wake -> spawn -> serve, inside the routing deadline.
        assert handle.remote(21).result(timeout_s=90) == 42
        assert _serve_replica_count("ap-cold", "ColdEngine") == 1

        # Idle past the cold-start guard (1s) + sustain + cooldown: back to 0.
        assert _wait_until(
            lambda: _serve_replica_count("ap-cold", "ColdEngine") == 0,
            timeout_s=90) is not None, "idle deployment never drained to zero"

        # And it wakes AGAIN: scale-to-zero is a cycle, not a one-way door.
        assert handle.remote(4).result(timeout_s=90) == 8
        assert _serve_replica_count("ap-cold", "ColdEngine") >= 1
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
