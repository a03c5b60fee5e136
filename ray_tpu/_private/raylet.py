"""Raylet: the per-node manager process.

Design parity: reference `src/ray/raylet/` — NodeManager (node_manager.h:124) combining the
worker-lease protocol (HandleRequestWorkerLease), worker pool with prestart/reuse
(worker_pool.h:280), local + cluster lease managers with the hybrid scheduling policy
(scheduling/cluster_lease_manager.h, policy/hybrid_scheduling_policy.cc), placement-group
bundle resources (placement_group_resource_manager), and the object manager + plasma store
hosted in the same process (raylet/main.cc:177). Cross-node object transfer follows the
push/pull manager design (object_manager/push_manager.h, pull_manager.h) with chunked reads.

Topology difference from the reference (documented, intentional): workers hold exactly one
connection to their local raylet; all cross-process traffic is routed worker -> raylet
[-> raylet] -> worker rather than direct worker-to-worker gRPC. On TPU pods the data plane
for tensors is ICI via XLA collectives, not the object plane, so the object/control plane
optimizes for simplicity and robustness.
"""

from __future__ import annotations

import asyncio
import os
import signal
import subprocess
import sys
import time
import traceback
from typing import Any

from ray_tpu._private import rpc
from ray_tpu._private.config import CONFIG, bind_host_for, get_node_ip
from ray_tpu._private.ids import ActorID, NodeID, ObjectID, WorkerID
from ray_tpu._private.object_store import SharedObjectStore


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, proc: subprocess.Popen | None, kind: str,
                 env_key: str | None = None, log_path: str | None = None):
        self.worker_id = worker_id
        self.proc = proc
        self.kind = kind  # "worker" | "driver" | "actor"
        self.env_key = env_key  # pip-env hash this worker's interpreter serves
        self.log_path = log_path  # worker stdout/stderr file (death-cause tail)
        self.conn: rpc.Connection | None = None
        self.registered = asyncio.Event()
        self.busy_task: dict | None = None  # currently running normal task spec
        self.inflight_actor_tasks: dict = {}  # task_id -> spec (actor calls in flight)
        self.actor_id: ActorID | None = None
        self.acquired: dict[str, float] = {}
        self.pg_key: tuple | None = None  # bundle the acquisition came from, if any
        self.last_idle = time.monotonic()
        self.started_at = time.monotonic()
        self.task_started_at = 0.0  # dispatch time of busy_task (OOM kill order)
        self.oom_killed: tuple | None = None  # (usage_frac, threshold) when reaped
        self.log_owner: str | None = None  # worker_id hex of current work's owner
        self.direct_addr: tuple[str, int] | None = None  # worker's direct-call server
        self.leased_to: WorkerID | None = None  # owner holding a cached lease

    @property
    def alive(self):
        return self.conn is not None and not self.conn.closed


class PullManager:
    """Prioritized, byte-budgeted admission for remote object pulls.

    Reference: `src/ray/object_manager/pull_manager.h:49` — three priority
    tiers (gets ahead of waits ahead of task args) and an in-flight byte cap
    so a burst of large pulls backpressures instead of blowing the store.
    Admission is FIFO within a tier; one oversized pull is always admitted
    when the manager is idle (progress guarantee)."""

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self.inflight_bytes = 0
        self.inflight_count = 0
        self._seq = 0
        self._waiters: list[tuple] = []  # sorted (priority, seq, size, event)

    def _admissible(self, size: int) -> bool:
        if self.inflight_count == 0:
            return True  # never deadlock on one object larger than the budget
        return self.inflight_bytes + size <= self.budget

    async def admit(self, object_id, size: int, priority: int):
        if self._waiters or not self._admissible(size):
            ev = asyncio.Event()
            self._seq += 1
            entry = (priority, self._seq, size, ev)
            self._waiters.append(entry)
            self._waiters.sort(key=lambda e: (e[0], e[1]))
            while True:
                await ev.wait()
                ev.clear()
                head = self._waiters[0] if self._waiters else None
                if head is entry and self._admissible(size):
                    self._waiters.pop(0)
                    break
                if head is not None and head is not entry:
                    head[3].set()  # misdirected wakeup: forward to the head
                # else: we're head but capacity is short — wait for a release
        self.inflight_bytes += size
        self.inflight_count += 1
        # Chain-admit: room may remain for the next waiter.
        if self._waiters and self._admissible(self._waiters[0][2]):
            self._waiters[0][3].set()

    def release(self, object_id, size: int):
        self.inflight_bytes -= size
        self.inflight_count -= 1
        if self._waiters:
            self._waiters[0][3].set()


class ResourceManager:
    """Reference: LocalResourceManager + placement_group_resource_manager."""

    def __init__(self, total: dict[str, float]):
        self.total = dict(total)
        self.available = dict(total)
        # (pg_id, bundle_index) -> {"reserved": {...}, "available": {...}}
        self.bundles: dict[tuple, dict] = {}

    def feasible(self, demand: dict[str, float], pg_key=None) -> bool:
        pool = self.bundles[pg_key]["reserved"] if pg_key in self.bundles else self.total
        return all(pool.get(r, 0) >= amt for r, amt in demand.items())

    def can_acquire(self, demand: dict[str, float], pg_key=None) -> bool:
        if pg_key is not None:
            bundle = self.bundles.get(pg_key)
            if bundle is None:
                return False
            return all(bundle["available"].get(r, 0) >= amt for r, amt in demand.items())
        return all(self.available.get(r, 0) >= amt for r, amt in demand.items())

    def acquire(self, demand: dict[str, float], pg_key=None) -> bool:
        if not self.can_acquire(demand, pg_key):
            return False
        pool = self.bundles[pg_key]["available"] if pg_key is not None else self.available
        for r, amt in demand.items():
            pool[r] = pool.get(r, 0) - amt
        return True

    def release(self, demand: dict[str, float], pg_key=None):
        if pg_key is not None:
            bundle = self.bundles.get(pg_key)
            if bundle is None:
                return
            pool = bundle["available"]
            cap = bundle["reserved"]
        else:
            pool = self.available
            cap = self.total
        for r, amt in demand.items():
            pool[r] = min(pool.get(r, 0) + amt, cap.get(r, 0))

    def reserve_bundle(self, pg_key, resources: dict[str, float]) -> bool:
        if not all(self.available.get(r, 0) >= amt for r, amt in resources.items()):
            return False
        for r, amt in resources.items():
            self.available[r] -= amt
        self.bundles[pg_key] = {"reserved": dict(resources), "available": dict(resources)}
        return True

    def cancel_bundle(self, pg_key):
        bundle = self.bundles.pop(pg_key, None)
        if bundle is None:
            return
        for r, amt in bundle["reserved"].items():
            self.available[r] = min(self.available.get(r, 0) + amt, self.total.get(r, 0))


class Raylet:
    def __init__(
        self,
        node_id: NodeID,
        gcs_addr: tuple[str, int],
        resources: dict[str, float],
        labels: dict | None = None,
        is_head: bool = False,
        session_dir: str = "/tmp/ray_tpu",
        object_store_bytes: int | None = None,
        worker_env: dict | None = None,
        node_ip: str | None = None,
    ):
        from ray_tpu._private.gcs_replication import parse_addrs

        self.node_id = node_id
        # All GCS candidate addresses; gcs_addr tracks the CURRENT primary
        # (the one this raylet is registered with).
        self.gcs_addrs = parse_addrs(gcs_addr)
        self.gcs_addr = self.gcs_addrs[0]
        # The address peers dial: never advertise loopback on a multi-host
        # cluster (reference: NodeManager registers node_manager_address, not
        # localhost). Direct worker servers advertise this IP too.
        self.node_ip = node_ip or get_node_ip(self.gcs_addr[0])
        self.is_head = is_head
        self.labels = labels or {}
        self.session_dir = session_dir
        self.worker_env = worker_env or {}
        resources = dict(resources)
        if "memory" not in resources:
            # Every node advertises schedulable memory (bytes) so
            # @remote(memory=N) is feasible however the node was started —
            # init(), `ray_tpu start`, the YAML launcher, or cluster_utils.
            try:
                import psutil

                resources["memory"] = float(int(
                    psutil.virtual_memory().total
                    * (1.0 - CONFIG.object_store_memory_fraction)
                ))
            except Exception:
                pass
        self.resources = ResourceManager(resources)
        if object_store_bytes is None:
            try:
                import psutil

                object_store_bytes = int(
                    psutil.virtual_memory().total * CONFIG.object_store_memory_fraction
                )
            except Exception:
                object_store_bytes = 2 << 30
        self.store = SharedObjectStore(object_store_bytes)

        self.server: rpc.RpcServer | None = None
        self.gcs: rpc.Connection | None = None
        self.port: int | None = None
        self.workers: dict[WorkerID, WorkerHandle] = {}
        self.actors: dict[ActorID, WorkerID] = {}  # actors hosted on this node
        self.actor_addr_cache: dict[ActorID, dict] = {}
        self.task_queue: list[dict] = []  # ready tasks waiting for resources/worker
        self.running: dict[Any, dict] = {}  # task_id -> spec (dispatched)
        self.peer_conns: dict[NodeID, rpc.Connection] = {}
        self.node_view: dict[NodeID, dict] = {}  # cluster view from GCS
        self._sched_wakeup = asyncio.Event()
        self._spawning = 0  # worker spawns awaiting registration
        # TPU chip id -> the process pinned to it (see _free_chips).
        self._chip_holders: dict[int, subprocess.Popen] = {}
        self._pulls_inflight: dict[ObjectID, asyncio.Future] = {}
        # Tasks this raylet forwarded to a peer and is responsible for until the
        # results reach the owner (reference: the owner-side NormalTaskSubmitter
        # retries when a leased node dies). task_id -> {"spec", "target",
        # "missing_since"}. Re-queued (tasks) or failed (actor calls) when the
        # target node dies, so work cannot vanish with a node between the moment
        # it was handed off and the moment its results reached the owner.
        self.delegated: dict[Any, dict] = {}
        # Sealed objects this node holds: id -> (size, owner). Re-reported to the
        # GCS after a GCS restart so the (non-persisted, owner-based) object
        # directory can be rebuilt from the nodes that actually hold the data.
        self._sealed_objects: dict[ObjectID, tuple[int, Any]] = {}
        # Batched object-directory traffic: per-put GCS round trips dominated
        # put cost on small hosts (reference: object directory updates are
        # similarly async/batched via the ray_syncer). Ops keep their relative
        # order (a free must not be applied before the report that precedes it,
        # nor after a re-report that follows it); a seal+free pair inside one
        # window cancels out only when the GCS never learned the object.
        self._obj_ops: list = []  # ordered ("report", ...) | ("free", oid) | None
        self._obj_pending_report: dict[ObjectID, int] = {}  # oid -> _obj_ops index
        self._obj_known: set[ObjectID] = set()  # flushed to GCS, not yet freed
        self._obj_flush_scheduled = False
        self.pull_manager = PullManager(CONFIG.pull_budget_bytes)
        self._authoritative = (float("-inf"), {})  # (asked at, the GCS's node list): composite scheduling
        # pip runtime-env venvs (reference: runtime-env agent + env-keyed worker
        # pools, worker_pool.h:280): env key -> venv python path once built.
        self._venv_python: dict[str, str] = {}
        self._venv_failed: dict[str, tuple[str, float]] = {}  # key -> (err, at)
        self._venv_building: set[str] = set()
        self._env_specs: dict[str, dict] = {}  # env key -> its runtime_env
        self._gcs_connected_at = time.monotonic()  # refreshed on every (re)connect
        self._full_node_view: dict[NodeID, dict] = {}  # incl. alive=False nodes
        self._shutdown = False
        # cgroup-v2 worker isolation (reference src/ray/common/cgroup2/):
        # active only where the cgroupfs is writable; the raylet itself moves
        # into the reserved "system" group so worker memory pressure can't
        # starve the control plane.
        from ray_tpu._private.cgroup import manager_from_env

        self._cgroup = manager_from_env(node_id.hex()[:12])
        if self._cgroup is not None:
            self._cgroup.place_system_process(os.getpid())

    # ------------------------------------------------------------------ startup

    async def start(self, port: int = 0):
        self.server = rpc.RpcServer(lambda conn: self)
        await self.server.start(host=bind_host_for(self.node_ip), port=port)
        self.port = self.server.port
        await self._connect_gcs()
        loop = asyncio.get_running_loop()
        loop.create_task(self._heartbeat_loop())
        loop.create_task(self._scheduler_loop())
        loop.create_task(self._idle_reaper_loop())
        loop.create_task(self._log_monitor_loop())
        loop.create_task(self._memory_monitor_loop())
        return self

    async def _connect_gcs(self, deadline_s: float = 60.0):
        """Connect (or reconnect) to the GCS PRIMARY, register, and sync
        hosted state.

        Retries while the GCS is down: the control plane can restart (or fail
        over to another candidate) independently of raylets (reference: GCS
        clients buffer+retry during GCS downtime). With a replicated GCS the
        probe walks the candidate list, following NOT_PRIMARY redirects until
        the lease holder answers."""
        deadline = time.monotonic() + deadline_s
        hint = None
        i = 0
        while True:
            addr = tuple(hint) if hint else self.gcs_addrs[i % len(self.gcs_addrs)]
            hint = None
            i += 1
            try:
                conn = await rpc.connect(
                    *addr, handler=self, name="raylet->gcs"
                )
            except OSError:
                if self._shutdown or time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.5)
                continue
            try:
                st = await conn.call("repl_status", timeout=5.0)
            except rpc.RpcError:
                st = None
            if st is None or st.get("role") != "primary":
                hint = (st or {}).get("primary")
                try:
                    await conn.close()
                except Exception:
                    pass  # probe conn teardown; the retry loop owns recovery
                if self._shutdown or time.monotonic() > deadline:
                    raise rpc.ConnectionLost(
                        f"no GCS primary reachable at {self.gcs_addrs}")
                if not hint:
                    await asyncio.sleep(0.3)
                continue
            try:
                await self._register_with_gcs(conn)
            except rpc.ConnectionLost as e:
                # Role flipped (or the primary died) between the probe and the
                # registration sequence: follow any redirect hint and retry.
                hint = getattr(e, "primary", None)
                try:
                    await conn.close()
                except Exception:
                    pass  # half-registered conn teardown; loop retries anyway
                if self._shutdown or time.monotonic() > deadline:
                    raise
                if not hint:
                    await asyncio.sleep(0.3)
                continue
            self.gcs = conn
            self.gcs_addr = addr
            break
        # Armed only after full registration: a half-registered conn that
        # dies mid-sequence is retried here, not by a racing reconnect task.
        self.gcs.on_close(self._on_gcs_lost)
        # Delegation-recovery grace starts now: peers need time to re-register
        # with a restarted GCS before their absence can be read as death.
        self._gcs_connected_at = time.monotonic()

    async def _register_with_gcs(self, conn):
        await conn.call(
            "register_node",
            self.node_id,
            (self.node_ip, self.port),
            self.resources.total,
            self.labels,
            self.is_head,
        )
        # Actor state changes invalidate the local address cache (restart support).
        await conn.call("subscribe", "actors")
        await conn.call("subscribe", "nodes")
        hosted = {}
        for actor_id, worker_id in self.actors.items():
            h = self.workers.get(worker_id)
            hosted[actor_id] = {
                "worker_id": worker_id,
                "direct_addr": h.direct_addr if h is not None else None,
            }
        await conn.call(
            "sync_node_state",
            self.node_id,
            hosted,
            [(oid, sz, owner) for oid, (sz, owner) in self._sealed_objects.items()],
            list(self.resources.bundles.keys()),
        )

    def _on_gcs_lost(self, conn):
        if self._shutdown:
            return
        asyncio.get_running_loop().create_task(self._reconnect_gcs())

    async def _reconnect_gcs(self):
        # Retry indefinitely: a raylet must rejoin whenever the GCS comes back,
        # however long the outage (a bounded attempt would leave a zombie node).
        while not self._shutdown:
            try:
                await self._connect_gcs(deadline_s=60.0)
                return
            except Exception:
                await asyncio.sleep(1.0)

    def _pending_demand(self) -> dict:
        """Aggregate resources of queued-but-unplaceable work (autoscaler signal)."""
        demand: dict[str, float] = {}
        for spec in self.task_queue:
            for r, amt in (spec.get("resources") or {}).items():
                demand[r] = demand.get(r, 0.0) + float(amt)
        return demand

    async def _heartbeat_loop(self):
        while not self._shutdown:
            try:
                await self.gcs.call(
                    "heartbeat", self.node_id, self.resources.available,
                    self._pending_demand(),
                )
                nodes = await self.gcs.call("get_nodes")
                self.node_view = {n["node_id"]: n for n in nodes if n["alive"]}
                self._full_node_view = {n["node_id"]: n for n in nodes}
                await self._check_delegations()
            except rpc.NotPrimaryError:
                # Our candidate was deposed but its socket survived: close it
                # so the on_close path re-probes the candidate list and
                # re-registers with the new primary.
                try:
                    await self.gcs.close()
                except Exception:
                    pass  # already-dead conn; on_close reconnect still fires
            except rpc.RpcError:
                pass
            await asyncio.sleep(CONFIG.heartbeat_interval_s)

    async def _check_delegations(self):
        """Backstop for a missed node-removal pubsub event.

        A target the GCS affirmatively marks dead (alive=False) is recovered at
        once. A target merely *absent* from the view gets a longer grace — after
        a GCS restart, get_nodes only lists re-registered raylets, so a slow
        peer must not be treated as dead (that would duplicate normal tasks and
        spuriously fail in-flight actor calls against a live node)."""
        now = time.monotonic()
        full_view = getattr(self, "_full_node_view", {})
        in_reconnect_grace = now - self._gcs_connected_at < 4 * CONFIG.heartbeat_interval_s
        dead_targets = set()
        for entry in self.delegated.values():
            target = entry["target"]
            if target in self.node_view:
                entry["missing_since"] = None
            elif target in full_view:  # present but alive=False: confirmed dead
                dead_targets.add(target)
            elif in_reconnect_grace:
                entry["missing_since"] = None
            elif entry["missing_since"] is None:
                entry["missing_since"] = now
            elif now - entry["missing_since"] > 2 * CONFIG.heartbeat_interval_s:
                dead_targets.add(target)
        for target in dead_targets:
            await self._recover_delegated(target)

    async def _idle_reaper_loop(self):
        while not self._shutdown:
            await asyncio.sleep(10)
            # Reclaim arena blocks of direct-path puts whose writer died between
            # alloc and seal (no raylet create record exists for them).
            srv = getattr(self.store, "_srv", None)
            if srv is not None:
                try:
                    srv.reap_stale_allocated(60_000)
                except Exception:
                    pass  # reaping is advisory; the next sweep retries
            now = time.monotonic()
            idle = [
                w
                for w in self.workers.values()
                if w.kind == "worker"
                and w.busy_task is None
                and w.actor_id is None
                and w.leased_to is None
                and w.alive
                and now - w.last_idle > CONFIG.idle_worker_kill_s
            ]
            # Keep a small warm pool.
            for w in idle[2:]:
                await self._kill_worker(w)

    # ------------------------------------------------------------------ peers

    async def _peer(self, node_id: NodeID) -> rpc.Connection | None:
        conn = self.peer_conns.get(node_id)
        if conn is not None and not conn.closed:
            return conn
        info = self.node_view.get(node_id)
        if info is None:
            try:
                nodes = await self.gcs.call("get_nodes")
                self.node_view = {n["node_id"]: n for n in nodes if n["alive"]}
            except rpc.RpcError:
                return None
            info = self.node_view.get(node_id)
            if info is None:
                return None
        host, port = info["address"]
        try:
            # raylint: disable=RL902 (one-shot per-peer dial, memoized in peer_conns above; the steady-state scheduling loop never reaches it)
            conn = await rpc.connect(host, port, handler=self, name=f"raylet->{node_id.hex()[:8]}")
        except OSError:
            return None
        self.peer_conns[node_id] = conn
        return conn

    # ------------------------------------------------------------------ worker pool

    def _free_chips(self, n_tpu: float) -> list[int] | None:
        """Ids of `n_tpu` chips that no live process holds, or None while too few
        are free. A chip is free again when the process that was given it has
        exited, which is when libtpu lets go of it: there is no release call.
        Raises ValueError for a demand no process can be pinned to."""
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager

        if n_tpu != int(n_tpu):
            raise ValueError(
                f"an actor asked for {n_tpu} TPU: a chip belongs to one process, "
                "ask for whole chips")
        on_host = int(self.resources.total.get("TPU", 0))
        TPUAcceleratorManager.chip_bounds(int(n_tpu), on_host)
        self._chip_holders = {c: p for c, p in self._chip_holders.items()
                              if p.poll() is None}
        free = [c for c in range(on_host) if c not in self._chip_holders]
        return free[:int(n_tpu)] if len(free) >= n_tpu else None

    def _spawn_worker(self, kind: str = "worker", python_exe: str | None = None,
                      env_key: str | None = None,
                      chips: list[int] | None = None) -> WorkerHandle:
        """Start a worker process. `chips` are the TPU chips it was granted
        (`_free_chips`): it is pinned to them. A worker that was granted none, on
        a host that has chips, is held to the CPU backend, so it cannot take a
        chip from the worker that was granted it. Pooled task workers are spawned
        before they are leased and are therefore never granted chips: TPU work
        runs in actors (ROADMAP, Design D9)."""
        worker_id = WorkerID.from_random()
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        # The whole id: ids of one process share their leading bytes (seed + counter),
        # so a prefix names one file for every worker of the node.
        log_path = os.path.join(log_dir, f"worker-{worker_id.hex()}.log")
        out = open(log_path, "wb")
        env = dict(os.environ)
        env.update(self.worker_env)
        chips_on_host = int(self.resources.total.get("TPU", 0))
        if chips:
            from ray_tpu.accelerators.tpu import TPUAcceleratorManager

            TPUAcceleratorManager.set_visible_chips(
                chips, env, chips_on_host=chips_on_host)
        elif chips_on_host and "JAX_PLATFORMS" not in self.worker_env:
            env["JAX_PLATFORMS"] = "cpu"
        from ray_tpu._private.node import _package_pythonpath

        env["PYTHONPATH"] = _package_pythonpath(env.get("PYTHONPATH"))
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        # Workers must agree with this raylet on the node's advertised IP: they
        # bind their direct server per get_node_ip(), and the raylet publishes
        # direct_addr on self.node_ip — a mismatch (e.g. Raylet(node_ip=...)
        # without the env var) would advertise an interface the worker never
        # bound.
        env["RAY_TPU_NODE_IP"] = self.node_ip
        env["RAY_TPU_RAYLET_PORT"] = str(self.port)
        # Full candidate list, current primary first: a worker spawned during
        # a failover window still finds the control plane.
        _gcs_order = [self.gcs_addr] + [
            a for a in self.gcs_addrs if a != self.gcs_addr
        ]
        env["RAY_TPU_GCS_ADDR"] = ",".join(f"{h}:{p}" for h, p in _gcs_order)
        # Unbuffered so crash tracebacks reach the log file even on abrupt death
        # (reference: worker stdout/stderr files tailed by log_monitor.py).
        env["PYTHONUNBUFFERED"] = "1"
        renv = self._env_specs.get(env_key) if env_key else None
        if renv and renv.get("image_uri"):
            # Containerized worker (reference runtime_env/image_uri.py): the
            # engine runs on the host; host network/IPC keeps raylet RPC and
            # the shm object store reachable. PYTHONPATH stays host-side —
            # the image must contain ray_tpu.
            from ray_tpu._private import runtime_env as runtime_env_mod

            passthrough = {k: v for k, v in env.items()
                           if k.startswith("RAY_TPU_") or k == "PYTHONUNBUFFERED"
                           or k in self.worker_env}
            cmd = runtime_env_mod.container_command(
                renv, session_dir=self.session_dir, env=passthrough,
            )
        else:
            cmd = [python_exe or sys.executable,
                   "-m", "ray_tpu._private.default_worker"]
        proc = subprocess.Popen(
            cmd,
            env=env,
            stdout=out,
            stderr=subprocess.STDOUT,
        )
        out.close()  # child owns its duplicated fd; don't leak one per spawn
        for c in chips or ():
            self._chip_holders[c] = proc
        if self._cgroup is not None and not (renv and renv.get("image_uri")):
            # Containerized workers: proc is the engine CLI, not the worker —
            # the engine owns the container's cgroup, placing the client pid
            # would cap the wrong process.
            self._cgroup.place_worker(proc.pid)
        handle = WorkerHandle(worker_id, proc, kind, env_key=env_key, log_path=log_path)
        self.workers[worker_id] = handle
        return handle

    def _find_idle_worker(self, env_key: str | None = None) -> WorkerHandle | None:
        for w in self.workers.values():
            if (
                w.kind == "worker" and w.alive and w.registered.is_set()
                and w.busy_task is None and w.actor_id is None
                and w.leased_to is None and w.env_key == env_key
            ):
                return w
        return None

    # -- pip runtime-env venvs --------------------------------------------

    def _venv_cache_root(self) -> str:
        return os.path.join(self.session_dir, "runtime_envs")

    def _resolve_env_python(self, spec: dict) -> tuple[str | None, bool]:
        """(python_exe, ready). Starts an async venv build on first sight; the
        scheduler retries the task until the env is ready (or fails it)."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        key = runtime_env_mod.env_key(spec.get("runtime_env"))
        if key is None:
            return None, True
        if key in self._venv_python:
            return self._venv_python[key], True
        failed = self._venv_failed.get(key)
        if failed is not None:
            err, at = failed
            if time.monotonic() - at < 60.0:
                raise RuntimeError(f"runtime_env setup failed: {err}")
            # Retry window: a transient failure (wheel house mid-populate, disk
            # pressure) must not poison the env forever.
            self._venv_failed.pop(key, None)
        if key not in self._venv_building:
            self._venv_building.add(key)
            loop = asyncio.get_running_loop()
            renv = spec["runtime_env"]
            self._env_specs[key] = renv

            def build():
                if "conda" in renv:
                    return runtime_env_mod.ensure_conda_env(
                        renv, self._venv_cache_root()
                    )
                if "image_uri" in renv:
                    # No python to build — just fail fast here when no
                    # container engine exists on this node (the spawn would
                    # otherwise die repeatedly and opaquely).
                    runtime_env_mod.container_command(
                        renv, session_dir=self.session_dir, env={}
                    )
                    return None
                return runtime_env_mod.ensure_pip_env(renv, self._venv_cache_root())

            fut = loop.run_in_executor(None, build)

            def done(f):
                self._venv_building.discard(key)
                try:
                    self._venv_python[key] = f.result()
                except Exception as e:  # noqa: BLE001
                    self._venv_failed[key] = (str(e), time.monotonic())
                self._sched_wakeup.set()

            fut.add_done_callback(done)  # asyncio future: callback runs on the loop
        return None, False

    def _maybe_spawn_worker(self, env_key: str | None = None,
                            python_exe: str | None = None):
        """Background worker prestart. Bounded to the node's CPU slots plus slack
        under normal load, but when EVERY task worker is busy (e.g. nested
        zero-resource tasks whose parents block in get()), the pool may grow past
        the cap one spawn at a time — otherwise a parent waiting on a child that
        can never get a worker deadlocks the node."""
        cap = max(4, int(self.resources.total.get("CPU", 1))) + 2
        # Registered only: handles for in-flight spawns are already in
        # self.workers and would otherwise double-count against the cap
        # alongside self._spawning.
        task_workers = [
            w for w in self.workers.values()
            if w.kind == "worker" and w.alive and w.actor_id is None
            and w.registered.is_set()
        ]
        if env_key is not None:
            # Env-keyed pool: vanilla idle workers cannot serve this task, so the
            # vanilla cap must not block the spawn; bound the keyed pool itself.
            keyed = [w for w in task_workers if w.env_key == env_key]
            if any(w.busy_task is None for w in keyed):
                return  # an idle keyed worker exists; dispatch will find it
            # Count in-flight spawns against the keyed bound too: the 20ms
            # dispatch poll must not stack duplicate spawns while the first
            # keyed worker is still registering.
            if len(keyed) + self._spawning >= max(2, cap // 2) or self._spawning >= 4:
                return
            self._spawning += 1
            handle = self._spawn_worker(python_exe=python_exe, env_key=env_key)
            self._await_registration(handle)
            return
        all_busy = all(w.busy_task is not None for w in task_workers)
        over_cap = len(task_workers) + self._spawning >= cap
        if over_cap and not (all_busy and self._spawning == 0):
            return
        if self._spawning >= 4:
            return
        self._spawning += 1
        handle = self._spawn_worker(python_exe=python_exe, env_key=env_key)
        self._await_registration(handle)

    def _await_registration(self, handle: WorkerHandle):
        async def wait_registered():
            try:
                await asyncio.wait_for(
                    handle.registered.wait(), CONFIG.worker_register_timeout_s
                )
                self._sched_wakeup.set()
            except asyncio.TimeoutError:
                await self._kill_worker(handle)
            finally:
                self._spawning -= 1

        asyncio.get_running_loop().create_task(wait_registered())

    async def _kill_worker(self, handle: WorkerHandle):
        self.workers.pop(handle.worker_id, None)
        if handle.conn is not None:
            await handle.conn.close()
        if handle.proc is not None:
            try:
                handle.proc.terminate()
            except Exception:
                pass

    async def _death_cause(self, handle: WorkerHandle, base: str) -> str:
        """Structured death cause: exit code / signal + tail of the worker's log.

        Reference: ActorDeathCause (src/ray/protobuf/common.proto) attaches the
        why to actor death instead of a bare "actor died".
        """
        rc = None
        if handle.proc is not None:
            for _ in range(10):  # give the OS up to ~1s to reap the exit status
                rc = handle.proc.poll()
                if rc is not None:
                    break
                await asyncio.sleep(0.1)
        cause = base
        if handle.oom_killed is not None:
            frac, threshold = handle.oom_killed
            cause = (
                f"{base}: killed by the node memory monitor (memory usage "
                f"{frac:.2f} > threshold {threshold:.2f})"
            )
        if rc is not None:
            if rc < 0:
                try:
                    signame = signal.Signals(-rc).name
                except ValueError:
                    signame = f"signal {-rc}"
                cause += f" (killed by {signame})"
            else:
                cause += f" (exit code {rc})"
        tail = self._tail_log(handle.log_path)
        if tail:
            cause += f"; last lines of {os.path.basename(handle.log_path)}:\n{tail}"
        return cause

    @staticmethod
    def _tail_log(log_path: str | None, max_bytes: int = 4096, max_lines: int = 20) -> str:
        if not log_path:
            return ""
        try:
            with open(log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - max_bytes))
                data = f.read().decode("utf-8", "replace")
        except OSError:
            return ""
        lines = [ln for ln in data.splitlines() if ln.strip()]
        return "\n".join(lines[-max_lines:])

    async def _memory_monitor_loop(self):
        """OOM defense: kill workers (group-by-owner, retriable first) when node
        memory crosses the threshold, instead of letting the node thrash/die.

        Reference: memory_monitor.h:52 polling + worker_killing_policy_group_by_owner.h:87.
        """
        refresh_ms = CONFIG.memory_monitor_refresh_ms
        if refresh_ms <= 0:
            return
        from ray_tpu._private.memory_monitor import MemoryMonitor, pick_worker_to_kill

        monitor = MemoryMonitor(CONFIG.meminfo_path)
        threshold = CONFIG.memory_usage_threshold
        loop = asyncio.get_running_loop()
        above_since: float | None = None
        while not self._shutdown:
            await asyncio.sleep(refresh_ms / 1000.0)
            # Off the loop: a read of /proc/meminfo blocks for seconds while a worker
            # initialises the TPU runtime (seen: 8 s on a v5e host, PERF.md PR 21), and
            # a raylet that misses node_death_timeout_s of heartbeats is declared dead.
            frac = await loop.run_in_executor(None, monitor.usage_fraction)
            if frac is None or frac < threshold:
                above_since = None
                continue
            now = time.monotonic()
            if above_since is None:
                above_since = now
                continue
            if now - above_since < CONFIG.memory_monitor_min_wait_s:
                continue
            victim = pick_worker_to_kill(list(self.workers.values()))
            if victim is None:
                continue
            victim.oom_killed = (frac, threshold)
            above_since = None  # re-debounce before the next kill
            if victim.leased_to is not None:
                # The raylet holds no spec for leased pushed tasks: hand the
                # lessee the cause so exhausted retries surface OutOfMemoryError
                # instead of a generic crash. A CALL (not notify): the ack
                # guarantees the cause is recorded before the conn-close from
                # the kill races it.
                owner = self.workers.get(victim.leased_to)
                if owner is not None and owner.alive:
                    try:
                        await asyncio.wait_for(
                            owner.conn.call(
                                "lease_oom",
                                {"worker_id": victim.worker_id,
                                 "cause": f"killed by the node memory monitor "
                                          f"(memory usage {frac:.2f} > "
                                          f"threshold {threshold:.2f})"},
                            ),
                            2.0,
                        )
                    except Exception:
                        pass  # event publish is advisory; the kill proceeds regardless
            await self._kill_worker(victim)

    async def _log_monitor_loop(self):
        """Tail every worker's log file and publish new lines to the driver.

        Reference: python/ray/_private/log_monitor.py streams per-worker
        stdout/stderr files back to the driver via GCS pubsub.
        """
        offsets: dict[str, int] = {}  # log_path -> bytes already shipped
        while not self._shutdown:
            await asyncio.sleep(0.5)
            for handle in list(self.workers.values()):
                path = handle.log_path
                if not path or handle.kind == "driver":
                    continue
                try:
                    size = os.path.getsize(path)
                except OSError:
                    continue
                off = offsets.get(path, 0)
                if size <= off:
                    continue
                try:
                    with open(path, "rb") as f:
                        f.seek(off)
                        chunk = f.read(min(size - off, 256 * 1024))
                except OSError:
                    continue
                # Ship whole lines only; hold a trailing partial line for later —
                # unless the window is full with no newline (one giant line):
                # ship it truncated and advance, or the tail would stall forever.
                cut = chunk.rfind(b"\n")
                if cut < 0:
                    if len(chunk) < 256 * 1024:
                        continue
                    offsets[path] = off + len(chunk)
                    text = chunk.decode("utf-8", "replace") + "...[line truncated]"
                else:
                    offsets[path] = off + cut + 1
                    text = chunk[:cut].decode("utf-8", "replace")
                lines = [ln for ln in text.splitlines() if ln.strip()]
                if not lines:
                    continue
                owner = getattr(handle, "log_owner", None)
                msg = {
                    "kind": handle.kind,
                    "pid": handle.proc.pid if handle.proc else None,
                    "node": self.node_id.hex(),
                    "worker": handle.worker_id.hex(),  # log-viewer identity
                    "owner": owner,  # driver scoping: worker_id hex of work's owner
                    "lines": lines[:200],
                }
                try:
                    await self.gcs.notify("publish_worker_logs", msg)
                except Exception:
                    pass  # GCS briefly unreachable: lines ship on the next poll
            # Drop offsets of files whose workers are gone (bounded memory).
            live = {h.log_path for h in self.workers.values() if h.log_path}
            for path in list(offsets):
                if path not in live:
                    offsets.pop(path)

    def _on_worker_lost(self, handle: WorkerHandle):
        """Worker connection dropped: fail or retry its in-flight work."""
        self.workers.pop(handle.worker_id, None)
        if self._cgroup is not None and handle.proc is not None:
            self._cgroup.remove_worker(handle.proc.pid)
        if handle.acquired:
            self.resources.release(handle.acquired, handle.pg_key)
            handle.acquired = {}
            handle.pg_key = None
            handle.leased_to = None
        # A dying owner's cached leases must not strand workers (reference:
        # leases are tied to the lessee's liveness). The worker may still be
        # executing a pushed task the raylet cannot see (leased tasks never set
        # busy_task here), so returning it to the idle pool would double-book
        # it — kill it instead; _on_worker_lost releases its resources.
        loop = asyncio.get_running_loop()
        for w in list(self.workers.values()):
            if w.leased_to == handle.worker_id:
                w.leased_to = None
                loop.create_task(self._kill_worker(w))
        self._sched_wakeup.set()
        spec = handle.busy_task
        loop = asyncio.get_running_loop()
        if spec is not None:
            handle.busy_task = None
            self.running.pop(spec["task_id"], None)
            # Streaming tasks are not retried: a replay would re-emit items the
            # consumer already took (and rewrite sealed item buffers); fail the
            # stream cleanly instead.
            if spec.get("retries_left", 0) > 0 and spec.get("num_returns") != "streaming":
                spec["retries_left"] -= 1
                self.task_queue.append(spec)
                self._sched_wakeup.set()
            else:
                async def fail_with_cause(spec=spec):
                    await self._fail_task(
                        spec,
                        await self._death_cause(handle, "worker died during execution"),
                        oom=handle.oom_killed is not None,
                    )

                loop.create_task(fail_with_cause())
        if handle.actor_id is not None or handle.inflight_actor_tasks:
            actor_id = handle.actor_id
            inflight = list(handle.inflight_actor_tasks.values())
            handle.inflight_actor_tasks.clear()

            async def report_with_cause():
                cause = await self._death_cause(handle, "actor worker process died")
                if actor_id is not None:
                    await self._report_actor_failure(actor_id, cause)
                # Fail actor calls that were pushed but never completed
                # (caller would hang otherwise).
                for spec in inflight:
                    await self._fail_actor_task(spec, cause)

            if actor_id is not None:
                self.actors.pop(actor_id, None)
            loop.create_task(report_with_cause())

    async def _report_actor_failure(self, actor_id: ActorID, reason: str):
        try:
            await self.gcs.call("actor_failed", actor_id, reason)
        except rpc.RpcError:
            pass

    async def _fail_task(self, spec: dict, reason: str, oom: bool = False):
        from ray_tpu._private import serialization
        from ray_tpu.exceptions import OutOfMemoryError, WorkerCrashedError

        err_cls = OutOfMemoryError if oom else WorkerCrashedError
        err = serialization.dumps(err_cls(f"task {spec.get('name')} failed: {reason}"))
        results = [
            {"object_id": oid, "inline": err, "error": True}
            for oid in spec["return_ids"]
        ]
        await self._route_results_to_owner(spec, results)
        if spec.get("num_returns") == "streaming":
            owner = spec["owner"]
            await self._route_to_worker(
                owner["node_id"], owner["worker_id"], "stream_abort",
                {"task_id": spec["task_id"], "reason": reason},
            )
        await self._settle_delegation(spec)

    # ------------------------------------------------------------------ delegation

    async def _forward_to_peer(self, spec: dict, target: NodeID, method: str = "submit_task") -> bool:
        """Track-then-notify a spec to a peer; untrack if the send fails so a
        never-delivered task is not 'recovered' into a duplicate later."""
        peer = await self._peer(target)
        if peer is None:
            return False
        self._track_delegation(spec, target)
        try:
            await peer.notify(method, spec)
        except rpc.RpcError:
            self.delegated.pop(spec["task_id"], None)
            return False
        return True

    def _track_delegation(self, spec: dict, target: NodeID):
        """Remember a spec forwarded to `target` until its results reach the owner."""
        if spec.get("type") not in ("task", "actor_task"):
            return
        via = spec.setdefault("via", [])
        if self.node_id not in via:
            via.append(self.node_id)
        self.delegated[spec["task_id"]] = {
            "spec": spec, "target": target, "missing_since": None,
        }

    async def _settle_delegation(self, spec: dict):
        """Results reached the routing stage: release every forwarder on the path."""
        for nid in spec.get("via", ()):
            if nid == self.node_id:
                self.delegated.pop(spec["task_id"], None)
                continue
            peer = await self._peer(nid)
            if peer is not None:
                try:
                    await peer.notify("task_settled", spec["task_id"])
                except rpc.RpcError:
                    pass

    async def rpc_task_settled(self, conn, task_id):
        self.delegated.pop(task_id, None)
        return True

    async def _recover_delegated(self, dead: NodeID):
        """The node a task was handed to died: re-queue it here (normal tasks,
        within the retry budget) or fail it to the owner (actor calls)."""
        for task_id, entry in list(self.delegated.items()):
            if entry["target"] != dead:
                continue
            self.delegated.pop(task_id, None)
            spec = entry["spec"]
            if spec["type"] == "actor_task":
                await self._fail_actor_task(spec, "actor's node died with call in flight")
            elif (
                spec.get("retries_left", 0) > 0
                and spec.get("num_returns") != "streaming"
            ):
                spec["retries_left"] -= 1
                self.task_queue.append(spec)
                self._sched_wakeup.set()
            else:
                await self._fail_task(spec, f"node {dead.hex()[:8]} died (retries exhausted)")

    # ------------------------------------------------------------------ scheduling

    def _pg_key(self, spec) -> tuple | None:
        pg = spec.get("placement_group")
        if pg is None:
            return None
        return (pg["pg_id"], pg["bundle_index"])

    async def _scheduler_loop(self):
        """Reference: ClusterLeaseManager::ScheduleAndGrantLeases.

        Each wakeup makes ONE full pass, but a resource shape that failed to
        dispatch is memoized for the pass and later tasks with the same shape are
        skipped without the (await-laden) dispatch attempt — a deep homogeneous
        queue (10k queued 1-CPU tasks) costs one real attempt plus cheap dict
        checks instead of the O(n^2)-awaits rescans that capped bulk-async
        throughput, while heterogeneous queues still get every distinct shape
        tried (no head-of-line starvation).
        """
        while not self._shutdown:
            # Event-driven with a poll fallback: completions/registrations set the
            # wakeup and dispatch IMMEDIATELY; an unconditional sleep here would
            # gate throughput to (idle workers)/(sleep) per second.
            try:
                await asyncio.wait_for(
                    self._sched_wakeup.wait(), timeout=0.02 if self.task_queue else None
                )
            except asyncio.TimeoutError:
                pass
            self._sched_wakeup.clear()
            remaining = []
            queue, self.task_queue = self.task_queue, []
            failed_shapes: set = set()
            for spec in queue:
                shape = self._dispatch_shape(spec)
                if shape in failed_shapes:
                    remaining.append(spec)
                    continue
                try:
                    dispatched = await self._try_dispatch(spec)
                except Exception:
                    # e.g. a peer connection dying mid-notify: the spec stays
                    # queued and the loop survives (an escaping exception after
                    # the queue swap would silently lose every queued task).
                    traceback.print_exc()
                    dispatched = False
                if not dispatched:
                    remaining.append(spec)
                    failed_shapes.add(shape)
            # Work submitted while this pass ran landed in the fresh task_queue.
            self.task_queue = remaining + self.task_queue

    def _dispatch_shape(self, spec: dict) -> tuple:
        """Pass-local memo key: specs with equal shape dispatch-or-fail together.
        Includes the runtime-env key: a pip-env task waiting on its venv must not
        poison the memo for plain tasks with the same resource shape."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        strategy = spec.get("scheduling_strategy") or {}
        # Label/composite selectors join the key for the same reason env_key
        # did: an undispatchable labeled task must not poison the memo for
        # plain tasks of the same resource shape.
        label_key = None
        if strategy.get("labels") or strategy.get("composite"):
            label_key = repr((strategy.get("labels"), strategy.get("composite")))
        return (
            tuple(sorted((spec.get("resources") or {}).items())),
            self._pg_key(spec),
            strategy.get("node_id"),
            label_key,
            runtime_env_mod.env_key(spec.get("runtime_env")),
        )

    def _label_feasible_nodes(self, hard: dict, demand: dict,
                              views: dict | None = None) -> list:
        """Alive peers (from the GCS view) matching a hard label selector with
        the resource shape in their total supply."""
        from ray_tpu.util.scheduling_strategies import match_labels

        out = []
        for node_id, view in (views or self.node_view).items():
            if node_id == self.node_id or not view.get("alive", True):
                continue
            if not match_labels(view.get("labels"), hard):
                continue
            total = view.get("resources_total") or {}
            if all(total.get(r, 0) >= amt for r, amt in demand.items()):
                out.append((node_id, view))
        return out

    async def _authoritative_views(self) -> dict:
        """Current cluster membership straight from the GCS: composite
        resolution must not miss a labeled node whose subscription update is
        still in flight. Asked for at most once a second and kept between:
        the dispatch loop comes here per queued task per pass and must not
        head-of-line block on an RPC each time."""
        asked, views = self._authoritative
        now = time.monotonic()
        if now - asked >= 1.0:
            try:
                nodes = await self.gcs.call("get_nodes")
                views = {v["node_id"]: v for v in nodes}
            except Exception:
                views = self.node_view
            self._authoritative = (now, views)
        return views

    def _composite_choose(self, spec: dict, subs: list,
                          views: dict | None = None) -> dict | None:
        """First sub-strategy that is satisfiable RIGHT NOW (reference shape:
        composite policies over node_label_scheduling_policy.cc). None = no
        sub currently satisfiable (the task stays queued)."""
        from ray_tpu.util.scheduling_strategies import match_labels

        demand = spec.get("resources") or {}
        views = views if views is not None else self.node_view
        for sub in subs:
            sub = sub or {}
            if sub.get("node_id") is not None:
                view = views.get(sub["node_id"])
                if sub["node_id"] == self.node_id or (
                    view is not None and view.get("alive", True)
                ):
                    return sub
                continue
            hard = (sub.get("labels") or {}).get("hard")
            if hard:
                local_ok = match_labels(self.labels, hard) and self.resources.feasible(
                    demand, None
                )
                if local_ok or self._label_feasible_nodes(hard, demand, views):
                    return sub
                continue
            # plain resource scheduling: satisfiable if anyone can ever run it
            if self.resources.feasible(demand, None):
                return sub
            for _nid, view in views.items():
                total = view.get("resources_total") or {}
                if view.get("alive", True) and all(
                    total.get(r, 0) >= amt for r, amt in demand.items()
                ):
                    return sub
        return None

    async def _try_dispatch(self, spec: dict) -> bool:
        demand = spec.get("resources") or {}
        strategy = spec.get("scheduling_strategy")
        views = None  # None => the subscribed node_view
        if strategy and strategy.get("composite"):
            subs = strategy["composite"]
            chosen = self._composite_choose(spec, subs)
            if chosen != (subs[0] or {}):
                # None, or a later sub-strategy: an earlier one was turned down
                # on the subscribed view, which may lag a just-registered
                # labeled node. Resolve again on the GCS's own list.
                views = await self._authoritative_views()
                chosen = self._composite_choose(spec, subs, views)
                if chosen is None:
                    return False  # nothing satisfiable yet: stay queued
            # chosen applies to THIS dispatch only (spec keeps the composite,
            # so forwarded peers and retries re-evaluate against fresh views)
            strategy = dict(chosen) or None
        if strategy and strategy.get("labels"):
            from ray_tpu.util.scheduling_strategies import match_labels

            sel = strategy["labels"]
            hard = sel.get("hard")
            soft = sel.get("soft")
            if hard and not match_labels(self.labels, hard):
                # Must run on a labeled node: forward to a matching peer
                # (soft-preferred), else wait for one to join. Reuse the fresh
                # views when the composite step fetched them — the node that
                # made the sub satisfiable may not be in the subscribed view.
                peers = self._label_feasible_nodes(hard, demand, views)
                if soft:
                    preferred = [
                        p for p in peers if match_labels(p[1].get("labels"), soft)
                    ]
                    peers = preferred or peers
                for node_id, _view in peers:
                    if await self._forward_to_peer(spec, node_id):
                        return True
                return False
            if soft and not match_labels(self.labels, soft):
                # Soft-only preference: route to an idle soft-matching peer if
                # one exists (it will keep the task — its own labels match);
                # otherwise run here.
                for node_id, view in self._label_feasible_nodes(
                    {**(hard or {})}, demand, views
                ):
                    if not match_labels(view.get("labels"), soft):
                        continue
                    avail = view.get("resources_available") or {}
                    if all(avail.get(r, 0) >= amt for r, amt in demand.items()):
                        if await self._forward_to_peer(spec, node_id):
                            return True
                # no idle preferred peer: fall through to local dispatch
            # local node matches (or soft best-effort): normal dispatch
        if strategy and strategy.get("node_id") is not None:
            target = strategy["node_id"]
            if target != self.node_id:
                if await self._forward_to_peer(spec, target):
                    return True
                if not strategy.get("soft"):
                    await self._fail_task(spec, f"affinity node {target} unavailable")
                    return True
                # soft affinity: fall through to normal scheduling
        pg_key = self._pg_key(spec)
        if pg_key is not None and pg_key not in self.resources.bundles:
            # Bundle not on this node: hand off asynchronously (pg readiness can take
            # seconds; never head-of-line block the scheduler loop on it).
            asyncio.get_running_loop().create_task(self._route_pg_task(spec))
            return True
        if not self.resources.feasible(demand, pg_key):
            return await self._spill(spec)
        if not self.resources.can_acquire(demand, pg_key):
            # Feasible but busy; consider spreading if another node is free.
            if await self._maybe_spread(spec):
                return True
            return False
        from ray_tpu._private import runtime_env as runtime_env_mod

        env_key = runtime_env_mod.env_key(spec.get("runtime_env"))
        if env_key is not None:
            try:
                python_exe, ready = self._resolve_env_python(spec)
            except RuntimeError as e:
                await self._fail_task(spec, str(e))
                return True
            if not ready:
                return False  # venv building; wakeup re-dispatches
        else:
            python_exe = None
        worker = self._find_idle_worker(env_key)
        if worker is None:
            # Spawn happens in the BACKGROUND: awaiting a worker's registration
            # inside the dispatch loop would serialize the whole scheduler behind
            # process startup. The task stays queued; registration wakes us.
            self._maybe_spawn_worker(env_key=env_key, python_exe=python_exe)
            return False
        # No await separates can_acquire from here (single-threaded loop), so this
        # acquire cannot fail; it performs the actual bookkeeping.
        if not self.resources.acquire(demand, pg_key):
            return False
        worker.acquired = demand
        worker.pg_key = pg_key
        worker.busy_task = spec
        worker.task_started_at = time.monotonic()
        owner_wid = (spec.get("owner") or {}).get("worker_id")
        worker.log_owner = owner_wid.hex() if hasattr(owner_wid, "hex") else None
        self.running[spec["task_id"]] = spec
        try:
            await worker.conn.notify("push_task", spec)
        except rpc.RpcError:
            self._on_worker_lost(worker)
            return False
        return True

    @staticmethod
    def _spec_hard_labels(spec: dict) -> dict | None:
        strategy = spec.get("scheduling_strategy") or {}
        return (strategy.get("labels") or {}).get("hard") or None

    def _peer_label_ok(self, spec: dict, view: dict) -> bool:
        hard = self._spec_hard_labels(spec)
        if not hard:
            return True
        from ray_tpu.util.scheduling_strategies import match_labels

        return match_labels(view.get("labels"), hard)

    async def _spill(self, spec: dict) -> bool:
        """Task infeasible on this node: find a feasible node and forward (spillback)."""
        demand = spec.get("resources") or {}
        for node_id, info in self.node_view.items():
            if node_id == self.node_id or not self._peer_label_ok(spec, info):
                continue
            if all(info["resources_total"].get(r, 0) >= amt for r, amt in demand.items()):
                if await self._forward_to_peer(spec, node_id):
                    return True
        return False  # keep queued; cluster may gain a node

    async def _maybe_spread(self, spec: dict) -> bool:
        demand = spec.get("resources") or {}
        if not demand:
            return False
        for node_id, info in self.node_view.items():
            if node_id == self.node_id or not self._peer_label_ok(spec, info):
                continue
            avail = info.get("resources_available", {})
            if all(avail.get(r, 0) >= amt for r, amt in demand.items()):
                if await self._forward_to_peer(spec, node_id):
                    return True
        return False

    async def _route_pg_task(self, spec: dict):
        """Off-loop placement-group routing: wait for the PG, then deliver the task to
        its bundle's node (or fail it if the PG can't be placed)."""
        pg = spec["placement_group"]
        idx = pg["bundle_index"]
        for _attempt in range(10):
            try:
                info = await self.gcs.call("pg_wait_ready", pg["pg_id"], 30.0)
            except rpc.RpcError:
                await asyncio.sleep(0.5)
                continue
            if info.get("state") == "DEAD":
                await self._fail_task(spec, "placement group could not be scheduled")
                return
            allocations = info.get("allocations") or []
            if idx >= len(allocations):
                await self._fail_task(spec, f"placement group has no bundle {idx}")
                return
            target = allocations[idx]
            if target is None:
                await asyncio.sleep(0.2)
                continue
            if target == self.node_id:
                # Bundle is (now) local: re-enter the normal queue.
                self.task_queue.append(spec)
                self._sched_wakeup.set()
                return
            if await self._forward_to_peer(spec, target):
                return
            await asyncio.sleep(0.2)
        await self._fail_task(spec, "placement group routing failed")

    # ------------------------------------------------------------------ RPC: workers

    async def rpc_register_worker(self, conn, worker_id: WorkerID, kind: str, pid: int,
                                  direct_port: int | None = None,
                                  direct_bind_host: str | None = None):
        handle = self.workers.get(worker_id)
        if handle is None:
            handle = WorkerHandle(worker_id, None, kind)
            self.workers[worker_id] = handle
        handle.conn = conn
        handle.kind = kind if handle.kind == "worker" and kind == "driver" else handle.kind
        if direct_port:
            # Advertise the node IP only when the worker's bind actually covers
            # it (raylet-spawned workers always do — they inherit
            # RAY_TPU_NODE_IP — but an externally-started driver may have bound
            # loopback while this raylet advertises a routable IP). A loopback
            # direct_addr stays correct for same-host peers; the GCS vets it
            # out of cross-host records.
            covers = direct_bind_host in (None, "0.0.0.0", self.node_ip)
            handle.direct_addr = (
                (self.node_ip, direct_port) if covers else ("127.0.0.1", direct_port)
            )
        handle.registered.set()
        conn.on_close(lambda c: self._on_worker_lost(handle))
        return {"node_id": self.node_id, "store_capacity": self.store.capacity,
                "node_ip": self.node_ip,
                # Native arenas support the workers' zero-RPC put/get fast path.
                "store_arena": getattr(self.store, "_arena_name", None)}

    async def rpc_submit_task(self, conn, spec: dict):
        self.task_queue.append(spec)
        self._sched_wakeup.set()
        return True

    async def rpc_task_done(self, conn, task_id, results: list, extra: dict | None = None,
                            resources_released=True):
        spec = self.running.pop(task_id, None)
        handle = None
        for w in self.workers.values():
            if w.busy_task is not None and w.busy_task["task_id"] == task_id:
                handle = w
                break
        if handle is not None:
            self.resources.release(handle.acquired, handle.pg_key)
            handle.acquired = {}
            handle.pg_key = None
            handle.busy_task = None
            handle.last_idle = time.monotonic()
            self._sched_wakeup.set()
        if spec is not None:
            await self._route_results_to_owner(spec, results, extra)
            await self._settle_delegation(spec)
        return True

    async def _route_results_to_owner(self, spec: dict, results: list,
                                      extra: dict | None = None):
        owner = spec["owner"]
        payload = {"task_id": spec["task_id"], "results": results, **(extra or {})}
        await self._route_to_worker(owner["node_id"], owner["worker_id"], "task_result", payload)

    async def _route_to_worker(self, node_id: NodeID, worker_id: WorkerID, method: str, payload):
        if node_id == self.node_id:
            handle = self.workers.get(worker_id)
            if handle is not None and handle.alive:
                try:
                    await handle.conn.notify(method, payload)
                except rpc.RpcError:
                    pass
            return
        peer = await self._peer(node_id)
        if peer is not None:
            try:
                await peer.notify("route", worker_id, method, payload)
            except rpc.RpcError:
                pass

    async def rpc_route(self, conn, worker_id: WorkerID, method: str, payload):
        handle = self.workers.get(worker_id)
        if handle is not None and handle.alive:
            try:
                await handle.conn.notify(method, payload)
            except rpc.RpcError:
                pass
        return True

    async def rpc_route_call(self, conn, worker_id: WorkerID, method: str, payload):
        """Routed request that needs an answer (e.g. inline-object fetch from owner)."""
        handle = self.workers.get(worker_id)
        if handle is None or not handle.alive:
            return {"error": "worker_not_found"}
        try:
            return await handle.conn.call(method, payload)
        except rpc.RpcError:
            return {"error": "worker_lost"}

    async def rpc_request_lease(self, conn, resources: dict, runtime_env=None,
                                owner_worker_id: WorkerID | None = None):
        """Grant a cached worker lease to a submitting worker.

        Reference: NormalTaskSubmitter's lease caching
        (task_submission/normal_task_submitter.h:81) — the owner holds the lease
        and pushes same-shape tasks straight to the worker, returning it when the
        local queue drains. The raylet only does resource accounting here; the
        per-task hot path never touches it.
        """
        from ray_tpu._private import runtime_env as runtime_env_mod

        demand = resources or {"CPU": 1}
        if not self.resources.feasible(demand, None):
            return {"ok": False, "infeasible": True}
        env_key = runtime_env_mod.env_key(runtime_env)
        python_exe = None
        if env_key is not None:
            try:
                python_exe, ready = self._resolve_env_python({"runtime_env": runtime_env})
            except RuntimeError as e:
                return {"ok": False, "error": str(e)}
            if not ready:
                return {"ok": False}
        if not self.resources.can_acquire(demand, None):
            return {"ok": False}
        worker = self._find_idle_worker(env_key)
        if worker is None or worker.direct_addr is None:
            self._maybe_spawn_worker(env_key=env_key, python_exe=python_exe)
            return {"ok": False}
        self.resources.acquire(demand, None)
        worker.acquired = demand
        worker.leased_to = owner_worker_id
        owner_hex = owner_worker_id.hex() if hasattr(owner_worker_id, "hex") else None
        worker.log_owner = owner_hex
        return {"ok": True, "worker_id": worker.worker_id,
                "direct_addr": worker.direct_addr}

    async def rpc_release_lease(self, conn, worker_id: WorkerID):
        handle = self.workers.get(worker_id)
        if handle is None or handle.leased_to is None:
            return False
        self.resources.release(handle.acquired, None)
        handle.acquired = {}
        handle.leased_to = None
        handle.log_owner = None
        handle.last_idle = time.monotonic()
        self._sched_wakeup.set()
        return True

    async def rpc_call_worker(self, conn, target: dict, method: str, payload):
        """Worker-to-worker request routed by address (e.g. borrower asking the
        owner to reconstruct a lost object)."""
        node_id, worker_id = target["node_id"], target["worker_id"]
        if node_id == self.node_id:
            return await self.rpc_route_call(conn, worker_id, method, payload)
        peer = await self._peer(node_id)
        if peer is None:
            return {"error": "node_unreachable"}
        try:
            return await peer.call("route_call", worker_id, method, payload)
        except rpc.RpcError:
            return {"error": "node_unreachable"}

    async def rpc_stream_item(self, conn, owner: dict, task_id, index: int, result: dict):
        """Route one streaming-task item to the owning worker."""
        await self._route_to_worker(
            owner["node_id"], owner["worker_id"], "stream_item",
            {"task_id": task_id, "index": index, "result": result},
        )
        return True

    async def rpc_stream_end(self, conn, owner: dict, task_id, count: int):
        await self._route_to_worker(
            owner["node_id"], owner["worker_id"], "stream_end",
            {"task_id": task_id, "count": count},
        )
        return True

    async def rpc_report_borrow(self, conn, object_id: ObjectID, owner: dict, delta: int,
                                borrower=None):
        """Forward a borrower's ref registration/release to the parent worker."""
        await self._route_to_worker(
            owner["node_id"], owner["worker_id"], "borrow_update",
            {"object_id": object_id, "delta": delta, "borrower": borrower},
        )
        return True

    async def rpc_check_borrows(self, conn, node_hex: str, worker_hex: str,
                                object_ids):
        """Borrow-audit holdings probe: ask the worker which of object_ids it
        still borrows. None = no verdict (unreachable); the audit must not
        reconcile on a maybe."""
        if node_hex == self.node_id.hex():
            for wid, handle in self.workers.items():
                if wid.hex() == worker_hex:
                    if not handle.alive:
                        return None
                    try:
                        return await handle.conn.call(
                            "borrow_check", {"object_ids": object_ids},
                            timeout=10.0,
                        )
                    except Exception:
                        return None  # worker unreachable != borrow released; audit treats as unknown
            return None
        target = None
        for nid in self.node_view:
            if nid.hex() == node_hex:
                target = nid
                break
        if target is None:
            return None
        peer = await self._peer(target)
        if peer is None:
            return None
        try:
            return await peer.call("check_borrows", node_hex, worker_hex,
                                   object_ids, timeout=15.0)
        except Exception:
            return None  # peer raylet unreachable: verdict unknown, not not-held

    async def rpc_check_worker_alive(self, conn, node_hex: str, worker_hex: str):
        """Borrow-audit probe: True = alive, False = CONFIRMED dead (its own
        raylet denies it, or the GCS marked its node dead), None = no verdict
        (unreachable/partitioned — the audit must not free on a maybe)."""
        if node_hex == self.node_id.hex():
            for wid, handle in self.workers.items():
                if wid.hex() == worker_hex:
                    return handle.alive
            return False  # our own table is authoritative for our node
        target = None
        for nid in self.node_view:
            if nid.hex() == node_hex:
                target = nid
                break
        if target is None:
            # Not in the live view: only a confirmed-dead record is a verdict.
            for nid, view in self._full_node_view.items():
                if nid.hex() == node_hex and not view.get("alive", True):
                    return False
            return None
        peer = await self._peer(target)
        if peer is None:
            return None  # dial failure != death
        try:
            return await peer.call("check_worker_alive", node_hex, worker_hex,
                                   timeout=5.0)
        except Exception:
            return None  # dial/call failure != death; only a definite answer counts

    # ------------------------------------------------------------------ RPC: object store

    def _queue_object_report(self, object_id: ObjectID, size: int, owner):
        self._obj_pending_report[object_id] = len(self._obj_ops)
        self._obj_ops.append(("report", object_id, self.node_id, size, owner))
        self._schedule_obj_flush()

    def _queue_object_free(self, object_id: ObjectID):
        idx = self._obj_pending_report.pop(object_id, None)
        if idx is not None and object_id not in self._obj_known:
            # Sealed and freed within one window AND never flushed before:
            # the GCS never knew — both ops cancel.
            self._obj_ops[idx] = None
            return
        self._obj_ops.append(("free", object_id))
        self._schedule_obj_flush()

    def _drain_obj_ops(self) -> list:
        ops = [op for op in self._obj_ops if op is not None]
        self._obj_ops.clear()
        self._obj_pending_report.clear()
        for op in ops:
            if op[0] == "report":
                self._obj_known.add(op[1])
            else:
                self._obj_known.discard(op[1])
        return ops

    def _schedule_obj_flush(self):
        if self._obj_flush_scheduled:
            return
        self._obj_flush_scheduled = True

        async def _flush():
            await asyncio.sleep(CONFIG.object_report_flush_s)
            self._obj_flush_scheduled = False
            ops = self._drain_obj_ops()
            if not ops:
                return
            try:
                await self.gcs.notify("object_ops_batch", ops)
            except Exception:
                # GCS down/reconnecting: sealed objects are re-reported by the
                # reconnect sync (sync_node_state); frees are best-effort.
                pass

        asyncio.get_running_loop().create_task(_flush())

    async def rpc_store_create(self, conn, object_id: ObjectID, size: int):
        # Off-loop: under memory pressure create() spills LRU objects to disk,
        # which must not stall scheduling/heartbeats/resolves on the event loop.
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self.store.create, object_id, size)

    async def rpc_store_seal(self, conn, object_id: ObjectID, size: int, owner):
        self.store.seal(object_id)
        self._sealed_objects[object_id] = (size, owner)
        self._queue_object_report(object_id, size, owner)
        return True

    async def rpc_store_ops_batch(self, conn, ops: list):
        """Batched worker store bookkeeping for the zero-RPC direct-arena data
        plane: [("sealed", oid, size, owner) | ("free", oid)], in the order the
        worker performed them. The store itself needs no action for "sealed"
        (the worker sealed in shared memory); only location bookkeeping runs."""
        for op in ops:
            if op[0] == "sealed":
                _, object_id, size, owner = op
                self._sealed_objects[object_id] = (size, owner)
                self._queue_object_report(object_id, size, owner)
            else:
                _, object_id = op
                self.store.free(object_id, eager=True)
                self._sealed_objects.pop(object_id, None)
                self._queue_object_free(object_id)

    async def rpc_store_put_bytes(self, conn, object_id: ObjectID, data: bytes, owner):
        loop = asyncio.get_running_loop()
        name = await loop.run_in_executor(None, self.store.put_bytes, object_id, data)
        self._sealed_objects[object_id] = (len(data), owner)
        self._queue_object_report(object_id, len(data), owner)
        return name

    async def rpc_store_info(self, conn, object_id: ObjectID):
        return self.store.info(object_id)

    async def rpc_store_free(self, conn, object_id: ObjectID):
        # The owner's refcount hit zero: no ObjectRef exists anywhere, so the
        # payload can never be legally read again. Eager eviction returns the
        # block to the freelist immediately (reuse keeps put pages warm) —
        # pinned readers still defer the actual recycle to their release.
        self.store.free(object_id, eager=True)
        self._sealed_objects.pop(object_id, None)
        self._queue_object_free(object_id)
        return True

    async def rpc_evict_object(self, conn, object_id: ObjectID):
        self.store.free(object_id, eager=True)
        self._sealed_objects.pop(object_id, None)
        return True

    async def rpc_read_chunk(self, conn, object_id: ObjectID, offset: int, length: int):
        return self.store.read_bytes(object_id, offset, length)

    async def rpc_store_stats(self, conn):
        stats = self.store.stats()
        stats["pull_inflight_bytes"] = self.pull_manager.inflight_bytes
        return stats

    async def rpc_resolve_object(self, conn, object_id: ObjectID, owner=None, timeout: float = 300.0,
                                 priority: int = 1):
        """Ensure the object is readable on this node.

        Returns {"shm": (name, size)} for store objects or {"inline": bytes} fetched from
        the owner's in-process memory store. Reference: CoreWorker::Get's plasma-provider
        path + PullManager for remote objects.
        """
        deadline = time.monotonic() + timeout
        lost_polls = 0
        unknown_polls = 0
        while True:
            info = self.store.info(object_id)
            if info is not None:
                return {"shm": info}
            inflight = self._pulls_inflight.get(object_id)
            if inflight is not None:
                await inflight
                continue
            loc = None
            got_loc = False
            try:
                loc = await self.gcs.call("object_locations", object_id)
                got_loc = True
            except rpc.RpcError:
                pass
            if loc is not None and not loc["locations"]:
                # The directory knows this object but every node holding a copy is
                # gone: report it lost quickly so the owner can reconstruct from
                # lineage instead of burning the full resolve timeout. Two polls of
                # grace cover a copy in transit between seal and report.
                lost_polls += 1
                if lost_polls >= 2:
                    return {"error": "lost"}
            else:
                lost_polls = 0
            if got_loc and loc is None:
                # The directory has never heard of this object. Location reports
                # are batched, so a fresh seal can be unknown for a window — but
                # a persistently-unknown plasma object means its holder died
                # before its report flushed. Declare it lost so the owner can
                # rebuild from lineage instead of burning the resolve timeout.
                unknown_polls += 1
                if unknown_polls >= 25 and owner is not None:
                    return {"error": "lost"}
            else:
                unknown_polls = 0
            if loc and loc["locations"]:
                fut = asyncio.get_running_loop().create_future()
                self._pulls_inflight[object_id] = fut
                try:
                    ok = await self._pull_object(object_id, loc, priority)
                finally:
                    self._pulls_inflight.pop(object_id, None)
                    fut.set_result(None)
                if ok:
                    continue
            elif owner is not None:
                # Small object living in the owner's memory store.
                reply = await self._fetch_inline_from_owner(object_id, owner)
                if reply is not None:
                    return {"inline": reply}
            if time.monotonic() > deadline:
                return {"error": "timeout"}
            await asyncio.sleep(CONFIG.get_poll_interval_s * 10)

    async def _fetch_inline_from_owner(self, object_id: ObjectID, owner) -> bytes | None:
        node_id, worker_id = owner["node_id"], owner["worker_id"]
        payload = {"object_id": object_id}
        if node_id == self.node_id:
            handle = self.workers.get(worker_id)
            if handle is None or not handle.alive:
                return None
            try:
                reply = await handle.conn.call("fetch_inline", payload)
            except rpc.RpcError:
                return None
        else:
            peer = await self._peer(node_id)
            if peer is None:
                return None
            try:
                reply = await peer.call("route_call", worker_id, "fetch_inline", payload)
            except rpc.RpcError:
                return None
        if isinstance(reply, dict) and reply.get("data") is not None:
            return reply["data"]
        return None

    async def _pull_object(self, object_id: ObjectID, loc: dict,
                           priority: int = 1) -> bool:
        """Pull a remote object under the pull manager's byte budget
        (reference: pull_manager.h:49 — prioritized admission with in-flight
        byte caps so a burst of large pulls cannot exhaust the store)."""
        await self.pull_manager.admit(object_id, loc["size"], priority)
        try:
            return await self._pull_object_now(object_id, loc)
        finally:
            self.pull_manager.release(object_id, loc["size"])

    async def _pull_object_now(self, object_id: ObjectID, loc: dict) -> bool:
        """Chunked-parallel pull from a remote node (reference: PullManager +
        ObjectBufferPool chunked receives). A window of pipelined read_chunk
        requests keeps the wire full instead of paying one RTT per chunk."""
        size = loc["size"]
        for location in loc["locations"]:
            if location["node_id"] == self.node_id:
                continue
            peer = await self._peer(location["node_id"])
            if peer is None:
                continue
            try:
                shm_name = self.store.create(object_id, size)
                from ray_tpu._private.object_store import LocalObjectReader

                chunk = CONFIG.object_store_min_chunk_bytes
                window = max(1, CONFIG.pull_chunk_window)
                reader = LocalObjectReader()
                try:
                    # write_view, NOT read(): this buffer receives the pulled
                    # chunks. read() takes a pinned READ view, which degrades
                    # to a read-only copy on Python < 3.12 — writes would
                    # TypeError (and silently vanish if they didn't).
                    buf = reader.write_view(shm_name, size)
                    sem = asyncio.Semaphore(window)

                    async def fetch(off: int):
                        ln = min(chunk, size - off)
                        async with sem:
                            data = await peer.call("read_chunk", object_id, off, ln)
                        if not data or len(data) != ln:
                            raise IOError(
                                f"short chunk at {off}: {0 if not data else len(data)}"
                                f"/{ln} of {object_id}"
                            )
                        buf[off : off + ln] = data

                    # return_exceptions: every fetch settles before this line
                    # passes, so a failed attempt never leaves orphan tasks
                    # writing into the buffer during the next location's retry.
                    results = await asyncio.gather(
                        *[fetch(o) for o in range(0, size, chunk)],
                        return_exceptions=True,
                    )
                    errs = [r for r in results if isinstance(r, BaseException)]
                    if errs:
                        raise errs[0]
                    del buf
                finally:
                    reader.close()
                self.store.seal(object_id)
                self._sealed_objects[object_id] = (size, loc.get("owner"))
                self._queue_object_report(object_id, size, loc.get("owner"))
                return True
            except Exception:
                traceback.print_exc()
                self.store.free(object_id, eager=True)
        return False

    # ------------------------------------------------------------------ RPC: actors

    async def rpc_create_actor(self, conn, actor_id: ActorID, spec: dict):
        """From GCS: lease a dedicated worker and instantiate the actor."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        demand = dict(spec.get("resources") or {})
        pg_key = self._pg_key(spec)
        # pip runtime env: the actor's worker must run inside the env's venv.
        # Routed through the same single-flight builder as tasks so concurrent
        # creations of the same env never race one cache directory.
        python_exe = None
        if runtime_env_mod.env_key(spec.get("runtime_env")) is not None:
            deadline = time.monotonic() + 600
            while True:
                try:
                    python_exe, ready = self._resolve_env_python(spec)
                except RuntimeError as e:
                    return {"ok": False, "reason": f"runtime_env failed: {e}",
                            "fatal": True}
                if ready:
                    break
                if time.monotonic() > deadline:
                    return {"ok": False, "reason": "runtime_env build timed out",
                            "fatal": True}
                await asyncio.sleep(0.25)
        if not self.resources.acquire(demand, pg_key):
            return {"ok": False, "reason": "resources"}
        chips = None
        if demand.get("TPU"):
            try:
                chips = self._free_chips(demand["TPU"])
            except ValueError as e:
                self.resources.release(demand, pg_key)
                return {"ok": False, "reason": str(e), "fatal": True}
            if chips is None:  # the last holder is still exiting: GCS asks again
                self.resources.release(demand, pg_key)
                return {"ok": False, "reason": "resources"}

        async def cleanup(handle):
            # Detach bookkeeping BEFORE killing so _on_worker_lost (conn-close
            # callback) neither double-releases nor reports a spurious actor death.
            handle.acquired = {}
            handle.pg_key = None
            handle.actor_id = None
            self.resources.release(demand, pg_key)
            await self._kill_worker(handle)

        handle = self._spawn_worker(
            kind="actor", python_exe=python_exe,
            env_key=runtime_env_mod.env_key(spec.get("runtime_env")),
            chips=chips,
        )
        try:
            await asyncio.wait_for(handle.registered.wait(), CONFIG.worker_register_timeout_s)
        except asyncio.TimeoutError:
            # Kill first so _death_cause sees the exit status immediately
            # instead of polling a still-live process for its full wait.
            await cleanup(handle)
            reason = await self._death_cause(handle, "actor worker failed to register")
            return {"ok": False, "reason": reason}
        handle.acquired = demand
        handle.pg_key = pg_key
        try:
            result = await handle.conn.call("init_actor", actor_id, spec, timeout=300)
        except rpc.RpcError as e:
            await cleanup(handle)
            reason = await self._death_cause(handle, f"worker died during init: {e}")
            return {"ok": False, "reason": reason}
        if not result.get("ok"):
            await cleanup(handle)
            # Application error in __init__: retrying cannot help.
            return {"ok": False, "reason": result.get("error", "init failed"), "fatal": True}
        handle.actor_id = actor_id
        if (self._cgroup is not None and handle.proc is not None
                and demand.get("memory")
                and not (spec.get("runtime_env") or {}).get("image_uri")):
            # A declared memory resource becomes a hard per-worker memory.max
            # (native workers only: for containers, proc is the engine CLI).
            self._cgroup.place_worker(handle.proc.pid,
                                      memory_bytes=int(demand["memory"]))
        owner_wid = (spec.get("owner") or {}).get("worker_id")
        handle.log_owner = owner_wid.hex() if hasattr(owner_wid, "hex") else None
        self.actors[actor_id] = handle.worker_id
        return {"ok": True, "worker_id": handle.worker_id,
                "direct_addr": handle.direct_addr}

    async def rpc_submit_actor_task(self, conn, spec: dict):
        """Route an actor method call to the actor's host node/worker."""
        actor_id = spec["actor_id"]
        worker_id = self.actors.get(actor_id)
        if worker_id is not None:
            handle = self.workers.get(worker_id)
            if handle is not None and handle.alive:
                handle.inflight_actor_tasks[spec["task_id"]] = spec
                await handle.conn.notify("push_task", spec)
                return True
            # Actor worker died; report and fall through to error.
            await self._report_actor_failure(actor_id, "actor worker dead at submit")
            await self._fail_actor_task(spec, "actor worker died")
            return False
        addr = await self._actor_address(actor_id)
        if addr is None:
            reason = "actor not found or dead"
            try:  # surface the GCS-recorded death cause, not a bare "dead"
                info = await self.gcs.call("get_actor_info", actor_id)
                if info is not None and info.get("death_cause"):
                    reason = f"actor is dead: {info['death_cause']}"
            except rpc.RpcError:
                pass
            await self._fail_actor_task(spec, reason)
            return False
        if addr["node_id"] == self.node_id:
            handle = self.workers.get(addr["worker_id"])
            if handle is not None and handle.alive:
                handle.inflight_actor_tasks[spec["task_id"]] = spec
                await handle.conn.notify("push_task", spec)
                return True
            await self._fail_actor_task(spec, "actor worker dead")
            return False
        if not await self._forward_to_peer(spec, addr["node_id"], "submit_actor_task"):
            await self._fail_actor_task(spec, "actor node unreachable")
            return False
        return True

    async def _actor_address(self, actor_id: ActorID):
        cached = self.actor_addr_cache.get(actor_id)
        if cached is not None:
            return cached
        info = None
        for _attempt in range(20):  # survive a GCS restart mid-lookup
            try:
                info = await self.gcs.call("wait_actor_alive", actor_id, 60.0)
                break
            except rpc.ConnectionLost:
                await asyncio.sleep(0.5)
            except rpc.RpcError:
                return None
        if info is None:
            return None
        if info is None or info["state"] != "ALIVE":
            return None
        self.actor_addr_cache[actor_id] = info["address"]
        return info["address"]

    async def _fail_actor_task(self, spec: dict, reason: str):
        from ray_tpu._private import serialization
        from ray_tpu.exceptions import ActorDiedError

        err = serialization.dumps(ActorDiedError(spec.get("actor_id"), reason))
        results = [
            {"object_id": oid, "inline": err, "error": True} for oid in spec["return_ids"]
        ]
        await self._route_results_to_owner(spec, results)
        if spec.get("num_returns") == "streaming":
            owner = spec["owner"]
            await self._route_to_worker(
                owner["node_id"], owner["worker_id"], "stream_abort",
                {"task_id": spec["task_id"], "reason": reason},
            )
        await self._settle_delegation(spec)

    async def rpc_actor_task_done(self, conn, spec_owner, task_id, results,
                                  extra: dict | None = None):
        """Actor worker finished a method call; route results to owner."""
        spec = None
        for w in self.workers.values():
            if w.conn is conn:
                spec = w.inflight_actor_tasks.pop(task_id, None)
                break
        await self._route_to_worker(
            spec_owner["node_id"],
            spec_owner["worker_id"],
            "task_result",
            {"task_id": task_id, "results": results, **(extra or {})},
        )
        if spec is not None:
            await self._settle_delegation(spec)
        return True

    async def rpc_kill_actor_worker(self, conn, actor_id: ActorID):
        worker_id = self.actors.pop(actor_id, None)
        if worker_id is None:
            return False
        handle = self.workers.get(worker_id)
        if handle is not None:
            self.resources.release(handle.acquired, handle.pg_key)
            handle.acquired = {}
            handle.pg_key = None
            handle.actor_id = None
            await self._kill_worker(handle)
        return True

    async def rpc_invalidate_actor_cache(self, conn, actor_id: ActorID):
        self.actor_addr_cache.pop(actor_id, None)
        return True

    # ------------------------------------------------------------------ RPC: bundles

    async def rpc_reserve_bundle(self, conn, pg_id, bundle_index, resources):
        return self.resources.reserve_bundle((pg_id, bundle_index), resources)

    async def rpc_cancel_bundle(self, conn, pg_id, bundle_index):
        self.resources.cancel_bundle((pg_id, bundle_index))
        return True

    # ------------------------------------------------------------------ RPC: misc

    async def rpc_publish(self, conn, channel, message):
        """Pubsub fan-in from GCS: actor restarts/deaths and node membership."""
        if channel == "actors":
            view = message.get("actor", {})
            actor_id = view.get("actor_id")
            if actor_id is not None:
                if view.get("state") == "ALIVE" and view.get("address"):
                    self.actor_addr_cache[actor_id] = view["address"]
                else:
                    self.actor_addr_cache.pop(actor_id, None)
        elif channel == "nodes" and message.get("event") == "removed":
            node_id = message["node"]["node_id"]
            self.node_view.pop(node_id, None)
            conn_dead = self.peer_conns.pop(node_id, None)
            if conn_dead is not None:
                await conn_dead.close()
            await self._recover_delegated(node_id)
        return True

    async def rpc_node_stats(self, conn):
        return {
            "node_id": self.node_id,
            "resources_total": self.resources.total,
            "resources_available": self.resources.available,
            "num_workers": len(self.workers),
            "queued_tasks": len(self.task_queue),
            "running_tasks": len(self.running),
            "store": self.store.stats(),
            # Who holds what: the first question of every "why is this node
            # full" investigation (reference: node manager debug state dump).
            "resource_holders": [
                {
                    "worker_id": h.worker_id.hex()[:12],
                    "kind": h.kind,
                    "pid": h.proc.pid if h.proc is not None else None,
                    "chips": [c for c, p in self._chip_holders.items() if p is h.proc],
                    "actor_id": h.actor_id.hex()[:12] if h.actor_id else None,
                    "leased": h.leased_to is not None,
                    "acquired": dict(h.acquired),
                    "pg_key": repr(h.pg_key) if h.pg_key else None,
                }
                for h in self.workers.values() if h.acquired
            ],
            "pg_bundles": {
                repr(k): v["reserved"] for k, v in self.resources.bundles.items()
            },
        }

    async def shutdown(self):
        self._shutdown = True
        # Flush batched object-directory traffic: a clean shutdown must not
        # strand seals/frees in the window (holders that die unreported are
        # covered by the resolve path's unknown-object lost detection).
        ops = self._drain_obj_ops()
        if ops:
            try:
                await self.gcs.notify("object_ops_batch", ops)
            except Exception:
                pass  # GCS down: ops re-drain after the reconnect path replays
        for handle in list(self.workers.values()):
            if handle.kind != "driver":
                await self._kill_worker(handle)
        if self.server is not None:
            await self.server.close()
        self.store.destroy()
        if self._cgroup is not None:
            self._cgroup.teardown()
