"""ServeController: the serve control plane actor.

Design parity: reference `python/ray/serve/_private/controller.py` (:103) +
`application_state.py` + `deployment_state.py` — hold the desired state (apps →
deployments → configs), reconcile replica actors toward it (create missing, kill
excess, replace dead), serve routing tables to handles, and run the autoscaling
policy over replica stats (`autoscaling_policy.py`).
"""

from __future__ import annotations

import asyncio
import math
import time
import traceback
from typing import Any, Dict, List, Optional

from ray_tpu.serve._common import (
    AUTOPILOT_KEY,
    CONTROLLER_KV_NS,
    REGISTRY_KEY,
    TARGET_STATE_KEY,
)


class ServeController:
    """Async actor. One per cluster, named SERVE_CONTROLLER in the serve namespace.

    Durable control plane (docs/fault_tolerance.md): declarative target state
    (app configs, deployment specs, autoscale targets, http options) and the
    replica/proxy registry persist to GCS KV on every mutation. The actor runs
    with max_restarts=-1; a restarted incarnation lazily recovers the persisted
    state on its first method call, probes the registered actors, and RE-ADOPTS
    the ones still alive — live replicas keep serving through a controller death
    or a GCS restart, and reconciliation only replaces what actually died.
    """

    def __init__(self):
        # app -> deployment -> spec dict (blobs + DeploymentConfig)
        self._apps: Dict[str, Dict[str, dict]] = {}
        # app -> deployment -> list of replica ActorHandles
        self._replicas: Dict[str, Dict[str, list]] = {}
        self._versions: Dict[str, int] = {}
        self._loop_started = False
        self._shutting_down = False
        # Durable-state bookkeeping: recovery runs at most once per
        # incarnation (lazily, on the first method call — __init__ runs off
        # the actor's event loop and must not block on KV I/O).
        self._recovered = False
        self._recover_lock = asyncio.Lock()
        self._state_dirty = False
        self._registry_snapshot: Optional[tuple] = None
        # autoscale bookkeeping: (app, dep) -> last scale decision time
        self._last_scale: Dict[tuple, float] = {}
        # health bookkeeping OUTSIDE the spec dicts: redeploys must not reset a
        # live replica's "has been healthy" status or its startup clock.
        # (app, dep) -> {"healthy": set[actor_id], "created": {actor_id: t}}
        self._health: Dict[tuple, dict] = {}
        # Per-node HTTP proxies (reference: one ProxyActor per node, proxy.py):
        # node_id hex -> (actor handle, port). Reconciled against cluster
        # membership in the control loop once ensure_proxies() arms it.
        self._http_options: Optional[dict] = None
        self._proxies: Dict[str, tuple] = {}
        # Serializes proxy reconciliation: concurrent ensure_proxies calls
        # (driver + control loop) must not both create/start the same node's
        # proxy — interleaved starts split the bound-port table.
        self._proxy_lock = asyncio.Lock()
        self._mux_ids: Dict[str, dict] = {}  # "app#dep" -> {actor_id: [model ids]}
        # SLO autopilot (docs/autoscale.md): lazily constructed on the first
        # tick with CONFIG.serve_autopilot on, or recovered from its own KV
        # record. Its targets/cooldowns persist separately from the
        # declarative state so deploy replays cannot clobber them.
        self._autopilot = None
        self._autopilot_last = 0.0
        self._autopilot_wake_ts: Dict[str, float] = {}

    # -- durable control-plane state --------------------------------------
    #
    # Two KV records in CONTROLLER_KV_NS:
    #   TARGET_STATE_KEY — declarative intent (apps/specs/configs/http options):
    #     what the operator asked for; enough to rebuild everything from cold.
    #   REGISTRY_KEY — the replica/proxy actor handles the previous incarnation
    #     created: what exists RIGHT NOW, so recovery adopts live actors
    #     instead of replacing them (replica processes hold warm compiled
    #     models; a cold-start would drop every in-flight request).

    @staticmethod
    def _kv_io(fn):
        """Run a blocking GCS KV op off the actor's event loop."""
        loop = asyncio.get_running_loop()
        return loop.run_in_executor(None, fn)

    async def _ensure_recovered(self):
        if self._recovered:
            return
        async with self._recover_lock:
            if self._recovered:
                return
            await self._recover()  # raylint: disable=RL905 (the recover lock exists precisely to hold callers across this await: nothing may proceed on unrecovered state)
            self._recovered = True
        self._arm_control_loop()

    def _arm_control_loop(self):
        if not self._loop_started:
            # Restarted incarnations get no run_control_loop call from a
            # driver; the loop re-arms off whichever method call (proxy route
            # refresh, handle routing, a redeploy) touched the controller.
            asyncio.get_running_loop().create_task(self.run_control_loop())

    async def _recover(self):
        import cloudpickle

        import ray_tpu
        from ray_tpu.serve._common import async_get

        w = ray_tpu.global_worker()
        state_blob = await self._kv_io(
            lambda: w.gcs_kv_get(CONTROLLER_KV_NS, TARGET_STATE_KEY)
        )
        if state_blob is None:
            return  # fresh control plane: nothing persisted
        state = cloudpickle.loads(state_blob)
        self._apps = state.get("apps") or {}
        self._http_options = state.get("http_options")
        registry_blob = await self._kv_io(
            lambda: w.gcs_kv_get(CONTROLLER_KV_NS, REGISTRY_KEY)
        )
        registry = cloudpickle.loads(registry_blob) if registry_blob else {}
        # Autopilot law state (targets, cooldown wall-clocks, tenant
        # weights): a restarted controller resumes mid-loop — remaining
        # cooldowns are honored, so recovery cannot double-fire a scale
        # decision the previous incarnation just took.
        ap_blob = await self._kv_io(
            lambda: w.gcs_kv_get(CONTROLLER_KV_NS, AUTOPILOT_KEY)
        )
        if ap_blob:
            try:
                from ray_tpu._private.config import CONFIG
                from ray_tpu.serve.autopilot import Autopilot

                self._autopilot = Autopilot.load(
                    cloudpickle.loads(ap_blob),
                    decision_log_cap=CONFIG.serve_autopilot_decision_log)
            except Exception:
                traceback.print_exc()  # corrupt blob: start the loop cold
        self._versions = dict(registry.get("versions") or {})

        # Probe every registered actor CONCURRENTLY; adopt the live ones.
        async def probe(handle):
            try:
                await async_get(handle.ready.remote(), timeout=15)
                return True
            except Exception:
                return False

        candidates: List[tuple] = []  # (kind, app, dep_or_nid, handle, extra)
        for app, deps in (registry.get("replicas") or {}).items():
            for dep, handles in deps.items():
                for h in handles:
                    candidates.append(("replica", app, dep, h, None))
        for nid, (h, port) in (registry.get("proxies") or {}).items():
            candidates.append(("proxy", None, nid, h, port))
        alive = await asyncio.gather(*(probe(c[3]) for c in candidates))
        adopted = 0
        for (kind, app, key, handle, extra), ok in zip(candidates, alive):
            if not ok:
                continue
            adopted += 1
            if kind == "replica":
                self._replicas.setdefault(app, {}).setdefault(key, []).append(handle)
                health = self._health.setdefault((app, key), {
                    "healthy": set(), "created": {},
                })
                # Adopted replicas answered the probe: they are healthy NOW,
                # so a later silence means death, not a startup grace period.
                health["healthy"].add(handle._actor_id)
                health["created"][handle._actor_id] = time.monotonic()
            else:
                self._proxies[key] = (handle, extra)
        # Registry shrank to the adopted survivors: persist the pruned view and
        # bump versions where the set changed so routers refetch.
        for app, deps in (registry.get("replicas") or {}).items():
            for dep, handles in deps.items():
                if len(self._replicas.get(app, {}).get(dep, [])) != len(handles):
                    self._bump(app, dep)
        await self._persist_registry(force=True)
        try:
            from ray_tpu.util.metrics import Counter

            Counter(
                "controller_recoveries_total",
                "control-plane recoveries from persisted state",
                tag_keys=("plane",),
            ).inc(1.0, tags={"plane": "serve"})
        except Exception:
            pass  # observability only: a metrics hiccup must not fail recovery

    def _persistable_apps(self) -> dict:
        """Deep-ish copy of the app table without transient reconcile keys."""
        out: Dict[str, Dict[str, Any]] = {}
        for app, deps in self._apps.items():
            out[app] = {}
            for name, spec in deps.items():
                if name == "__meta__":
                    out[app][name] = dict(spec)
                else:
                    out[app][name] = {
                        k: v for k, v in spec.items() if k != "_dead"
                    }
        return out

    async def _persist_state(self):
        import cloudpickle

        import ray_tpu

        blob = cloudpickle.dumps(
            {"apps": self._persistable_apps(), "http_options": self._http_options}
        )
        w = ray_tpu.global_worker()
        await self._kv_io(
            lambda: w.gcs_kv_put(CONTROLLER_KV_NS, TARGET_STATE_KEY, blob)
        )
        self._state_dirty = False

    def _registry_fingerprint(self) -> tuple:
        return (
            tuple(
                (app, dep, tuple(sorted(r._actor_id.hex() for r in handles)))
                for app, deps in sorted(self._replicas.items())
                for dep, handles in sorted(deps.items())
            ),
            tuple(
                (nid, h._actor_id.hex(), port)
                for nid, (h, port) in sorted(self._proxies.items())
            ),
            tuple(sorted(self._versions.items())),
        )

    async def _persist_registry(self, force: bool = False):
        fingerprint = self._registry_fingerprint()
        if not force and fingerprint == self._registry_snapshot:
            return
        import cloudpickle

        import ray_tpu

        blob = cloudpickle.dumps({
            "replicas": {
                app: {dep: list(handles) for dep, handles in deps.items()}
                for app, deps in self._replicas.items()
            },
            "proxies": dict(self._proxies),
            "versions": dict(self._versions),
        })
        w = ray_tpu.global_worker()
        await self._kv_io(lambda: w.gcs_kv_put(CONTROLLER_KV_NS, REGISTRY_KEY, blob))
        self._registry_snapshot = fingerprint

    async def _persist_autopilot(self):
        if self._autopilot is None:
            return
        import cloudpickle

        import ray_tpu

        blob = cloudpickle.dumps(self._autopilot.dump())
        w = ray_tpu.global_worker()
        await self._kv_io(
            lambda: w.gcs_kv_put(CONTROLLER_KV_NS, AUTOPILOT_KEY, blob)
        )
        self._autopilot.mark_clean()

    async def _clear_persisted_state(self):
        import ray_tpu

        w = ray_tpu.global_worker()
        for key in (TARGET_STATE_KEY, REGISTRY_KEY, AUTOPILOT_KEY):
            try:
                await self._kv_io(
                    lambda k=key: w.gcs_call("kv_del", CONTROLLER_KV_NS, k)
                )
            except Exception:
                pass  # GCS down during teardown: stale keys are cleared by
                # the driver-side serve.shutdown() fallback kv_del
        self._registry_snapshot = None

    async def health(self) -> dict:
        """Liveness + identity probe (chaos tests SIGKILL the controller by
        pid and wait for a new incarnation to answer from a different one)."""
        import os

        await self._ensure_recovered()
        return {
            "pid": os.getpid(),
            "apps": sorted(self._apps),
            "recovered": self._recovered,
        }

    # -- proxies -----------------------------------------------------------
    async def ensure_proxies(self, http_options: Optional[dict] = None) -> int:
        """Arm per-node proxy management and return the head node's proxy port.

        Explicit options always take effect: serve.run()/get_proxy_port() arm the
        defaults with {}, and a later serve.start(http_options={'port': N}) must
        not be silently ignored — a port change restarts the proxies."""
        await self._ensure_recovered()
        # Option merge + port-change restart must happen under the same lock
        # as reconciliation: an in-flight reconcile may be about to register a
        # proxy started with the OLD port, and a kill/clear outside the lock
        # would miss it, leaving a stale-port proxy in the table.
        async with self._proxy_lock:
            if http_options:
                prev = self._http_options
                self._http_options = {**(prev or {}), **http_options}
                changed = prev is not None and any(
                    prev.get(k) != self._http_options.get(k)
                    for k in ("port", "grpc_port")
                )
                if changed:
                    for _nid, (handle, _port) in list(self._proxies.items()):
                        self._kill(handle)
                    self._proxies.clear()
                await self._persist_state()
            elif self._http_options is None:
                self._http_options = {}
                await self._persist_state()
            await self._reconcile_proxies_locked()  # raylint: disable=RL905 (proxy reconciliation is deliberately lock-serialized: two interleaved reconciles would double-start proxies on the same node)
        await self._persist_registry()
        import ray_tpu

        head_hex = next(
            (n["node_id"].hex() for n in ray_tpu.nodes() if n.get("is_head")), None
        )
        if head_hex and head_hex in self._proxies:
            return self._proxies[head_hex][1]
        return next(iter(self._proxies.values()))[1] if self._proxies else 0

    async def proxy_ports(self) -> Dict[str, int]:
        await self._ensure_recovered()
        return {nid: port for nid, (_h, port) in self._proxies.items()}

    async def _reconcile_proxies(self):
        if self._http_options is None:
            return
        async with self._proxy_lock:
            await self._reconcile_proxies_locked()  # raylint: disable=RL905 (proxy reconciliation is deliberately lock-serialized: two interleaved reconciles would double-start proxies on the same node)

    async def _reconcile_proxies_locked(self):
        import ray_tpu
        from ray_tpu.serve._common import SERVE_NAMESPACE, async_get
        from ray_tpu.serve._proxy import HTTPProxy
        from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

        alive = {n["node_id"].hex(): n for n in ray_tpu.nodes() if n["alive"]}
        # Drop proxies on dead nodes.
        for nid in list(self._proxies):
            if nid not in alive:
                handle, _port = self._proxies.pop(nid)
                self._kill(handle)
        # One proxy per alive node, every node offered the SAME configured port
        # (reference operating model: "any node, one port", proxy.py:706). On a
        # single-host test cluster the extra binds collide and the proxy falls
        # back to an ephemeral port (see HTTPProxy.start).
        for nid, info in alive.items():
            if nid in self._proxies:
                continue
            from ray_tpu._private.config import CONFIG

            port = self._http_options.get("port", CONFIG.serve_http_port)
            host = self._http_options.get("host", "127.0.0.1")
            grpc_port = self._http_options.get("grpc_port")
            proxy_cls = ray_tpu.remote(num_cpus=0)(HTTPProxy)
            try:
                proxy = proxy_cls.options(
                    name=f"SERVE_PROXY:{nid[:12]}", namespace=SERVE_NAMESPACE,
                    get_if_exists=True, max_concurrency=1000,
                    scheduling_strategy=NodeAffinitySchedulingStrategy(
                        info["node_id"], soft=False
                    ),
                ).remote(host, port, grpc_port)
                bound = await async_get(proxy.start.remote(), timeout=30)
            except Exception:
                # node may have just died; next pass retries. Say why: a proxy that
                # never binds leaves serve.get_proxy_port() answering None.
                traceback.print_exc()
                continue
            self._proxies[nid] = (proxy, bound)

    # -- deploy / teardown -------------------------------------------------
    async def deploy_app(self, app: str, deployments: Dict[str, dict],
                         route_prefix: Optional[str], ingress: str,
                         ingress_streaming: bool = False) -> bool:
        await self._ensure_recovered()
        if route_prefix is not None:
            for other, deps in self._apps.items():
                if other != app and deps.get("__meta__", {}).get("route_prefix") == route_prefix:
                    raise ValueError(
                        f"route_prefix {route_prefix!r} is already used by app "
                        f"{other!r}; pass a distinct route_prefix (or None for "
                        f"handle-only access)"
                    )
        old = self._apps.get(app, {})
        live = self._replicas.setdefault(app, {})
        # Redeploy: replicas built from changed code/args/config are stale — kill
        # them so reconcile rebuilds from the new blobs (a count-only reconcile
        # would happily keep serving the old code). SCALE fields (num_replicas /
        # autoscaling_config) are explicitly not staleness: a declarative
        # re-apply that only edits replica counts scales the live replica set
        # in place via reconcile (reference: lightweight config updates,
        # serve/_private/deployment_state.py).
        import dataclasses as _dc

        def _code_cfg(cfg):
            return _dc.replace(cfg, num_replicas=1, autoscaling_config=None)

        for name, spec in deployments.items():
            if name == "__meta__":
                continue
            prev = old.get(name)
            if prev is not None and (
                prev["target_blob"] != spec["target_blob"]
                or prev["init_blob"] != spec["init_blob"]
                or _code_cfg(prev["config"]) != _code_cfg(spec["config"])
            ):
                for r in live.pop(name, []):
                    self._kill(r)
                self._bump(app, name)
            elif (
                prev is not None
                and "_autoscale_target" in prev
                and spec["config"].autoscaling_config is not None
            ):
                # Same code, declarative re-apply: the autoscaler's earned
                # target survives the replay — `self._apps[app] =
                # deployments` below would otherwise snap the replica count
                # back to the spec's min and re-cold-start the surge
                # capacity (regression: test_serve_autopilot).
                spec["_autoscale_target"] = prev["_autoscale_target"]
        # Deployments dropped from the app entirely.
        for name in list(old):
            if name != "__meta__" and name not in deployments:
                for r in live.pop(name, []):
                    self._kill(r)
                self._mux_ids.pop(f"{app}#{name}", None)
        self._apps[app] = deployments
        meta = self._apps[app].setdefault("__meta__", {})
        meta["route_prefix"] = route_prefix
        meta["ingress"] = ingress
        meta["ingress_streaming"] = ingress_streaming
        # Persist intent BEFORE reconciling: if the controller dies mid-create,
        # the next incarnation re-reads the full target and reconciles toward
        # it (the registry then tells it which replicas already exist).
        await self._persist_state()
        await self._reconcile_app(app)
        await self._persist_registry()
        return True

    async def delete_app(self, app: str) -> bool:
        await self._ensure_recovered()
        self._apps.pop(app, None)
        await self._persist_state()
        for key in [k for k in self._mux_ids if k.startswith(f"{app}#")]:
            self._mux_ids.pop(key, None)
        for replicas in self._replicas.pop(app, {}).values():
            for r in replicas:
                await self._retire(r)
        await self._persist_registry()
        return True

    async def shutdown_serve(self) -> bool:
        # Best-effort recovery first so persisted-but-unloaded apps' replicas
        # are found and killed too; a failed recovery must not block teardown.
        try:
            await self._ensure_recovered()
        except Exception:
            pass  # recovery needs the GCS; shutdown proceeds on memory state
        self._shutting_down = True
        for app in list(self._apps):
            await self.delete_app(app)
        for _nid, (handle, _port) in list(self._proxies.items()):
            self._kill(handle)
        self._proxies.clear()
        self._http_options = None
        # An explicit shutdown is the END of the serve instance: clear the
        # durable state so the next controller starts cold by design.
        await self._clear_persisted_state()
        return True

    def _kill(self, actor):
        import ray_tpu

        try:
            ray_tpu.kill(actor)
        except Exception:
            pass

    async def _notify_retire(self, app: str, name: str, victim):
        """Scale-down prune hook: before the victim actor dies, the app's
        ingress router (DPRouter/PDRouter) is told to drop the victim's
        prefix fingerprints and adapter-residency entries — without this,
        the router keeps routing cache-affine traffic at a corpse until its
        dead-replica pruning notices on a later pick. Best-effort and
        duck-typed: plain apps whose ingress has no `retire_replica` simply
        skip it."""
        from ray_tpu.serve._common import async_get

        meta = self._apps.get(app, {}).get("__meta__", {})
        ingress = meta.get("ingress")
        if not ingress or ingress == name:
            return
        routers = self._replicas.get(app, {}).get(ingress, [])
        refs = [
            r.handle_request.remote("retire_replica", (victim._actor_id,), {})
            for r in routers
        ]
        for ref in refs:
            try:
                await async_get(ref, timeout=2)
            except Exception:
                pass  # no hook on this ingress (or it is mid-restart)

    async def _retire(self, actor):
        """Graceful replica retirement (delete/scale-down path): give the
        wrapped instance's shutdown() hook a bounded chance to release
        cross-process resources — dp rank tokens, engine steppers, stream
        pumps — before the hard kill reclaims the process. Dead-replica and
        stale-redeploy kills stay on the fast `_kill` path: those replicas
        are gone or about to be replaced wholesale."""
        from ray_tpu.serve._common import async_get

        try:
            await async_get(actor.prepare_shutdown.remote(), timeout=2)
        except Exception:
            pass  # replica dead or unresponsive: the hard kill reclaims it
        self._kill(actor)

    # -- routing tables ----------------------------------------------------
    async def get_replicas(self, app: str, deployment: str) -> dict:
        await self._ensure_recovered()
        key = f"{app}#{deployment}"
        return {
            "version": self._versions.get(key, 0),
            "replicas": list(self._replicas.get(app, {}).get(deployment, [])),
            "multiplexed": dict(self._mux_ids.get(key, {})),
            # Lets handles distinguish "app deleted" (stop retrying) from
            # "replicas still starting / controller just recovered" (wait).
            "exists": app in self._apps and deployment in self._apps.get(app, {}),
        }

    async def get_app_meta(self, app: str) -> Optional[dict]:
        await self._ensure_recovered()
        if app not in self._apps:
            return None
        meta = self._apps[app].get("__meta__", {})
        return {"route_prefix": meta.get("route_prefix"),
                "ingress": meta.get("ingress"),
                "ingress_streaming": meta.get("ingress_streaming", False)}

    async def list_apps(self) -> dict:
        await self._ensure_recovered()
        out = {}
        for app, deps in self._apps.items():
            meta = deps.get("__meta__", {})
            out[app] = {
                "route_prefix": meta.get("route_prefix"),
                "ingress": meta.get("ingress"),
                "ingress_streaming": meta.get("ingress_streaming", False),
                "deployments": {
                    name: {
                        "num_replicas": len(self._replicas.get(app, {}).get(name, [])),
                        "target": spec["config"].num_replicas,
                    }
                    for name, spec in deps.items()
                    if name != "__meta__"
                },
            }
        return out

    async def ready(self, app: str) -> bool:
        """All deployments of the app have their target replica count, and each
        replica answers ready()."""
        import ray_tpu
        from ray_tpu.serve._common import async_get

        await self._ensure_recovered()
        deps = self._apps.get(app)
        if deps is None:
            return False
        for name, spec in deps.items():
            if name == "__meta__":
                continue
            want = self._target_replicas(app, name)
            have = self._replicas.get(app, {}).get(name, [])
            if len(have) < want:
                return False
            try:
                await async_get([r.ready.remote() for r in have], timeout=30)
            except Exception:
                return False
        return True

    # -- reconciliation ----------------------------------------------------
    def _target_replicas(self, app: str, name: str) -> int:
        spec = self._apps[app][name]
        cfg = spec["config"]
        # Autopilot-held targets win for managed deployments: they are the
        # closed-loop decision, persisted in their own KV record so neither
        # a controller restart nor a declarative redeploy resets them.
        if self._autopilot is not None:
            from ray_tpu._private.config import CONFIG

            if CONFIG.serve_autopilot:
                target = self._autopilot.target_for(app, name)
                if target is not None and self._autopilot.manages(app, name):
                    return target
        if cfg.autoscaling_config is not None:
            return spec.setdefault("_autoscale_target", cfg.autoscaling_config.min_replicas)
        return cfg.num_replicas

    async def _reconcile_app(self, app: str):
        import ray_tpu
        from ray_tpu.serve._replica import Replica

        deps = self._apps.get(app, {})
        live = self._replicas.setdefault(app, {})
        for name, spec in list(deps.items()):
            if name == "__meta__":
                continue
            cfg = spec["config"]
            replicas = live.setdefault(name, [])
            # Drop dead replicas (ping failed in the control loop marks them).
            dead = spec.pop("_dead", [])
            if dead:
                keep = []
                for r in replicas:
                    if any(r._actor_id == d for d in dead):
                        self._kill(r)
                    else:
                        keep.append(r)
                live[name] = replicas = keep
            want = self._target_replicas(app, name)
            actor_opts = dict(cfg.ray_actor_options or {})
            actor_opts.setdefault("num_cpus", 0)
            actor_cls = ray_tpu.remote(**actor_opts)(Replica)
            while len(replicas) < want:
                replicas.append(
                    actor_cls.options(max_concurrency=cfg.max_ongoing_requests).remote(
                        spec["target_blob"], spec["init_blob"], name, app,
                        cfg.user_config,
                    )
                )
                self._bump(app, name)
            while len(replicas) > want:
                victim = replicas.pop()
                await self._notify_retire(app, name, victim)
                await self._retire(victim)
                self._bump(app, name)

    def _bump(self, app: str, name: str):
        key = f"{app}#{name}"
        self._versions[key] = self._versions.get(key, 0) + 1

    # -- control loop ------------------------------------------------------
    async def run_control_loop(self):
        if self._loop_started:
            return
        self._loop_started = True
        while not self._shutting_down:
            try:
                # Recovery first (idempotent): the loop may be the only caller
                # on a restarted controller. A GCS outage makes _step raise
                # ConnectionLost after the rpc deadline — caught here, retried
                # next tick; live replicas keep serving off routers' cached
                # tables in the meantime.
                await self._ensure_recovered()
                await self._step()
                if self._state_dirty:
                    await self._persist_state()
                await self._persist_registry()
            except Exception:
                traceback.print_exc()
            from ray_tpu._private.config import CONFIG

            await asyncio.sleep(CONFIG.serve_control_loop_interval_s)

    async def _step(self):
        from ray_tpu.serve._common import async_get

        for app in list(self._apps):
            deps = self._apps.get(app, {})
            for name, spec in list(deps.items()):
                if name == "__meta__":
                    continue
                replicas = self._replicas.get(app, {}).get(name, [])
                # Health check + stats, probed CONCURRENTLY (a serial 5s timeout
                # per starting replica would stall the whole control loop).
                # A replica that has never responded is STARTING (model
                # load/compile can take minutes) and gets a grace period; a
                # replica whose ACTOR DIED is dead immediately; a
                # previously-healthy one that stops answering is dead too.
                health = self._health.setdefault((app, name), {
                    "healthy": set(), "created": {},
                })
                live_ids = {r._actor_id for r in replicas}
                health["healthy"] &= live_ids
                health["created"] = {
                    k: v for k, v in health["created"].items() if k in live_ids
                }
                now = time.monotonic()
                grace_s = 600.0
                for r in replicas:
                    health["created"].setdefault(r._actor_id, now)

                async def probe(r):
                    try:
                        return await async_get(r.get_stats.remote(), timeout=5)
                    except Exception as e:
                        return e

                results = await asyncio.gather(*(probe(r) for r in replicas))
                stats = []
                dead = []
                mux_ids: Dict[Any, list] = {}
                for r, res in zip(replicas, results):
                    if not isinstance(res, Exception):
                        stats.append(res)
                        health["healthy"].add(r._actor_id)
                        ids = res.get("multiplexed_ids") or []
                        if ids:
                            mux_ids[r._actor_id] = list(ids)
                        continue
                    died = type(res).__name__ == "ActorDiedError"
                    started = health["created"].get(r._actor_id, now)
                    if (
                        died
                        or r._actor_id in health["healthy"]
                        or now - started > grace_s
                    ):
                        dead.append(r._actor_id)
                if dead:
                    spec["_dead"] = dead
                # Cluster-wide multiplex view: replicas report loaded model ids
                # through get_stats; routers prefer replicas that already hold
                # the model (reference routes on replica-reported ids,
                # python/ray/serve/multiplex.py).
                self._mux_ids[f"{app}#{name}"] = mux_ids
                cfg = spec["config"]
                # The legacy ongoing-requests autoscaler stands down for
                # autopilot-managed deployments: two laws writing one
                # target would fight.
                if cfg.autoscaling_config is not None and stats and not (
                    self._autopilot is not None
                    and self._autopilot.manages(app, name)
                ):
                    self._autoscale(app, name, spec, stats)
            await self._reconcile_app(app)
        await self._maybe_autopilot()
        await self._reconcile_proxies()

    def _autoscale(self, app: str, name: str, spec: dict, stats: List[dict]):
        cfg = spec["config"].autoscaling_config
        total_ongoing = sum(s["ongoing"] for s in stats)
        current = spec.get("_autoscale_target", cfg.min_replicas)
        desired = max(
            cfg.min_replicas,
            min(cfg.max_replicas, math.ceil(total_ongoing / cfg.target_ongoing_requests)),
        )
        now = time.monotonic()
        key = (app, name)
        last = self._last_scale.get(key, 0.0)
        if desired > current and now - last >= cfg.upscale_delay_s:
            spec["_autoscale_target"] = desired
            self._last_scale[key] = now
            self._state_dirty = True  # autoscale target is declarative state
        elif desired < current and now - last >= cfg.downscale_delay_s:
            spec["_autoscale_target"] = current - 1  # scale down gently
            self._last_scale[key] = now
            self._state_dirty = True

    # -- SLO autopilot (docs/autoscale.md) ---------------------------------
    def _ensure_autopilot(self):
        if self._autopilot is None:
            from ray_tpu._private.config import CONFIG
            from ray_tpu.serve.autopilot import Autopilot

            self._autopilot = Autopilot(
                decision_log_cap=CONFIG.serve_autopilot_decision_log)
        return self._autopilot

    def _autopilot_bounds(self, spec: dict):
        """Per-deployment scaling bounds: the deployment's own
        AutoscalingConfig min/max win when set; the serve_autopilot_* flags
        are the fleet default. Timing knobs always come from the flags."""
        from ray_tpu._private.config import CONFIG
        from ray_tpu.serve.autopilot import ReplicaBounds

        ac = spec["config"].autoscaling_config
        return ReplicaBounds(
            min_replicas=(ac.min_replicas if ac is not None
                          else CONFIG.serve_autopilot_min_replicas),
            max_replicas=(ac.max_replicas if ac is not None
                          else CONFIG.serve_autopilot_max_replicas),
            burn_high=CONFIG.serve_autopilot_burn_high,
            queue_high=CONFIG.serve_autopilot_queue_high,
            sustain_ticks=CONFIG.serve_autopilot_sustain_ticks,
            upscale_cooldown_s=CONFIG.serve_autopilot_upscale_cooldown_s,
            downscale_cooldown_s=CONFIG.serve_autopilot_downscale_cooldown_s,
            cold_start_guard_s=CONFIG.serve_autopilot_cold_start_guard_s,
        )

    async def _autopilot_observe(self) -> list:
        """Probe every replica's `autopilot_signals()` (duck-typed opt-in:
        deployments whose replicas answer become autopilot-managed) and
        fold the answers into per-deployment observations."""
        from ray_tpu.serve._common import async_get
        from ray_tpu.serve.autopilot import aggregate_signals

        probes = []
        for app, deps in list(self._apps.items()):
            for name, spec in list(deps.items()):
                if name == "__meta__":
                    continue
                replicas = self._replicas.get(app, {}).get(name, [])
                if not replicas:
                    continue
                refs = [
                    r.handle_request.remote("autopilot_signals", (), {})
                    for r in replicas
                ]
                probes.append((app, name, spec, len(replicas), refs))
        out = []
        for app, name, spec, n, refs in probes:
            results = await asyncio.gather(
                *(async_get(ref, timeout=5) for ref in refs),
                return_exceptions=True)
            signals = [r for r in results if isinstance(r, dict)]
            if not signals:
                continue  # no replica opted in: not autopilot-managed
            obs = aggregate_signals(app, name, signals)
            obs.replicas = n  # count starting replicas too, not just responders
            obs.bounds = self._autopilot_bounds(spec)
            out.append(obs)
        return out

    async def _maybe_autopilot(self):
        from ray_tpu._private.config import CONFIG

        if not CONFIG.serve_autopilot:
            return
        now = time.time()
        if now - self._autopilot_last < CONFIG.serve_autopilot_interval_s:
            return
        self._autopilot_last = now
        from ray_tpu.serve.autopilot import (
            ScaleAction,
            WeightBounds,
        )

        ap = self._ensure_autopilot()
        observations = await self._autopilot_observe()
        weight_bounds = WeightBounds(
            step=CONFIG.serve_autopilot_weight_step,
            floor=CONFIG.serve_autopilot_weight_floor,
            ceiling=CONFIG.serve_autopilot_weight_max,
            deadband=CONFIG.serve_autopilot_weight_deadband,
            sustain_ticks=CONFIG.serve_autopilot_sustain_ticks,
            cooldown_s=CONFIG.serve_autopilot_upscale_cooldown_s,
        )
        actions = ap.tick(
            observations, weight_bounds,
            pd_ratio_tol=CONFIG.serve_autopilot_pd_ratio_tol, now=now)
        for action in actions:
            if isinstance(action, ScaleAction):
                op = ap.begin_scale_op(action)
                await self._apply_scale_op(op, action.app)
            else:
                await self._broadcast_weight(action)
        if ap.dirty:
            await self._persist_autopilot()

    async def _apply_scale_op(self, op, app: str) -> bool:
        """Actuate one replica-count change under its two-phase token: the
        reconcile either lands (commit) or the autopilot's target rolls
        back to what the cluster actually has (abort) — a failed scale-up
        must not persist a phantom target that respawns forever."""
        try:
            await self._reconcile_app(app)
            await self._persist_registry()
        except Exception:
            traceback.print_exc()
            op.abort()
            return False
        op.commit()
        return True

    async def _broadcast_weight(self, action) -> None:
        """Push one tenant's adapted WFQ weight to every managed replica of
        the app (the engine forwards to its scheduler's weighted-fair
        queues; DPRouter fans out to DP ranks)."""
        from ray_tpu.serve._common import async_get

        refs = []
        for name in list(self._apps.get(action.app, {})):
            if name == "__meta__":
                continue
            if not (self._autopilot is not None
                    and self._autopilot.manages(action.app, name)):
                continue
            for r in self._replicas.get(action.app, {}).get(name, []):
                refs.append(r.handle_request.remote(
                    "set_tenant_weight", (action.tenant, action.weight), {}))
        applied = 0
        for ref in refs:
            try:
                await async_get(ref, timeout=5)
                applied += 1
            except Exception:
                pass  # replica died or lacks the hook: next tick re-nudges
        action.decision["outcome"] = (
            f"applied:{applied}/{len(refs)}" if refs else "no_replicas")

    async def autopilot_wake(self, app: str, deployment: str) -> bool:
        """Scale-to-zero cold start: a deployment handle found zero
        replicas for an existing deployment. Bypasses pressure hysteresis
        (the requester is already waiting) and arms the cold-start guard so
        the fresh replica is not retired straight back to zero."""
        from ray_tpu._private.config import CONFIG

        await self._ensure_recovered()
        if not CONFIG.serve_autopilot:
            return False
        spec = self._apps.get(app, {}).get(deployment)
        if spec is None or deployment == "__meta__":
            return False
        key = f"{app}#{deployment}"
        now = time.monotonic()
        # A fleet of handles stampeding the same cold deployment collapses
        # to one wake per second.
        if now - self._autopilot_wake_ts.get(key, -1e9) < 1.0:
            return False
        self._autopilot_wake_ts[key] = now
        ap = self._ensure_autopilot()
        action = ap.wake(app, deployment, self._autopilot_bounds(spec))
        if action is None:
            return False
        op = ap.begin_scale_op(action)
        ok = await self._apply_scale_op(op, app)
        await self._persist_autopilot()
        return ok

    async def autopilot_stats(self) -> dict:
        """Report surface for serve_stats()/`ray_tpu status`: the decision
        log, autopilot-held targets, and adapted tenant weights. This is
        also where the autopilot's own metrics flush (report path)."""
        from ray_tpu._private.config import CONFIG

        await self._ensure_recovered()
        out = {"enabled": bool(CONFIG.serve_autopilot)}
        if self._autopilot is not None:
            out.update(self._autopilot.stats())
        return out
