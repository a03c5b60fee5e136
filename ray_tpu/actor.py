"""Actor API: @ray_tpu.remote on classes, ActorClass / ActorHandle / ActorMethod.

Design parity: reference `python/ray/actor.py` (ActorClass._remote :1498, ActorHandle
:1857, ActorMethod._remote :792) — named actors, namespaces, get_if_exists, max_restarts,
max_concurrency (threaded) and async actors (async def methods → asyncio event loop with
a concurrency semaphore), ordered per-caller method delivery.
"""

from __future__ import annotations

import inspect

from ray_tpu._private.ids import ActorID
from ray_tpu._private.worker import global_worker
from ray_tpu.exceptions import ActorDiedError
from ray_tpu.remote_function import (
    _build_pg_spec,
    _build_resources,
    _check_options,
    _resolve_scheduling,
)

_ACTOR_DEFAULTS = {
    "num_cpus": 0,
    "num_tpus": 0,
    "memory": None,  # bytes; schedulable + enforced via cgroup-v2 where active
    "resources": None,
    "name": None,
    "namespace": None,
    "get_if_exists": False,
    "lifetime": None,
    "max_restarts": 0,
    "max_concurrency": None,
    "concurrency_groups": None,
    "allow_out_of_order_execution": False,
    "placement_group": None,
    "placement_group_bundle_index": 0,
    "scheduling_strategy": None,
    "max_retries": None,
    "num_returns": 1,
    "runtime_env": None,
}


def _public_methods(cls) -> list[str]:
    names = []
    for name, member in inspect.getmembers(cls, predicate=callable):
        if not name.startswith("_") or name == "__call__":
            names.append(name)
    return names


def _declared_method_opts(cls) -> dict:
    """Collect @ray_tpu.method declarations: name -> opts dict."""
    out = {}
    for name, member in inspect.getmembers(cls, predicate=callable):
        opts = getattr(member, "__ray_tpu_method_opts__", None)
        if opts:
            out[name] = dict(opts)
    return out


def _has_async_methods(cls) -> bool:
    return any(
        inspect.iscoroutinefunction(m) or inspect.isasyncgenfunction(m)
        for _n, m in inspect.getmembers(cls, predicate=inspect.isfunction)
    )


def method(num_returns: int = 1, concurrency_group: str | None = None):
    """Method decorator (reference: @ray.method, python/ray/actor.py): bind a
    method to a declared concurrency group and/or set its return arity.
    Bare `@method` (no parentheses) decorates with the defaults."""

    def wrap(fn):
        fn.__ray_tpu_method_opts__ = {
            "num_returns": num_returns,
            "concurrency_group": concurrency_group,
        }
        return fn

    if callable(num_returns):
        fn, num_returns = num_returns, 1
        return wrap(fn)
    return wrap


class ActorMethod:
    def __init__(self, handle: "ActorHandle", method_name: str,
                 num_returns: int | None = None,
                 concurrency_group: str | None = None):
        self._handle = handle
        self._method_name = method_name
        declared = handle._method_opts.get(method_name, {})
        self._num_returns = (
            num_returns if num_returns is not None
            else declared.get("num_returns", 1)
        )
        self._concurrency_group = (
            concurrency_group if concurrency_group is not None
            else declared.get("concurrency_group")
        )

    def options(self, num_returns: int | None = None,
                concurrency_group: str | None = None, **_ignored):
        return ActorMethod(
            self._handle, self._method_name, num_returns, concurrency_group
        )

    def remote(self, *args, **kwargs):
        worker = global_worker()
        refs = worker.submit_actor_task(
            self._handle._actor_id, self._method_name, args, kwargs,
            self._num_returns, concurrency_group=self._concurrency_group,
            out_of_order=self._handle._out_of_order,
        )
        if self._num_returns == 1:
            return refs[0]
        return refs

    def bind(self, *args, **kwargs):
        """Build a compiled-graph node for this method call (reference:
        actor_method.bind() -> ClassMethodNode, python/ray/dag/class_node.py)."""
        from ray_tpu.dag.dag_node import ClassMethodNode

        return ClassMethodNode(self._handle, self._method_name, args, kwargs)


class ActorHandle:
    def __init__(
        self,
        actor_id: ActorID,
        method_names: list[str],
        class_name: str = "",
        method_opts: dict | None = None,
        out_of_order: bool = False,
        _owns_arg_pins: bool = False,
    ):
        self._actor_id = actor_id
        self._method_names = list(method_names)
        self._class_name = class_name
        self._out_of_order = out_of_order
        # method name -> {"num_returns": n, "concurrency_group": g} from
        # @ray_tpu.method declarations (travels with serialized handles).
        self._method_opts = dict(method_opts or {})
        # Only the handle returned to the CREATOR guards the actor's pinned init
        # args; deserialized copies (__reduce__) do not, so a borrower dropping
        # its copy cannot release pins it never took.
        self._owns_arg_pins = _owns_arg_pins

    def __del__(self):
        # GC-safe: defer (finalizers must not take runtime locks; see
        # ReferenceCounter.defer_remove).
        if getattr(self, "_owns_arg_pins", False):
            try:
                from ray_tpu._private.worker import global_worker_or_none

                w = global_worker_or_none()
                if w is not None:
                    w.reference_counter.defer_actor_pin_release(self._actor_id)
            except Exception:
                pass  # interpreter shutdown

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        if self._method_names and name not in self._method_names:
            raise AttributeError(
                f"actor {self._class_name or self._actor_id} has no method {name!r}"
            )
        return ActorMethod(self, name)

    def __repr__(self):
        return f"ActorHandle({self._class_name}, {self._actor_id.hex()[:12]})"

    def __reduce__(self):
        return (
            ActorHandle,
            (self._actor_id, self._method_names, self._class_name,
             self._method_opts, self._out_of_order),
        )


class ActorClass:
    def __init__(self, cls, options: dict):
        self._cls = cls
        self._options = _check_options(options, _ACTOR_DEFAULTS)
        self._cls_key = None

    def options(self, **overrides) -> "ActorClass":
        clone = ActorClass(self._cls, {**self._options, **overrides})
        clone._cls_key = self._cls_key
        return clone

    def remote(self, *args, **kwargs) -> ActorHandle:
        worker = global_worker()
        if self._cls_key is None or getattr(self, "_cls_session", None) != worker.session_token:
            self._cls_key = worker.functions.export(self._cls)
            self._cls_session = worker.session_token
        opts = self._options
        strategy, opts = _resolve_scheduling(opts)
        is_async = _has_async_methods(self._cls)
        max_concurrency = opts["max_concurrency"] or (1000 if is_async else 1)
        namespace = opts["namespace"]
        if namespace is None:
            import ray_tpu

            namespace = ray_tpu._current_namespace()
        from ray_tpu._private import runtime_env as runtime_env_mod

        method_names = _public_methods(self._cls)
        method_opts = _declared_method_opts(self._cls)
        cgroups = dict(opts["concurrency_groups"] or {})
        method_groups = {}
        for mname, mopts in method_opts.items():
            group = mopts.get("concurrency_group")
            if group is not None:
                if group not in cgroups:
                    raise ValueError(
                        f"method {mname!r} is bound to concurrency group "
                        f"{group!r} but the actor declares only "
                        f"{sorted(cgroups)} (pass concurrency_groups= to "
                        f"@ray_tpu.remote)"
                    )
                method_groups[mname] = group
        actor_id, owns_pins = worker.create_actor(
            cls_key=self._cls_key,
            class_name=self._cls.__name__,
            args=args,
            kwargs=kwargs,
            name=opts["name"],
            namespace=namespace,
            get_if_exists=opts["get_if_exists"],
            resources=_build_resources(opts),
            placement_group=_build_pg_spec(opts),
            max_restarts=opts["max_restarts"],
            max_concurrency=max_concurrency,
            is_async=is_async,
            scheduling_strategy=strategy,
            method_names=method_names,
            runtime_env=runtime_env_mod.validate(opts.get("runtime_env")),
            concurrency_groups=cgroups,
            method_groups=method_groups,
            method_opts=method_opts,
            allow_out_of_order_execution=opts["allow_out_of_order_execution"],
        )
        return ActorHandle(
            actor_id, method_names, self._cls.__name__, method_opts,
            out_of_order=opts["allow_out_of_order_execution"],
            _owns_arg_pins=owns_pins,
        )

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"actor class {self._cls.__name__} cannot be instantiated directly; "
            f"use {self._cls.__name__}.remote()"
        )


def get_actor(name: str, namespace: str | None = None) -> ActorHandle:
    worker = global_worker()
    if namespace is None:
        import ray_tpu

        namespace = ray_tpu._current_namespace()
    info = worker.gcs_call("get_actor_info", None, name, namespace)
    if info is None or info["state"] == "DEAD":
        raise ValueError(f"actor {name!r} not found in namespace {namespace!r}")
    return ActorHandle(
        info["actor_id"],
        info.get("method_names") or [],
        info.get("class_name") or "",
        info.get("method_opts"),
        out_of_order=info.get("out_of_order", False),
    )


def kill(actor: ActorHandle, no_restart: bool = True):
    worker = global_worker()
    worker.gcs_call("kill_actor", actor._actor_id, no_restart)


def exit_actor():
    """Terminate the current actor process (parity: ray.actor.exit_actor)."""
    import os

    worker = global_worker()
    if worker.actor_id is None:
        raise RuntimeError("exit_actor called outside an actor")
    os._exit(0)
