"""@ray_tpu.remote for functions.

Design parity: reference `python/ray/remote_function.py` (RemoteFunction wrapper, _remote
:313, .options() override chaining) — resources here speak TPU: `num_tpus` maps to the
"TPU" resource the accelerator manager advertises, the way num_gpus maps to "GPU" there.
"""

from __future__ import annotations

import functools

from ray_tpu._private.worker import global_worker

_DEFAULTS = {
    "num_cpus": 1,
    "num_tpus": 0,
    "memory": None,  # bytes; schedulable + enforced via cgroup-v2 where active
    "resources": None,
    "num_returns": 1,
    "max_retries": None,
    "placement_group": None,
    "placement_group_bundle_index": 0,
    "scheduling_strategy": None,
    "name": None,
    "runtime_env": None,
}


def _check_options(options: dict, known: dict) -> dict:
    """Defaults overlaid with `options`. An option nobody reads is an error: a bare
    resource name (`TPU=1`) used to be dropped here, so the actor reserved nothing."""
    unknown = sorted(set(options) - set(known))
    if unknown:
        raise ValueError(
            f"unknown option(s) {unknown} for @ray_tpu.remote; custom resources go in "
            f"resources={{...}} (e.g. resources={{'TPU': 1}}) or num_tpus=. "
            f"Known options: {sorted(known)}"
        )
    return {**known, **options}


def _build_resources(opts) -> dict:
    resources = dict(opts.get("resources") or {})
    if opts.get("num_cpus") is not None:
        resources["CPU"] = float(opts["num_cpus"])
    if opts.get("num_tpus"):
        resources["TPU"] = float(opts["num_tpus"])
    if opts.get("memory"):
        resources["memory"] = float(opts["memory"])
    return {r: amt for r, amt in resources.items() if amt}


def _build_pg_spec(opts):
    pg = opts.get("placement_group")
    if pg is None:
        return None
    from ray_tpu.util.placement_group import PlacementGroup

    if isinstance(pg, PlacementGroup):
        return {"pg_id": pg.id, "bundle_index": opts.get("placement_group_bundle_index", 0)}
    return pg if isinstance(pg, dict) else None


def _resolve_scheduling(opts):
    strategy = opts.get("scheduling_strategy")
    if strategy is None:
        return None, opts
    from ray_tpu.util.scheduling_strategies import (
        CompositeSchedulingStrategy,
        NodeAffinitySchedulingStrategy,
        NodeLabelSchedulingStrategy,
        PlacementGroupSchedulingStrategy,
    )

    if isinstance(strategy, PlacementGroupSchedulingStrategy):
        opts = dict(opts)
        opts["placement_group"] = strategy.placement_group
        opts["placement_group_bundle_index"] = strategy.placement_group_bundle_index
        return None, opts
    if isinstance(strategy, NodeAffinitySchedulingStrategy):
        return {"node_id": strategy.node_id, "soft": strategy.soft}, opts
    if isinstance(strategy, (NodeLabelSchedulingStrategy, CompositeSchedulingStrategy)):
        return strategy.to_spec(), opts
    return None, opts


class RemoteFunction:
    def __init__(self, fn, options: dict):
        self._fn = fn
        self._options = _check_options(options, _DEFAULTS)
        self._fn_key = None
        functools.update_wrapper(self, fn)

    def options(self, **overrides) -> "RemoteFunction":
        clone = RemoteFunction(self._fn, {**self._options, **overrides})
        clone._fn_key = self._fn_key
        return clone

    def remote(self, *args, **kwargs):
        worker = global_worker()
        # Re-export after a shutdown/init cycle: the key cache is per cluster session.
        # (The token is a plain string: RemoteFunction objects must stay picklable.)
        if self._fn_key is None or getattr(self, "_fn_session", None) != worker.session_token:
            self._fn_key = worker.functions.export(self._fn)
            self._fn_session = worker.session_token
        opts = self._options
        strategy, opts = _resolve_scheduling(opts)
        from ray_tpu._private import runtime_env as runtime_env_mod

        refs = worker.submit_task(
            fn_key=self._fn_key,
            name=opts.get("name") or getattr(self._fn, "__name__", "anonymous"),
            args=args,
            kwargs=kwargs,
            num_returns=opts["num_returns"],
            resources=_build_resources(opts),
            placement_group=_build_pg_spec(opts),
            max_retries=opts["max_retries"],
            scheduling_strategy=strategy,
            runtime_env=runtime_env_mod.validate(opts.get("runtime_env")),
        )
        if opts["num_returns"] == 1:
            return refs[0]
        return refs

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"remote function {self._fn.__name__} cannot be called directly; "
            f"use {self._fn.__name__}.remote()"
        )
