"""XLA tier: in-graph device collectives over mesh axes (the NCCL-path replacement).

Where the reference moves device tensors with eager NCCL calls
(`python/ray/util/collective/collective_group/nccl_collective_group.py`), the TPU-native
design expresses device collectives as XLA ops inside jit/shard_map over a
`jax.sharding.Mesh`: the compiler schedules them onto ICI (intra-slice) or DCN
(cross-slice) and overlaps them with compute. This module gives those ops the same verb
vocabulary as the eager API so user code reads uniformly across the two tiers.

Use inside `jax.shard_map` (or any jitted fn with bound axis names):

    @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    def step(x):
        g = xla.allreduce(local_grad(x), "dp")
        ...

`MeshGroup` additionally offers *eager* entry points that wrap one collective in a
shard_map and execute it immediately — useful at library boundaries (tests, small sync
points) where building a fused graph isn't worth it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util.collective.types import ReduceOp


def allreduce(x, axis_name, op: ReduceOp = ReduceOp.SUM):
    if op == ReduceOp.SUM:
        return jax.lax.psum(x, axis_name)
    if op == ReduceOp.MEAN:
        return jax.lax.pmean(x, axis_name)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axis_name)
    if op == ReduceOp.PRODUCT:
        # No pprod primitive; exp/sum/log is ill-conditioned, so gather-then-reduce.
        return jnp.prod(jax.lax.all_gather(x, axis_name), axis=0)
    raise ValueError(f"unknown reduce op {op}")


def allgather(x, axis_name, axis: int = 0, tiled: bool = False):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reducescatter(x, axis_name, scatter_axis: int = 0, op: ReduceOp = ReduceOp.SUM):
    if op not in (ReduceOp.SUM, ReduceOp.MEAN):
        raise ValueError("reducescatter supports SUM/MEAN (what XLA lowers natively)")
    out = jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=True)
    if op == ReduceOp.MEAN:
        out = out / jax.lax.axis_size(axis_name)
    return out


def ppermute(x, axis_name, perm: list[tuple[int, int]]):
    return jax.lax.ppermute(x, axis_name, perm)


def send_next(x, axis_name):
    """Ring shift: every shard sends to (rank+1) % n. The ring-attention building block."""
    n = jax.lax.axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def all_to_all(x, axis_name, split_axis: int, concat_axis: int, tiled: bool = True):
    """Ulysses-style head<->sequence reshard (SURVEY.md §5 long-context)."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


class MeshGroup:
    """Eager wrappers: one collective per call, shard_map-compiled and cached.

    The group's "ranks" are the positions along `axis` of `mesh`; inputs are global
    arrays sharded along that axis (or host arrays, which get sharded on entry).
    """

    def __init__(self, mesh: Mesh, axis: str = "dp"):
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self._cache: dict = {}

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def _sharded(self, x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def allreduce(self, stacked, op: ReduceOp = ReduceOp.SUM):
        """stacked: array of shape (world_size, ...) — per-rank inputs on dim 0.
        Returns their elementwise reduction (shape ``stacked.shape[1:]``)."""
        stacked = jnp.asarray(stacked)
        if stacked.shape[0] != self.world_size:
            raise ValueError(
                f"dim 0 ({stacked.shape[0]}) must equal world_size ({self.world_size})"
            )
        key = ("allreduce", op)
        fn = self._cache.get(key)
        if fn is None:
            fn = jax.jit(
                jax.shard_map(
                    partial(allreduce, axis_name=self.axis, op=op),
                    mesh=self.mesh,
                    in_specs=P(self.axis),
                    out_specs=P(None),
                )
            )
            # Keys are ("allreduce", <ReduceOp member>): bounded by the enum.
            self._cache[key] = fn  # raylint: disable=RL602 (key space is the fixed ReduceOp enum)
        return fn(self._sharded(stacked, P(self.axis)))[0]
