"""Tensor-parallel serving plan: the mesh + sharding rules for the decode plane.

Design parity: what Megatron-style tensor parallelism and vLLM's TP worker
processes do in the torch/NCCL world, expressed the TPU-native way
(docs/serving_tp.md): parallelism is a `jax.sharding.Mesh` over a named "tp"
axis and a table of PartitionSpecs; XLA's GSPMD partitioner inserts the ICI
collectives. No per-shard worker processes, no explicit all-reduces — ONE
engine process drives the whole mesh, and every compiled program
(prefill / decode / multi-step / spec-verify / adapter-install) is
partitioned by the compiler from its input shardings.

The rules are Megatron's: attention q/k/v projections split by head
(column-parallel), the output projection splits its head-contracted input
(row-parallel), MLP gate/up split the hidden expansion, down contracts it
back, embeddings/lm_head split the vocab. The per-slot KV pool splits on the
kv-head axis, so a model whose parameter+KV footprint exceeds one chip's HBM
serves from `footprint / tp` bytes per chip. Any dimension the tp degree
does not divide evenly is REPLICATED instead (correct, just not
memory-split), so GQA models with few kv heads degrade gracefully.

Numerics: sharded dims that feed contractions are split only where the
reference decomposition is exact (one-hot gathers, per-head attention); the
row-parallel all-reduces reassociate float sums, which moves logits by
~1e-6 — far below greedy argmax gaps — so greedy output is token-identical
across TP degrees (asserted by tests/test_llm_tp.py on the forced 8-device
CPU mesh).
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from ray_tpu.devtools import leaksan as _leaksan


def build_tp_mesh(tp, devices=None):
    """``tp`` -> Mesh or None (the single-device engine path).

    An int builds a 1-D mesh over the "tp" axis; a mapping passes arbitrary
    axes through to `parallel.mesh.create_mesh` (e.g. ``{"tp": 4, "sp": 2}``)
    for engines that also sequence-shard. tp<=1 / empty axes return None so
    the caller keeps the exact pre-mesh code path.
    """
    if tp is None:
        return None
    if isinstance(tp, Mapping):
        axes = {k: int(v) for k, v in tp.items()}
    else:
        axes = {"tp": int(tp)}
    if all(v <= 1 for v in axes.values()):
        return None
    from ray_tpu.parallel.mesh import create_mesh

    return create_mesh(axes, devices=devices)


def tp_degree(mesh) -> int:
    return 1 if mesh is None else int(mesh.shape.get("tp", 1))


def tp_device_count(tp) -> int:
    """Devices one TP engine consumes, computed WITHOUT building a mesh —
    deployment builders run on driver/router processes that may not hold the
    replica's devices, but still scale per-replica resource demands and
    placement bundles by this."""
    if tp is None:
        return 1
    if isinstance(tp, Mapping):
        import math

        return max(1, math.prod(int(v) for v in tp.values())) if tp else 1
    return max(1, int(tp))


def mesh_signature(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh's sharding regime, folded into every
    program-cache key: a sharding change is a DIFFERENT key by construction,
    never a silent recompile of an existing entry (the static-bucket
    program-cache contract, docs/serving_tp.md)."""
    if mesh is None:
        return None
    axes = tuple((k, int(v)) for k, v in mesh.shape.items() if int(v) > 1)
    dev = tuple(int(d.id) for d in mesh.devices.flat)
    return ("mesh", axes, dev)


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def _ns(mesh, *parts):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*parts))


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...], mesh):
    """PartitionSpec for one decode-engine parameter leaf.

    Keyed on the leaf's tree path (the `scan_layers=False` layout the engine
    requires: layer_i/attn/{q,k,v,o}/kernel, layer_i/mlp/{gate,up,down}/
    kernel, embedding, lm_head/kernel). Rules shard a dimension only when the
    tp degree divides it; everything else — norms, scales, odd-sized heads —
    replicates.
    """
    from jax.sharding import PartitionSpec

    tp = tp_degree(mesh)

    def axis(i: int) -> PartitionSpec:
        if tp <= 1 or shape[i] % tp != 0:
            return PartitionSpec()
        parts: List[Optional[str]] = [None] * len(shape)
        parts[i] = "tp"
        return PartitionSpec(*parts)

    parts = tuple(path)
    if len(parts) >= 3 and parts[-3] == "attn":
        proj = parts[-2]
        if proj in ("q", "k", "v"):
            return axis(1)          # [hidden, heads, head_dim]: split heads
        if proj == "o":
            return axis(0)          # [heads, head_dim, hidden]: row-parallel
    if len(parts) >= 3 and parts[-3] == "mlp":
        proj = parts[-2]
        if proj in ("gate", "up"):
            return axis(1)          # [hidden, mlp]: column-parallel
        if proj == "down":
            return axis(0)          # [mlp, hidden]: row-parallel
    if parts[-1] == "embedding":
        return axis(0)              # [vocab, hidden]: split the vocab rows
    if len(parts) >= 2 and parts[-2] == "lm_head":
        return axis(1)              # [hidden, vocab]: split the logits
    return PartitionSpec()


def shard_decode_params(params, mesh):
    """Device-put the engine's (unboxed) param tree onto the mesh per the TP
    rules. Leaves already resident with the target sharding pass through
    unmoved (jax.device_put short-circuits), so pre-sharded trees from the
    resharding checkpoint restore cost nothing here."""
    import jax
    from jax.sharding import NamedSharding

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        ns = NamedSharding(mesh, param_spec(path, tuple(tree.shape), mesh))
        return jax.device_put(tree, ns)

    return walk(params, ())


def kv_cache_sharding(mesh, n_kv_heads: int):
    """Sharding of one per-slot KV cache layer [B, T, Hkv, D]: split the
    kv-head axis (replicated when tp does not divide it)."""
    if tp_degree(mesh) <= 1 or n_kv_heads % tp_degree(mesh) != 0:
        return replicated(mesh)
    return _ns(mesh, None, None, "tp", None)


def kv_prefix_sharding(mesh, n_kv_heads: int):
    """Sharding of a transferred KV prefix [L, 2, P, Hkv, D] (the PD handoff
    and prefix-attach payload layout)."""
    if tp_degree(mesh) <= 1 or n_kv_heads % tp_degree(mesh) != 0:
        return replicated(mesh)
    return _ns(mesh, None, None, None, "tp", None)


def adapter_table_shardings(mesh, q_out: int, v_out: int) -> Dict[str, object]:
    """Shardings of the AdapterCache's stacked tables, aligned with the
    param rules: the B factors' output dims split like the projections they
    add into (q_B -> heads*head_dim, v_B -> kv_heads*head_dim); the A
    factors and scales are small and contract the replicated hidden dim, so
    they replicate."""
    tp = tp_degree(mesh)

    def out_axis(n: int):
        if tp <= 1 or n % tp != 0:
            return replicated(mesh)
        return _ns(mesh, None, None, None, "tp")

    return {
        "q_A": replicated(mesh),
        "q_B": out_axis(q_out),
        "v_A": replicated(mesh),
        "v_B": out_axis(v_out),
        "scale": replicated(mesh),
    }


def checkpoint_shardings(path: str, mesh) -> Dict[str, object]:
    """Manifest leaf key -> NamedSharding for `checkpoint.restore(path,
    shardings=...)`: weights stream from slice files STRAIGHT to their mesh
    layout (each device reads exactly the file regions overlapping its
    shard) — no host gather of the full tree, which is the point when the
    model does not fit one chip. A leading "params" segment (train-state
    saves) is ignored for rule matching."""
    from jax.sharding import NamedSharding

    from ray_tpu.checkpoint._format import load_manifest

    manifest = load_manifest(path)
    out: Dict[str, object] = {}
    for key, spec in manifest["leaves"].items():
        parts = tuple(p for p in key.split("/") if p)
        if parts and parts[0] == "params":
            parts = parts[1:]
        shape = tuple(int(d) for d in spec["shape"])
        out[key] = NamedSharding(mesh, param_spec(parts, shape, mesh))
    return out


def single_device_shardings(devices=None):
    """The TP=1 restore layout: every leaf streams from its slice files
    directly onto the default device (`jax.make_array_from_callback` reads
    the mmap regions into the device buffer) instead of materializing the
    whole tree host-side first."""
    import jax
    from jax.sharding import SingleDeviceSharding

    devs = devices if devices is not None else jax.devices()
    return SingleDeviceSharding(devs[0])


def _index_shape(index, shape) -> Tuple[int, ...]:
    out = []
    for dim, sl in enumerate(index):
        start = 0 if sl.start is None else int(sl.start)
        stop = shape[dim] if sl.stop is None else int(sl.stop)
        out.append(stop - start)
    return tuple(out)


def mesh_zeros(shape, dtype, sharding):
    """Zeros allocated DIRECTLY at their mesh layout: each device's shard is
    built from a shard-sized host buffer (`jax.make_array_from_callback`), so
    a pool larger than any single device's memory never materializes whole
    anywhere — the allocation that makes model-bigger-than-one-chip serving
    real."""
    import jax

    np_dtype = np.dtype(dtype)
    return jax.make_array_from_callback(
        tuple(shape), sharding,
        lambda index: np.zeros(_index_shape(index, shape), np_dtype),
    )


def per_device_bytes(tree_or_leaf) -> int:
    """Max bytes any single device holds for a (pytree of) jax arrays —
    the per-chip HBM high-water accounting bench_serve reports. Host numpy
    leaves count whole (they live on the one implicit device)."""
    import jax

    totals: Dict[int, int] = {}
    leaves = jax.tree_util.tree_leaves(tree_or_leaf)
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                nbytes = int(np.prod(shard.data.shape)) * np.dtype(leaf.dtype).itemsize
                totals[shard.device.id] = totals.get(shard.device.id, 0) + nbytes
        elif hasattr(leaf, "nbytes"):
            totals[-1] = totals.get(-1, 0) + int(leaf.nbytes)
    return max(totals.values(), default=0)


def per_device_byte_map(tree_or_leaf) -> Dict[str, int]:
    """Per-device byte attribution for a (pytree of) jax arrays — the
    memory-ledger complement to `per_device_bytes` (which keeps only the
    max). Keys are device ids as strings ("-1" = host numpy leaves); uses
    shard shape metadata only, never a device pull."""
    import jax

    totals: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree_or_leaf):
        if isinstance(leaf, jax.Array):
            itemsize = np.dtype(leaf.dtype).itemsize
            for shard in leaf.addressable_shards:
                nbytes = int(np.prod(shard.data.shape)) * itemsize
                key = str(shard.device.id)
                totals[key] = totals.get(key, 0) + nbytes
        elif hasattr(leaf, "nbytes"):
            totals["-1"] = totals.get("-1", 0) + int(leaf.nbytes)
    return totals


class ShardedKVPool:
    """Mesh-resident per-slot KV pool: every layer's (k, v) caches allocated
    at the kv-head-sharded layout, with the per-shard handles accounted as
    ONE acquire/release-paired resource. `free()` is the release obligation
    (leaklint RESOURCE_TABLE "mesh-sharded KV pool"; leaksan kind
    `kv_shard_pool`): the owning engine's shutdown/`prepare_shutdown` path
    must call it so drain-and-retire of a TP replica provably drops every
    shard's buffer reference — a forgotten pool is `tp * layers * 2`
    stranded HBM buffers that no host object names.

    The engine's programs consume the caches they are given and return the next
    generation in the same buffers; the pool tracks the ALLOCATION lifetime,
    not any single buffer generation, and keeps the numbers a report needs
    (`total_bytes`, `per_device`) so that no report touches an array.
    """

    def __init__(self, *, n_layers: int, shape, dtype, mesh, n_kv_heads: int,
                 name: str = ""):
        self.name = name or f"kvpool-{id(self):x}"
        self.sharding = kv_cache_sharding(mesh, n_kv_heads)
        self.n_layers = int(n_layers)
        self.shape = tuple(shape)
        self._freed = False
        self.caches = [
            (mesh_zeros(shape, dtype, self.sharding),
             mesh_zeros(shape, dtype, self.sharding))
            for _ in range(self.n_layers)
        ]
        itemsize = np.dtype(dtype).itemsize
        self.total_bytes = (
            2 * self.n_layers * int(np.prod(self.shape)) * itemsize
        )
        self.shard_count = 2 * self.n_layers * max(1, tp_degree(mesh))
        # device id -> bytes, read once while the zeroth generation is alive
        self.per_device = per_device_byte_map(self.caches)
        _leaksan.track(
            "kv_shard_pool", token=self.name,
            detail=f"{self.shard_count} shards / {self.total_bytes} B",
        )

    def take(self):
        """Hand the initial buffer generation to the owning engine and drop
        the pool's own references: the engine's first program consumes it."""
        caches, self.caches = self.caches, None
        return caches

    def free(self):
        """Idempotent: drop the pool's buffer references and balance the
        leak-accounting books. The engine nulls its own cache list alongside
        (the last live references to the final buffer generation)."""
        if self._freed:
            return
        self._freed = True
        self.caches = None
        _leaksan.untrack("kv_shard_pool", token=self.name)


__all__ = [
    "ShardedKVPool",
    "adapter_table_shardings",
    "build_tp_mesh",
    "tp_device_count",
    "checkpoint_shardings",
    "kv_cache_sharding",
    "kv_prefix_sharding",
    "mesh_signature",
    "mesh_zeros",
    "param_spec",
    "per_device_byte_map",
    "per_device_bytes",
    "replicated",
    "shard_decode_params",
    "single_device_shardings",
    "tp_degree",
]
