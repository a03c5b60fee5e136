"""Device-resident objects: tensor payloads that stay in accelerator memory.

Design parity: reference "Ray Direct Transport" (RDT) —
`python/ray/experimental/gpu_object_manager/` + `@ray.remote(tensor_transport=...)`:
ObjectRefs whose tensor payload never leaves device memory on the producing actor;
consumers on the same actor use it with zero transfer, remote consumers fetch it
through a transport (NCCL/NIXL there). TPU-first shape: jax Arrays live in the
producing actor's HBM keyed by a small DeviceObjectRef descriptor that travels
through the ordinary object plane; same-actor resolution is a dict lookup (no
transfer), cross-process resolution is one host round-trip (device_get -> numpy ->
object plane). On TPU pods, tensors that must move BETWEEN chips belong inside
jitted SPMD programs where XLA schedules ICI collectives — this API is for keeping
large tensors pinned to an actor across calls (KV caches, optimizer state,
sampled rollouts) without paying host serialization per call.
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ray_tpu._private.ids import ActorID


@dataclass(frozen=True)
class DeviceObjectRef:
    """A handle to a tensor living in a specific actor's device memory.

    Round 3: descriptors are first-class refcounted references — `ref` is an
    ordinary ObjectRef owned by the pinning actor, so descriptors ride the
    sequenced borrow protocol like any ref, and the HBM pin releases when the
    LAST descriptor anywhere goes out of scope (RDT parity: reference
    `gpu_object_manager.py` frees device objects via the reference counter,
    not actor death)."""

    actor_id: ActorID
    key: str
    shape: tuple
    dtype: str
    ref: Optional[Any] = field(default=None, compare=False)

    def __repr__(self):
        return (
            f"DeviceObjectRef({self.key[:8]}@{self.actor_id.hex()[:8]}, "
            f"{self.dtype}{list(self.shape)})"
        )


class _ActorDeviceStore:
    """Per-process store of device arrays (the gpu_object_store.py role)."""

    def __init__(self):
        self._objects: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def put(self, key: str, value):
        with self._lock:
            self._objects[key] = value

    def get(self, key: str):
        with self._lock:
            if key not in self._objects:
                raise ValueError(
                    f"device object {key[:8]}… is not pinned here: it was freed, "
                    f"its owner restarted, or the descriptor is stale"
                )
            return self._objects[key]

    def pop(self, key: str):
        with self._lock:
            return self._objects.pop(key, None)

    def keys(self):
        with self._lock:
            return list(self._objects)


_store = _ActorDeviceStore()


def _current_actor_id() -> ActorID:
    from ray_tpu._private.worker import global_worker

    w = global_worker()
    if w.actor_id is None:
        raise RuntimeError(
            "device objects live in actor processes; put() must run inside an "
            "actor method (reference: RDT objects are actor-owned)"
        )
    return w.actor_id


def put(value) -> DeviceObjectRef:
    """Pin a (jax) array in THIS actor's device memory; return its descriptor.

    The descriptor is tiny and travels through the normal object plane. Its
    embedded ObjectRef is owned by this actor: when every holder's reference
    dies (tracked by the sequenced borrow protocol), the owner's free hook
    evicts the HBM pin automatically — no explicit free() needed."""
    import jax.numpy as jnp

    from ray_tpu._private import serialization
    from ray_tpu._private.worker import global_worker

    actor_id = _current_actor_id()  # validate context BEFORE pinning anything
    w = global_worker()
    # Unconditional device placement: a numpy input must land in HBM, or every
    # later use pays host->device per call; no-op for arrays already on device.
    arr = jnp.asarray(value)
    key = uuid.uuid4().hex
    _store.put(key, arr)
    # Back the descriptor with an owned, refcounted id (the record resolves to
    # a sentinel so a stray ray.get() on the raw ref returns something legible
    # instead of hanging); the free hook evicts the pin on last release.
    ref = w.put_inline_owned(
        serialization.dumps({"device_object": key, "actor": actor_id.hex()}),
        free_hook=lambda: _store.pop(key),
    )
    return DeviceObjectRef(
        actor_id=actor_id,
        key=key,
        shape=tuple(arr.shape),
        dtype=str(arr.dtype),
        ref=ref,
    )


def _run_on_owner(ref: DeviceObjectRef, local_fn, remote_fn):
    """Local dict op on the owner; one remote __rtpu_apply__ hop elsewhere."""
    from ray_tpu._private.worker import global_worker

    w = global_worker()
    if w.actor_id is not None and w.actor_id == ref.actor_id:
        return local_fn()
    import ray_tpu
    from ray_tpu.actor import ActorHandle, ActorMethod

    handle = ActorHandle(ref.actor_id, [], "DeviceObjectOwner")
    return ray_tpu.get(
        ActorMethod(handle, "__rtpu_apply__").remote(remote_fn, ref.key)
    )


def get(ref: DeviceObjectRef, *, to_device: bool = False,
        on_chunk=None, sharding=None, _legacy: bool = False):
    """Resolve a descriptor to its array.

    Same actor: the device array itself, zero transfer. Elsewhere the payload
    streams over a DeviceChannel (round 11, docs/device_channels.md): the
    owner writes chunked raw frames — a shm ring on the same node, RPC frames
    across nodes — and this side assembles as they arrive, so D2H, wire, and
    assembly pipeline instead of one blocking full-tensor hop through the
    object plane. `to_device=True` stages each chunk onto the local device as
    it lands (`jax.device_put` per chunk + one device concatenate), and
    `on_chunk(leaf_idx, elt_offset, typed_chunk)` tees arriving chunks to the
    caller. `sharding` (implies to_device) is the consumer's target mesh
    layout: a mesh-sharded payload whose shard bounds match stages each
    arriving shard straight onto its own device — the sharded PD handoff
    path (docs/serving_tp.md).

    Payloads below `devobj_stream_min_bytes` take the one-hop object-plane
    blob instead: a stream pays a control round-trip plus ring setup, which
    only amortizes on multi-MB tensors. `_legacy=True`
    forces that path explicitly."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu._private.worker import global_worker

    w = global_worker()
    if sharding is not None:
        to_device = True
    if w.actor_id is not None and w.actor_id == ref.actor_id:
        value = _store.get(ref.key)
        if sharding is not None:
            import jax

            # Same-actor, different layout: one explicit placement (XLA
            # moves the bytes over ICI; no host staging).
            value = jax.device_put(value, sharding)
        return value
    # on_chunk only has meaning on the stream, so a tee request overrides
    # the size gate.
    if (not _legacy
            and (on_chunk is not None
                 or _descriptor_nbytes(ref) >= CONFIG.devobj_stream_min_bytes)):
        try:
            return _stream_fetch(ref, to_device=to_device, on_chunk=on_chunk,
                                 sharding=sharding)
        except _StreamUnsupported:
            pass  # owner predates streams or this process has no data plane
    value = _run_on_owner(ref, lambda: _store.get(ref.key), _fetch_host)
    if to_device:
        import jax

        value = (jax.device_put(value, sharding) if sharding is not None
                 else jax.device_put(value))
    return value


def free(ref: DeviceObjectRef) -> bool:
    """EARLY-release the pinned array on its owner. Usually unnecessary:
    descriptors are refcounted and the pin evicts when the last one dies —
    free() is for reclaiming HBM while descriptors still circulate (their
    get() then raises)."""
    return _run_on_owner(ref, lambda: _store.pop(ref.key) is not None, _free_local)


def transfer(ref: DeviceObjectRef, dst_actor,
             free_src: bool = False) -> DeviceObjectRef:
    """COPY a device object into another actor's memory, peer-to-peer.

    The destination actor pulls the tensor FROM the owner directly (actor-to-
    actor over the data plane — the caller only relays the tiny descriptor,
    never the payload; reference:
    `experimental/collective/tensor_transport_manager.py` p2p transports).
    Returns a new descriptor owned by `dst_actor`. The SOURCE pin stays alive
    until its descriptors die (or pass ``free_src=True`` for move semantics —
    mind other holders: their get() will then raise)."""
    import ray_tpu
    from ray_tpu.actor import ActorMethod

    out = ray_tpu.get(
        ActorMethod(dst_actor, "__rtpu_apply__").remote(_pull_and_pin, ref)
    )
    if free_src:
        free(ref)
    return out


async def _pull_and_pin(_instance, ref: DeviceObjectRef) -> DeviceObjectRef:
    """Runs on the DESTINATION actor: fetch from the owner, pin locally.
    Async so an async-actor destination's event loop never stalls behind the
    (possibly multi-MB) pull; sync actors run the coroutine on their executor
    thread via __rtpu_apply__."""
    import asyncio

    value = await asyncio.to_thread(get, ref)  # owner-direct fetch
    return put(value)


class _StreamUnsupported(Exception):
    """Streamed fetch cannot run here (no data plane / pre-stream owner)."""


def _descriptor_nbytes(ref: DeviceObjectRef) -> int:
    """Payload size from the descriptor alone (no owner round-trip). Unknown
    dtypes (extension dtypes not registered here) count as large: streaming
    is the safe default for anything that might be big."""
    import numpy as np

    try:
        itemsize = np.dtype(ref.dtype).itemsize
    except TypeError:
        return 1 << 62
    n = itemsize
    for d in ref.shape:
        n *= int(d)
    return n


# -- in-flight host-snapshot dedupe (round 11 satellite) ---------------------
# Concurrent consumers pulling the SAME key used to materialize the full
# tensor on the owner's executor once PER CONSUMER. One in-flight snapshot
# per key is shared by every fetch that arrives while it materializes; the
# entry clears on completion so memory is bounded by live requests, not a
# cache.
_snapshot_lock = threading.Lock()
_inflight_snapshots: Dict[str, list] = {}  # key -> [Event, value, exc]
_snapshot_materializations = 0  # introspection/testing
_TEST_SNAPSHOT_DELAY_S = 0.0  # test hook: widen the dedupe window


def _host_snapshot(key: str):
    """Host numpy view of a pinned device array; concurrent callers share one
    D2H materialization per key."""
    import numpy as np

    global _snapshot_materializations
    with _snapshot_lock:
        entry = _inflight_snapshots.get(key)
        if entry is None:
            entry = [threading.Event(), None, None]
            _inflight_snapshots[key] = entry
            owner = True
            _snapshot_materializations += 1
        else:
            owner = False
    if not owner:
        entry[0].wait()
        if entry[2] is not None:
            raise entry[2]
        return entry[1]
    try:
        arr = _store.get(key)
        if _TEST_SNAPSHOT_DELAY_S:
            time.sleep(_TEST_SNAPSHOT_DELAY_S)
        entry[1] = np.asarray(arr)
        return entry[1]
    except BaseException as e:  # noqa: BLE001 - waiters must observe failure
        entry[2] = e
        raise
    finally:
        with _snapshot_lock:
            _inflight_snapshots.pop(key, None)
        entry[0].set()


async def _fetch_host(_instance, key: str):
    """Runs on the owning actor: device -> host for the object plane. Async so
    an async-actor owner's event loop never stalls behind the D2H copy of a
    large tensor (KV prefixes are tens of MB) — the copy runs on a thread;
    sync-actor owners just run the coroutine on their executor thread.
    Concurrent fetches of one key share a single in-flight snapshot."""
    import asyncio

    return await asyncio.to_thread(_host_snapshot, key)


def _free_local(_instance, key: str) -> bool:
    return _store.pop(key) is not None


# -- chunked streaming (round 11 tentpole) -----------------------------------

_active_streams = 0  # writer-side pumps still holding a snapshot/segment
_streams_lock = threading.Lock()


def active_streams() -> int:
    """Writer-side streams still live in THIS process (introspection: a
    drained/aborted stream must release its snapshot pin and shm segment)."""
    with _streams_lock:
        return _active_streams


def _register_stream_ledger():
    """Join the device-memory ledger (docs/observability.md "compute
    plane"): a live stream pins a host snapshot + shm segment; the ledger
    surfaces the count so an OOM snapshot can implicate a stuck pump even
    though the pinned bytes are host-side (reported as count, not bytes)."""
    from ray_tpu.util import xprof

    xprof.register_memory_owner(
        "device_channel_streams",
        lambda: {"bytes": 0, "streams": active_streams()},
    )


_register_stream_ledger()


_devobj_metrics: dict = {}
_devobj_metrics_lock = threading.Lock()


def _metric(name: str):
    with _devobj_metrics_lock:
        m = _devobj_metrics.get(name)
        if m is None:
            from ray_tpu.util import metrics

            if name == "devobj_transfer_bytes":
                m = metrics.Counter(
                    "devobj_transfer_bytes",
                    "tensor bytes moved by device-object fetches/transfers",
                )
            else:
                m = metrics.Histogram(
                    "devobj_transfer_seconds",
                    "wall time of device-object fetches/transfers",
                    boundaries=[0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10],
                )
            _devobj_metrics[name] = m
        return m


def _note_transfer(nbytes: int, seconds: float):
    try:
        _metric("devobj_transfer_bytes").inc(nbytes)
        _metric("devobj_transfer_seconds").observe(seconds)
    except Exception:
        pass  # observability must never break the transfer


def _open_stream(_instance, key: str, reader_node, chunk_bytes):
    """Runs on the OWNING actor: mint a DeviceChannel toward `reader_node`
    and pump the pinned array through it on a background thread. Returns the
    (picklable) channel for the reader end. The pump holds its own reference
    to the array, so a concurrent free() cannot unpin bytes mid-stream, and
    destroys the ring once the reader drained it (or closed early)."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.experimental.channel import ChannelClosed
    from ray_tpu.experimental.device_channel import DeviceChannel

    global _active_streams
    arr = _store.get(key)  # raises for freed/stale keys BEFORE minting a ring
    w = global_worker()
    same_node = reader_node is not None and reader_node == w.node_id
    ch = DeviceChannel.create(
        same_node=same_node, chunk_bytes=chunk_bytes,
        owner=None if same_node else ("actor", w.actor_id),
    )
    with _streams_lock:
        _active_streams += 1
    from ray_tpu.devtools import leaksan as _leaksan

    stream_token = f"devobj-stream:{key[:8]}@{id(ch):x}"
    _leaksan.track("devobj_stream", token=stream_token)

    def pump():
        global _active_streams
        try:
            ch.send(arr, timeout=120.0)
            ch.drain(timeout=120.0)
        except (ChannelClosed, TimeoutError):
            pass  # reader closed early or died: unwind, release the pin
        except Exception:
            pass  # never let a pump thread take the actor down
        finally:
            try:
                ch.destroy()
            finally:
                with _streams_lock:
                    _active_streams -= 1
                _leaksan.untrack("devobj_stream", token=stream_token)

    threading.Thread(target=pump, name="devobj-stream", daemon=True).start()
    return ch


def _stream_fetch(ref: DeviceObjectRef, *, to_device: bool, on_chunk=None,
                  sharding=None):
    """Reader side of the chunked pull; raises _StreamUnsupported when the
    topology cannot stream (caller falls back to the object-plane blob)."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu._private.config import CONFIG
    from ray_tpu.actor import ActorHandle, ActorMethod

    w = global_worker()
    if CONFIG.llm_channel_chunk_bytes <= 0:
        raise _StreamUnsupported()
    handle = ActorHandle(ref.actor_id, [], "DeviceObjectOwner")
    t0 = time.monotonic()
    ch = ray_tpu.get(
        ActorMethod(handle, "__rtpu_apply__").remote(
            _open_stream, ref.key, w.node_id, CONFIG.llm_channel_chunk_bytes
        )
    )
    try:
        if to_device:
            value = ch.recv_device(timeout=120.0, sharding=sharding)
            nbytes = sum(
                int(x.size) * x.dtype.itemsize
                for x in _leaves_of(value)
            )
        else:
            value = ch.recv(on_chunk=on_chunk, timeout=120.0)
            nbytes = sum(x.nbytes for x in _leaves_of(value))
    except BaseException:
        # Unwind the writer: close wakes its blocked send, so the pinned
        # snapshot and the ring release instead of leaking.
        try:
            ch.close()
        except Exception:
            pass
        raise
    _note_transfer(nbytes, time.monotonic() - t0)
    return value


def _leaves_of(value):
    import numpy as np

    if isinstance(value, np.ndarray):
        return [value]
    import sys as _sys

    jax = _sys.modules.get("jax")
    if jax is not None and isinstance(value, jax.Array):
        return [value]
    if isinstance(value, dict):
        return [x for v in value.values() for x in _leaves_of(v)]
    if isinstance(value, (list, tuple)):
        return [x for v in value for x in _leaves_of(v)]
    return []


def stored_keys() -> list:
    """Keys pinned in THIS process (introspection/testing)."""
    return _store.keys()
