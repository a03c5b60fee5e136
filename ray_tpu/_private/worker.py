"""CoreWorker: the in-process runtime embedded in every driver and worker process.

Design parity: reference `src/ray/core_worker/core_worker.h` (SubmitTask :856, CreateActor
:881, SubmitActorTask :938, Put :483, Get :659) + `python/ray/_private/worker.py`. Holds
the in-process memory store (reference: store_provider/memory_store), the reference counter
(reference_counter.h), the function manager, dependency-gated task submission (reference:
DependencyResolver in task_submission/), and the task execution loop with per-caller
ordered actor queues (task_execution/ actor scheduling queues).
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import traceback
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

from ray_tpu._private import rpc, serialization
from ray_tpu._private.config import CONFIG, bind_host_for, get_node_ip
from ray_tpu._private.function_manager import FunctionManager
from ray_tpu._private.ids import ActorID, NodeID, ObjectID, TaskID, WorkerID, _Counter
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.object_store import LocalObjectReader
from ray_tpu.exceptions import (
    GetTimeoutError,
    ObjectLostError,
    RayTpuError,
    RayTpuTaskError,
)

_global_worker: Optional["CoreWorker"] = None
_global_lock = threading.Lock()
_MISS = object()  # local-arena fast-path miss sentinel

# Starting per-worker pipeline depth for the lease fast path
# (CONFIG.lease_pipeline_min_depth). Shallow by default so a burst queues
# work and acquires more workers (parallelism); lease denials ramp the depth
# toward CONFIG.lease_worker_slots (throughput via large coalesced frames
# once the node is saturated). 2, not 1: one task executing + one parked
# keeps the worker from going idle during the result/refill round trip.
def _lease_depth_min() -> int:
    return max(1, CONFIG.lease_pipeline_min_depth)


def _addr_key(addr: dict) -> tuple:
    """Hashable identity of a worker address (borrower bookkeeping)."""
    return (addr["node_id"].hex(), addr["worker_id"].hex())


def global_worker() -> "CoreWorker":
    if _global_worker is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _global_worker


def global_worker_or_none() -> Optional["CoreWorker"]:
    return _global_worker


def set_global_worker(worker: Optional["CoreWorker"]):
    global _global_worker
    with _global_lock:
        _global_worker = worker


class _Record:
    __slots__ = ("data", "error", "in_plasma", "resolved", "event", "callbacks")

    def __init__(self):
        self.data: bytes | None = None
        self.error = False
        self.in_plasma = False
        self.resolved = False
        self.event = threading.Event()
        self.callbacks: list = []


class MemoryStore:
    """In-process store for inline objects and pending futures (memory_store.h parity)."""

    def __init__(self):
        self._records: dict[ObjectID, _Record] = {}
        self._lock = threading.Lock()

    def create_pending(self, object_id: ObjectID) -> _Record:
        with self._lock:
            rec = self._records.get(object_id)
            if rec is None:
                rec = _Record()
                self._records[object_id] = rec
            return rec

    def get(self, object_id: ObjectID) -> _Record | None:
        with self._lock:
            return self._records.get(object_id)

    def resolve(self, object_id: ObjectID, data: bytes | None, error: bool,
                in_plasma: bool) -> bool:
        """Resolve an existing record. Returns False if the record was already freed
        (all refs dropped before the result arrived) — caller should discard/free."""
        with self._lock:
            rec = self._records.get(object_id)
            if rec is None:
                return False
            if rec.resolved and not rec.error and error:
                # First success wins: a late failure report (e.g. delegated-task
                # recovery racing a completion that already landed) must not
                # clobber a delivered result.
                return True
            rec.data = data
            rec.error = error
            rec.in_plasma = in_plasma
            rec.resolved = True
            callbacks = rec.callbacks
            rec.callbacks = []
        rec.event.set()
        for cb in callbacks:
            try:
                cb(object_id, rec)
            except Exception:
                traceback.print_exc()
        return True

    def add_done_callback(self, object_id: ObjectID, cb) -> bool:
        """Returns True if registered (pending), False if already resolved."""
        with self._lock:
            rec = self._records.get(object_id)
            if rec is None:
                rec = _Record()
                self._records[object_id] = rec
            if rec.resolved:
                return False
            rec.callbacks.append(cb)
            return True

    def pop(self, object_id: ObjectID):
        with self._lock:
            self._records.pop(object_id, None)


class ReferenceCounter:
    """Distributed reference counts with a sequenced borrowing protocol.

    Reference: `src/ray/core_worker/reference_counter.h` — the owner frees an
    object cluster-wide only when (a) its own local count is zero AND (b) every
    registered borrower has released.

    Borrow registration is SEQUENCED through the task protocol, never a bare
    fire-and-forget racing the owner's release:

    - **Task args**: while a task executes, its borrowed arg refs are protected
      by the caller's arg pins, so the executor defers registration entirely
      (a per-task borrow sink). Refs still held at completion ride the reply's
      `borrows` list; the caller records the executor as a borrower BEFORE it
      releases those pins (same message, strict order). The executor's later
      release routes to the caller (its borrow parent), forming the reference's
      borrower tree rather than a flat owner-centric count.
    - **Result refs**: refs serialized into a task's results are captured at
      pickle time; the executor pre-registers the caller as a sub-borrower
      before replying and the reply's `result_refs` pre-seed the caller's
      parent table, so the caller's first local ref never emits a racing +1
      and its release routes back to the executor.
    - Refs that arrive outside the task protocol (inside a put object) keep
      the legacy immediate report as a best-effort fallback.

    Borrower counts are keyed per borrower address; an audit loop drops
    borrowers whose process died without releasing (raylet/GCS death signals +
    direct pings), so crashes reconcile instead of leaking the object.
    """

    def __init__(self, worker: "CoreWorker"):
        self._counts: dict[ObjectID, int] = {}
        self._owned: set[ObjectID] = set()
        # id -> {borrower_key: count}; for owned ids these are direct borrowers,
        # for borrowed ids they are sub-borrowers this process handed refs to.
        self._borrows: dict[ObjectID, dict[str, int]] = {}
        self._borrowed_owner: dict[ObjectID, dict] = {}  # borrowed id -> PARENT address
        self._pending_free: set[ObjectID] = set()  # local zero, waiting on borrowers
        # Borrowed ids whose local count hit zero while sub-borrowers remain:
        # the upstream release is deferred until they drain.
        self._pending_upstream: set[ObjectID] = set()
        # Borrowed ids registered via the sequenced paths that have not yet
        # taken a local ref (pre-seeded by result_refs): the first local ref
        # must not emit the legacy racing report.
        self._preregistered: set[ObjectID] = set()
        # Ids first borrowed inside the currently-executing task (deferred).
        self._task_deferred: set[ObjectID] = set()
        # borrowed id -> the object's TRUE owner (never re-parented). Used to
        # mirror sub-borrower registrations to the owner so an INTERMEDIATE
        # borrower's crash cannot free an object a live grandchild holds
        # (reference: transitive borrower propagation,
        # src/ray/core_worker/reference_counter.h:43).
        self._true_owner: dict[ObjectID, dict] = {}
        self._lock = threading.Lock()
        self._worker = worker
        # GC-safety: __del__ may fire via garbage collection INSIDE a section
        # that already holds one of this runtime's locks (same thread), so
        # finalizers must never lock. They append to this deque (GIL-atomic)
        # and the release runs later from drain_deferred() on a normal API path.
        self._deferred: deque = deque()

    def defer_remove(self, object_id: ObjectID):
        """Finalizer-safe ref release: enqueue only; no locks, no RPC."""
        self._deferred.append(("ref", object_id))

    def defer_actor_pin_release(self, actor_id):
        self._deferred.append(("actor_pins", actor_id))

    def drain_deferred(self):
        """Apply releases queued by finalizers. Called from non-finalizer paths
        (put/get/submit/...) and the periodic flush loop, never from __del__."""
        while True:
            try:
                kind, ident = self._deferred.popleft()
            except IndexError:
                return
            if kind == "ref":
                self.remove_local_ref(ident)
            else:
                self._worker.release_actor_arg_pins(ident)

    def add_owned(self, object_id: ObjectID):
        with self._lock:
            self._owned.add(object_id)

    def add_local_ref(self, object_id: ObjectID, owner: dict | None = None):
        if owner is not None:
            self.record_true_owner(object_id, owner)
        report_to = None
        materialized = False
        with self._lock:
            n = self._counts.get(object_id, 0)
            self._counts[object_id] = n + 1
            self._pending_free.discard(object_id)  # re-acquired before borrowers drained
            self._pending_upstream.discard(object_id)
            if (
                n == 0
                and owner is not None
                and object_id not in self._owned
                and owner.get("worker_id") is not None
                and owner["worker_id"] != self._worker.worker_id
            ):
                if object_id in self._preregistered:
                    # Sequenced handoff (result_refs): parent already seeded,
                    # parent already counted us — no report. The materialized
                    # note runs after this lock drops (lock order: never take
                    # _embedded_lock under rc._lock — _settle_embedded_on_free
                    # holds them in the opposite order).
                    self._preregistered.discard(object_id)
                    materialized = True
                elif object_id not in self._borrowed_owner:
                    sink = self._worker._task_borrow_sink()
                    if sink is not None:
                        # Executing a task: the caller's arg pins protect the
                        # object until completion; registration (if the ref
                        # survives the task) rides the reply, sequenced.
                        sink[object_id] = owner
                        self._borrowed_owner[object_id] = owner
                        self._task_deferred.add(object_id)
                    else:
                        # Outside the task protocol (ref inside a put object):
                        # legacy immediate report, best effort.
                        self._borrowed_owner[object_id] = owner
                        report_to = owner
        if materialized:
            self._worker._note_embedded_materialized(object_id)
        if report_to is not None:
            self._worker._report_borrow(object_id, report_to, +1)

    def remove_local_ref(self, object_id: ObjectID):
        free = False
        report_to = None
        with self._lock:
            n = self._counts.get(object_id, 0) - 1
            if n > 0:
                self._counts[object_id] = n
            else:
                self._counts.pop(object_id, None)
                if object_id in self._task_deferred:
                    if self._borrow_total_locked(object_id) > 0:
                        # A sub-borrower registered with us mid-task (we handed
                        # the ref onward): we must stay in the chain — the
                        # reply handoff re-parents us to the caller and lists
                        # the id in `borrows`.
                        self._task_deferred.discard(object_id)
                        self._pending_upstream.add(object_id)
                    else:
                        # Dropped before the task finished: registration never
                        # happened anywhere, so nothing to report.
                        self._task_deferred.discard(object_id)
                        self._borrowed_owner.pop(object_id, None)
                        sink = self._worker._task_borrow_sink()
                        if sink is not None:
                            sink.pop(object_id, None)
                elif object_id in self._borrowed_owner:
                    if self._borrow_total_locked(object_id) > 0:
                        # Sub-borrowers still hold refs we handed out: the
                        # upstream release waits for them.
                        self._pending_upstream.add(object_id)
                    else:
                        report_to = self._borrowed_owner.pop(object_id)
                        self._true_owner.pop(object_id, None)
                elif object_id in self._owned:
                    if self._borrow_total_locked(object_id) > 0:
                        self._pending_free.add(object_id)
                    else:
                        self._owned.discard(object_id)
                        free = True
        if report_to is not None:
            self._worker._report_borrow(object_id, report_to, -1)
        if free:
            self._worker._free_owned_object(object_id)

    def _borrow_total_locked(self, object_id: ObjectID) -> int:
        # Negative entries are pending releases whose registration is still in
        # flight (see _apply_borrow): they hold nothing alive.
        return sum(v for v in self._borrows.get(object_id, {}).values() if v > 0)

    def add_sub_borrow(self, object_id: ObjectID, borrower_key: str):
        """Count a downstream borrower BEFORE the message that informs it is
        sent (the sequencing that makes the handoff race-free)."""
        with self._lock:
            per = self._borrows.setdefault(object_id, {})
            per[borrower_key] = per.get(borrower_key, 0) + 1
            mirror = self._mirror_target_locked(object_id)
        if mirror is not None:
            self._worker._report_borrow(object_id, mirror, +1, borrower_key)

    def _mirror_target_locked(self, object_id: ObjectID) -> dict | None:
        """The true owner to mirror a sub-borrower count to — None when this
        process IS the owner (its table is already authoritative)."""
        if object_id in self._owned:
            return None
        return self._true_owner.get(object_id)

    def record_true_owner(self, object_id: ObjectID, owner: dict | None):
        if owner is None or owner.get("worker_id") == self._worker.worker_id:
            return
        with self._lock:
            if object_id not in self._owned:
                self._true_owner.setdefault(object_id, owner)

    def pre_register_borrow(self, object_id: ObjectID, parent: dict):
        """Caller side of a result-ref handoff: seed the parent so the first
        local ref neither re-reports nor routes its release to the raw owner."""
        with self._lock:
            if (
                object_id in self._owned
                or object_id in self._borrowed_owner
                or parent.get("worker_id") == self._worker.worker_id
            ):
                return False
            self._borrowed_owner[object_id] = parent
            self._preregistered.add(object_id)
            return True

    def settle_unmaterialized(self, object_id: ObjectID) -> dict | None:
        """A reply's embedded ref was never deserialized and its containing
        result is gone: undo the pre-registration; returns the parent to
        release to (the executor pre-counted us)."""
        with self._lock:
            if object_id not in self._preregistered:
                return None
            self._preregistered.discard(object_id)
            return self._borrowed_owner.pop(object_id, None)

    def promote_task_borrows(self, kept: dict, parent: dict):
        """Executor side at task completion: arg borrows that survived the task
        re-parent to the caller (whose reply-side registration is sequenced
        ahead of its pin release)."""
        with self._lock:
            for object_id in kept:
                if object_id in self._task_deferred:
                    self._task_deferred.discard(object_id)
                    self._borrowed_owner[object_id] = parent
                elif (
                    object_id in self._pending_upstream
                    and object_id in self._borrowed_owner
                ):
                    # Held only by sub-borrowers now: re-route the eventual
                    # upstream release to the caller, who counts us via the
                    # reply's `borrows` list.
                    self._borrowed_owner[object_id] = parent

    def promote_captured(self, object_ids, parent: dict) -> list:
        """Deferred arg borrows captured into a task's results: re-parent to
        the caller immediately (their only local ref may die with the frame)
        and return those promoted, for the reply's `borrows` list."""
        promoted = []
        with self._lock:
            for object_id in object_ids:
                if object_id in self._task_deferred:
                    self._task_deferred.discard(object_id)
                    self._borrowed_owner[object_id] = parent
                    promoted.append(object_id)
        return promoted

    def update_borrow(self, object_id: ObjectID, delta: int,
                      borrower_key: str = "?"):
        """Parent side: a borrower registered (+1) or released (-1)."""
        self._apply_borrow(object_id, delta, borrower_key)

    def drop_borrow_entry(self, object_id: ObjectID, borrower_key: str):
        """Audit verdict: a live borrower no longer holds this id (its release
        was lost to a crashed parent): reconcile just that entry."""
        self._apply_borrow(object_id, None, borrower_key)

    def drop_borrower(self, borrower_key: str):
        """A borrower process died without releasing: reconcile its counts."""
        with self._lock:
            stale = [
                oid for oid, per in self._borrows.items() if borrower_key in per
            ]
        for oid in stale:
            self._apply_borrow(oid, None, borrower_key)

    def _apply_borrow(self, object_id: ObjectID, delta, borrower_key: str):
        free = False
        report_to = None
        mirror_to = None
        mirror_delta = 0
        with self._lock:
            per = self._borrows.setdefault(object_id, {})
            # Mirror every sub-borrower count change to the TRUE owner (no-op
            # when we are the owner): the owner's table then lists every
            # transitive borrower, so this process crashing cannot strand a
            # live grandchild's count. Mirrors land via the same routed
            # borrow_update; negative-entry tolerance absorbs reorders.
            mirror_to = self._mirror_target_locked(object_id)
            if mirror_to is not None:
                if delta is None:
                    mirror_delta = -max(per.get(borrower_key, 0), 0)
                else:
                    mirror_delta = delta
            if delta is None:
                per.pop(borrower_key, None)  # borrower died: drop all its refs
            else:
                # A release may arrive BEFORE its matching registration when the
                # two ride different channels (reply-borne +1 vs raylet-routed
                # -1): keep the negative entry as a pending release so the late
                # +1 nets to zero instead of resurrecting a count nobody will
                # ever release.
                n = per.get(borrower_key, 0) + delta
                if n == 0:
                    per.pop(borrower_key, None)
                else:
                    per[borrower_key] = n
            if not any(v > 0 for v in per.values()):
                if not per:
                    self._borrows.pop(object_id, None)
                if (
                    object_id in self._pending_free
                    and self._counts.get(object_id, 0) <= 0
                    and object_id in self._owned
                ):
                    self._pending_free.discard(object_id)
                    self._owned.discard(object_id)
                    free = True
                elif (
                    object_id in self._pending_upstream
                    and self._counts.get(object_id, 0) <= 0
                ):
                    self._pending_upstream.discard(object_id)
                    report_to = self._borrowed_owner.pop(object_id, None)
                    self._true_owner.pop(object_id, None)
        if mirror_to is not None and mirror_delta:
            self._worker._report_borrow(object_id, mirror_to, mirror_delta,
                                        borrower_key)
        if report_to is not None:
            self._worker._report_borrow(object_id, report_to, -1)
        if free:
            self._worker._free_owned_object(object_id)

    def borrower_snapshot(self) -> dict[str, list[ObjectID]]:
        """borrower_key -> ids it holds (for the crash-audit loop)."""
        with self._lock:
            out: dict[str, list[ObjectID]] = {}
            for oid, per in self._borrows.items():
                for key in per:
                    out.setdefault(key, []).append(oid)
            return out

    def num_refs(self, object_id: ObjectID) -> int:
        with self._lock:
            return self._counts.get(object_id, 0)

    def num_borrows(self, object_id: ObjectID) -> int:
        with self._lock:
            return self._borrow_total_locked(object_id)


class _LogDeduplicator:
    """Collapse identical log lines spamming from many workers (reference:
    python/ray/_private/ray_logging LogDeduplicator — the '[repeated Nx across
    cluster]' behavior). Lines are keyed with digits masked so counters and
    pids don't defeat the match; the first occurrence prints immediately, later
    ones within the window are counted and summarized when the window expires.
    Disabled via RAY_TPU_LOG_DEDUP=0 (every line passes through verbatim)."""

    @property
    def WINDOW_S(self) -> float:
        return CONFIG.log_dedup_window_s

    def __init__(self):
        import re

        self._mask = re.compile(r"\d+")
        self._seen: dict[str, dict] = {}
        self.enabled = os.environ.get("RAY_TPU_LOG_DEDUP", "1") not in (
            "0", "false", "off"
        )

    def ingest(self, prefix: str, pid, lines) -> str:
        if not self.enabled:
            return "".join(f"{prefix} {ln}\n" for ln in lines)
        now = time.monotonic()
        out = []
        out.append(self.flush_expired(now))
        for ln in lines:
            key = self._mask.sub("#", ln)
            entry = self._seen.get(key)
            # flush_expired above evicted every stale entry, so a hit here is
            # always inside the window.
            if entry is not None:
                entry["count"] += 1
                entry["pids"].add(pid)
                continue
            self._seen[key] = {
                "first_t": now, "count": 0, "line": ln, "prefix": prefix,
                "pids": {pid},
            }
            out.append(f"{prefix} {ln}\n")
        return "".join(out)

    def flush_expired(self, now: float | None = None) -> str:
        now = time.monotonic() if now is None else now
        out = []
        for key in list(self._seen):
            entry = self._seen[key]
            if now - entry["first_t"] >= self.WINDOW_S:
                del self._seen[key]
                if entry["count"]:
                    out.append(self._summary(entry))
        return "".join(out)

    @staticmethod
    def _summary(entry) -> str:
        n, pids = entry["count"], len(entry["pids"])
        return (
            f"{entry['prefix']} {entry['line']} "
            f"[repeated {n}x across {pids} process(es); set RAY_TPU_LOG_DEDUP=0 "
            f"to disable deduplication]\n"
        )


class _StreamState:
    """Owner-side state of one streaming-generator task (ObjectRefStream parity,
    reference task_manager.h). Items can arrive out of order (RPC dispatch is
    concurrent per message), so they buffer by index and emit in order."""

    def __init__(self):
        self.items: dict[int, "ObjectRef"] = {}
        self.total: int | None = None  # set at end-of-stream
        self.abort_error: Exception | None = None  # producer died, retries exhausted
        self.cond = threading.Condition()


class ObjectRefGenerator:
    """Iterator over the ObjectRefs yielded by a streaming task.

    Reference: `ObjectRefGenerator` / streaming generators
    (`num_returns="streaming"`). Each __next__ returns the next item's ObjectRef
    as soon as the executor has produced it — consumption overlaps production.
    A mid-stream exception in the generator body becomes a final error ref whose
    get() raises, followed by StopIteration.
    """

    def __init__(self, task_id: TaskID, worker: "CoreWorker"):
        self._task_id = task_id
        self._worker = worker
        self._consumed = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self._next(timeout=None)

    def _next(self, timeout: float | None):
        st = self._worker._streams.get(self._task_id)
        if st is None:
            raise StopIteration
        deadline = None if timeout is None else time.monotonic() + timeout
        with st.cond:
            while True:
                if self._consumed in st.items:
                    ref = st.items.pop(self._consumed)
                    self._consumed += 1
                    return ref
                if st.total is not None and self._consumed >= st.total:
                    self._worker._streams.pop(self._task_id, None)
                    raise StopIteration
                if st.abort_error is not None:
                    self._worker._streams.pop(self._task_id, None)
                    raise st.abort_error
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise GetTimeoutError(
                        f"no stream item within timeout for task {self._task_id.hex()}"
                    )
                st.cond.wait(0.2 if remaining is None else min(0.2, remaining))

    def __aiter__(self):
        return self

    async def __anext__(self):
        # StopIteration cannot cross an executor Future (Python converts it to
        # RuntimeError); end-of-stream travels as a sentinel instead.
        done = object()

        def step():
            try:
                return self.__next__()
            except StopIteration:
                return done

        item = await asyncio.get_running_loop().run_in_executor(None, step)
        if item is done:
            raise StopAsyncIteration
        return item

    def __del__(self):
        try:
            from ray_tpu.devtools import distsan

            # Local dict cleanup only: the finalizer tag asserts (under
            # RAY_TPU_DISTSAN=1) that no control-plane call sneaks in here.
            with distsan.finalizer("stream-iterator"):
                self._worker._streams.pop(self._task_id, None)
        except Exception:
            pass


class _ActorRuntime:
    """Execution state when this worker hosts an actor.

    Concurrency groups (reference: core_worker/task_execution/
    concurrency_group_manager.cc): each named group gets its OWN thread pool
    (sync actors) or semaphore (async actors) sized to its declared limit, so
    a method bound to one group cannot starve another — the default pool
    keeps max_concurrency for unbound methods. Dispatch releases tasks in
    per-caller seq order but never blocks on execution, so in-group ordering
    holds while groups stay independent. `out_of_order` skips seq gating
    entirely (reference: out_of_order_actor_submit_queue.cc)."""

    def __init__(self, instance, max_concurrency: int, is_async: bool,
                 concurrency_groups: dict | None = None,
                 method_groups: dict | None = None,
                 out_of_order: bool = False):
        self.instance = instance
        self.max_concurrency = max_concurrency
        self.is_async = is_async
        self.out_of_order = out_of_order
        self.concurrency_groups = dict(concurrency_groups or {})
        self.method_groups = dict(method_groups or {})
        self.expected_seq: dict[bytes, int] = {}
        self.buffered: dict[tuple[bytes, int], dict] = {}
        self.executor = ThreadPoolExecutor(max_workers=max_concurrency)
        self.group_executors: dict[str, ThreadPoolExecutor] = {}
        if not is_async:
            for gname, limit in self.concurrency_groups.items():
                self.group_executors[gname] = ThreadPoolExecutor(
                    max_workers=max(1, int(limit)),
                    thread_name_prefix=f"actor-cg-{gname}",
                )
        self.async_loop: asyncio.AbstractEventLoop | None = None
        self.semaphore: asyncio.Semaphore | None = None
        self.group_semaphores: dict[str, asyncio.Semaphore] = {}
        if is_async:
            self.async_loop = asyncio.new_event_loop()
            t = threading.Thread(target=self._run_loop, daemon=True, name="actor-asyncio")
            t.start()

    def group_of(self, spec) -> str | None:
        """Resolve a call's concurrency group: per-call override first, then
        the class-declared method binding. None = default pool."""
        return spec.get("concurrency_group") or self.method_groups.get(
            spec["method_name"]
        )

    def _run_loop(self):
        asyncio.set_event_loop(self.async_loop)
        self.semaphore = asyncio.Semaphore(self.max_concurrency)
        for gname, limit in self.concurrency_groups.items():
            self.group_semaphores[gname] = asyncio.Semaphore(max(1, int(limit)))
        self.async_loop.run_forever()


class CoreWorker:
    def __init__(
        self,
        mode: str,  # "driver" | "worker"
        raylet_addr: tuple[str, int],
        gcs_addr: tuple[str, int],
        worker_id: WorkerID | None = None,
        job_id=None,
        remote_data_plane: bool = False,
        proxy: tuple | None = None,
    ):
        self.mode = mode
        # Thin-client mode (reference: Ray Client, util/client/): this process
        # runs no local raylet, so plasma traffic rides RPC (put_bytes /
        # read_chunk) to a remote raylet instead of shared memory.
        self.remote_data_plane = remote_data_plane
        # (host, port, client_id) of a client proxy (util/client/proxier.py):
        # every control-plane dial tunnels through it (reference: proxier's
        # per-client routing of the Ray Client data channel).
        self.proxy = proxy
        self.session_token = os.urandom(8).hex()  # distinguishes init/shutdown cycles
        self.worker_id = worker_id or WorkerID.from_random()
        self.node_id: NodeID | None = None
        self.node_ip: str = "127.0.0.1"
        self._direct_bind_host: str = "127.0.0.1"
        self._store_arena: str | None = None
        self._store_ops: list[tuple] = []
        self._store_ops_lock = threading.Lock()
        self._store_ops_flushing = False
        self._result_queues: dict[int, tuple] = {}  # id(conn) -> (conn, [payloads])
        self._result_sending: set[int] = set()
        self._result_lock = threading.Lock()
        # Sequenced borrow handoffs embedded in task replies (see
        # ReferenceCounter docstring): task_id -> {refs, returns, src}.
        self._reply_embedded: dict = {}
        self._embedded_materialized: set[ObjectID] = set()
        self._embedded_lock = threading.Lock()
        # put object id -> refs embedded in its payload, pinned until the put
        # object is freed (contained-in protection; see put()).
        self._put_embedded_pins: dict[ObjectID, list[ObjectID]] = {}
        self._log_dedup = _LogDeduplicator()
        # Owned ids with an attached resource (e.g. a device-object HBM pin):
        # the hook runs when the id's last reference dies cluster-wide.
        self._owned_free_hooks: dict[ObjectID, Any] = {}
        self.job_id = job_id
        self.io = rpc.IoLoop(name=f"rtpu-io-{mode}")
        self.raylet: rpc.Connection | None = None
        self.gcs: rpc.Connection | None = None
        self.raylet_addr = raylet_addr
        # All GCS candidate addresses (one entry in the classic single-GCS
        # shape); gcs_addr tracks the CURRENT primary this worker talks to.
        from ray_tpu._private.gcs_replication import parse_addrs

        self.gcs_addrs: list[tuple[str, int]] = parse_addrs(gcs_addr)
        self.gcs_addr = self.gcs_addrs[0]
        self.memory_store = MemoryStore()
        self.reference_counter = ReferenceCounter(self)
        self.functions = FunctionManager(self)
        self.reader = LocalObjectReader()
        self._default_task_id = TaskID.from_random()  # driver "task" identity
        self._pending_promoted: dict[TaskID, list[ObjectID]] = {}
        self._put_counter = _Counter()
        self._task_counter = _Counter()
        # Lineage for reconstruction: owned return-object id -> shared entry
        # {"spec", "live": set of ids, "promoted": pinned arg ids} (task_manager.h:177).
        self._lineage: dict[ObjectID, dict] = {}
        self._lineage_lock = threading.Lock()
        self._reconstructing: set[ObjectID] = set()
        self._recon_attempts: dict[ObjectID, int] = {}
        self._actor_seq: dict[ActorID, _Counter] = {}
        self._actor_arg_pins: dict[ActorID, list[ObjectID]] = {}
        # Direct actor-call path (reference: ActorTaskSubmitter pushes method
        # calls straight to the actor process, no raylet per call,
        # task_submission/actor_task_submitter.h:67). Per-actor: cached direct
        # connection, in-flight specs (failed on conn loss), and a seq-ordered
        # send queue (deps may resolve out of order; sends must not).
        self._direct_server: rpc.RpcServer | None = None
        self._direct_actor: dict[ActorID, Any] = {}  # conn | None(=use raylet)
        self._direct_inflight: dict[ActorID, dict] = {}  # aid -> {task_id: spec}
        self._direct_send: dict[ActorID, dict] = {}  # aid -> {"next": int, "ready": {}}
        self._direct_lock = threading.Lock()
        # Cached worker leases for normal tasks (reference: lease caching +
        # PushNormalTask, normal_task_submitter.h:81,220): per resource shape,
        # leased workers that execute pushed tasks back-to-back with no raylet
        # hop per task.
        self._leases: dict[tuple, dict] = {}  # shape -> {"workers", "queue", ...}
        self._lease_inflight: dict[TaskID, tuple] = {}  # task_id -> (shape, wid)
        self._lease_oom: dict[WorkerID, str] = {}  # OOM causes from the raylet
        self._lease_lock = threading.Lock()
        self._streams: dict[TaskID, _StreamState] = {}  # owner side of streaming tasks
        self._task_executor = ThreadPoolExecutor(max_workers=4, thread_name_prefix="rtpu-exec")
        # Owner-pushed lease tasks run on ONE thread: the owner pipelines up to
        # lease_worker_slots specs ahead so the wire never idles, but execution
        # stays sequential per worker — a lease holds one resource slot
        # (reference: a core worker executes one task at a time).
        self._lease_executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="rtpu-lease")
        self._future_pool = ThreadPoolExecutor(max_workers=8, thread_name_prefix="rtpu-fut")
        self.actor_runtime: _ActorRuntime | None = None
        self.actor_id: ActorID | None = None
        self._connected = False
        self._gcs_reconnect_counter = None  # lazy util.metrics Counter
        self._task_events: list[dict] = []
        self._events_lock = threading.Lock()
        self._tls = threading.local()

    @property
    def current_task_id(self) -> TaskID:
        """The task identity of the calling thread (thread-local inside executors:
        concurrent tasks must stamp their own ObjectIDs for lineage to hold)."""
        return getattr(self._tls, "task_id", None) or self._default_task_id

    # ------------------------------------------------------------------ connect

    def connect(self):
        self.raylet = self.io.run(
            rpc.connect(*self.raylet_addr, handler=self, name=f"{self.mode}->raylet",
                        via=self.proxy)
        )
        self.gcs = self._connect_gcs_primary(deadline_s=60.0)
        direct_port = None
        if not self.remote_data_plane:
            # Direct-call server: peers (owners of actor calls / leased tasks,
            # cross-node channel readers) reach this process without a raylet
            # hop on the hot path. Drivers host one too: they are the writer
            # side of a compiled DAG's input channel. Bound on all interfaces
            # when this node advertises a routable IP, so remote-node peers can
            # actually dial the direct_addr the raylet publishes for us.
            bind = bind_host_for(get_node_ip(self.gcs_addr[0]))
            self._direct_server = self.io.run(
                rpc.RpcServer(lambda conn: self).start(host=bind)
            )
            direct_port = self._direct_server.port
            self._direct_bind_host = bind
        async def register():
            reply = await self.raylet.call(
                "register_worker", self.worker_id, self.mode, os.getpid(), direct_port,
                self._direct_bind_host,
            )
            # On the io loop, before it reads the raylet's next message: a task pushed right
            # behind the reply runs on another thread and may ask for the node id before this
            # thread wakes (tests/test_node_labels.py saw None under load).
            self.node_id = reply["node_id"]
            return reply

        reply = self.io.run(register())
        # Native-store direct data plane: with the arena name in hand, put/get
        # run entirely in shared memory (alloc/write/seal and lookup/read under
        # the arena's process-shared mutex) — no raylet RPC on the hot path.
        # Thin clients live on another host: the arena is unreachable for them.
        if not self.remote_data_plane:
            self._store_arena = reply.get("store_arena")
        node_ip = reply.get("node_ip", "127.0.0.1")
        # The IP peers may dial this worker's direct server on. Loopback when we
        # bound loopback-only, whatever the node advertises (compiled DAG driver
        # channels publish this).
        self.node_ip = (
            node_ip if self._direct_bind_host in ("0.0.0.0", node_ip) else "127.0.0.1"
        )
        if self.mode == "worker":
            self.raylet.on_close(lambda c: os._exit(0))
        elif os.environ.get("RAY_TPU_LOG_TO_DRIVER", "1") not in ("0", "false"):
            # Drivers see worker stdout/stderr live (reference: log_monitor.py
            # tails per-worker files and streams them to the driver).
            self.io.run(self.gcs.call("subscribe", "worker_logs"))
        if self.job_id is None:
            self.job_id = self.io.run(self.gcs.call("next_job_id"))
        self._connected = True
        self.io.spawn(self._event_flush_loop())
        self.io.spawn(self._borrow_audit_loop())
        return self

    def disconnect(self):
        self._connected = False
        try:
            self._drain_store_ops_sync()
        except Exception:
            pass
        try:
            for conn in list(self._direct_actor.values()):
                if conn is not None and not conn.closed:
                    self.io.run(conn.close())
            with self._lease_lock:
                lease_conns = [
                    w["conn"] for st in self._leases.values()
                    for w in st["workers"].values()
                ]
                self._leases.clear()
            for conn in lease_conns:
                if not conn.closed:
                    self.io.run(conn.close())
            if self.raylet is not None:
                self.io.run(self.raylet.close())
            if self.gcs is not None:
                self.io.run(self.gcs.close())
        except Exception:
            pass
        self.io.stop()
        self.reader.close()

    # ------------------------------------------------------------------ kv helpers

    def gcs_kv_put(self, ns: str, key: bytes, value: bytes, overwrite=True):
        return self.gcs_call("kv_put", ns, key, value, overwrite)

    def gcs_kv_get(self, ns: str, key: bytes):
        return self.gcs_call("kv_get", ns, key)

    def _connect_gcs_primary(self, deadline_s: float,
                             hint: tuple | None = None) -> rpc.Connection:
        """Dial GCS candidates until the current PRIMARY answers.

        A non-primary candidate (warm standby under quorum HA,
        docs/fault_tolerance.md) reports its role via `repl_status` and hints
        the primary's address; the probe follows hints first and otherwise
        walks the candidate list with exponential backoff + full jitter (a
        restarted/promoted GCS sees a spread-out thundering herd, not a
        synchronized stampede). Raises ConnectionLost past the deadline."""
        import random as _random

        deadline = time.monotonic() + deadline_s
        backoff = 0.05
        i = 0
        while True:
            addr = tuple(hint) if hint else self.gcs_addrs[i % len(self.gcs_addrs)]
            hint = None
            i += 1
            conn = None
            try:
                conn = self.io.run(
                    rpc.connect(*addr, handler=self,
                                name=f"{self.mode}->gcs", via=self.proxy)
                )
                st = self.io.run(conn.call("repl_status", timeout=5.0))
                if st.get("role") == "primary":
                    self.gcs_addr = addr
                    return conn
                hint = st.get("primary")
                self.io.run(conn.close())
            except (OSError, rpc.RpcError):
                if conn is not None:
                    try:
                        self.io.run(conn.close())
                    except Exception:
                        pass
            if time.monotonic() > deadline:
                raise rpc.ConnectionLost(
                    f"no GCS primary reachable at {self.gcs_addrs}"
                )
            if not hint:
                # Full jitter on the exponential step; never sleep past the
                # deadline (the final attempt should still get its shot).
                pause = backoff * (0.5 + _random.random())
                pause = min(pause, max(0.0, deadline - time.monotonic()))
                time.sleep(pause)
                backoff = min(backoff * 2.0, 2.0)

    def gcs_call(self, method: str, *args, timeout: float | None = None,
                 deadline_s: float | None = None):
        """GCS request with transparent reconnect + failover: the control
        plane may restart — or fail over to another head candidate — under us
        (reference: GCS clients buffer and retry during GCS downtime).

        ConnectionLost covers both a dead socket and a NOT_PRIMARY redirect
        (`rpc.NotPrimaryError` subclasses it, carrying the new primary's
        address); either way the call re-resolves the primary through
        `_connect_gcs_primary` and retries, up to a total deadline
        (`deadline_s`, default CONFIG.gcs_rpc_timeout_s), after which
        ConnectionLost surfaces to the caller."""
        from ray_tpu.devtools import distsan

        distsan.note_gcs_call(method)  # records if a hot/finalizer tag is active
        deadline = time.monotonic() + (
            deadline_s if deadline_s is not None else CONFIG.gcs_rpc_timeout_s
        )
        reconnects = 0
        while True:
            try:
                result = self.io.run(self.gcs.call(method, *args), timeout)
                if reconnects:
                    self._note_gcs_reconnects(reconnects)
                return result
            except rpc.ConnectionLost as e:
                if not self._connected or time.monotonic() > deadline:
                    raise
                hint = getattr(e, "primary", None)
                old = self.gcs
                if old is not None and not old.closed:
                    # A NOT_PRIMARY answer leaves the socket open; drop it so
                    # in-flight direct users fail fast onto the new conn.
                    try:
                        self.io.run(old.close())
                    except Exception:
                        pass
                self.gcs = self._connect_gcs_primary(
                    deadline_s=max(0.05, deadline - time.monotonic()),
                    hint=hint,
                )
                reconnects += 1

    def _note_gcs_reconnects(self, n: int):
        """Count successful GCS reconnections (`gcs_reconnect_total`). Called
        only after the re-issued request succeeded, so the nested KV flush
        inside the counter rides a healthy connection, never a retry loop."""
        try:
            if self._gcs_reconnect_counter is None:
                from ray_tpu.util.metrics import Counter

                self._gcs_reconnect_counter = Counter(
                    "gcs_reconnect_total",
                    "GCS client reconnections that recovered an in-flight call",
                )
            self._gcs_reconnect_counter.inc(n)  # raylint: disable=RL901 (rare reconnect event, not a data path; the nested flush rides the just-recovered connection — see docstring)
        except Exception:
            pass  # observability must never break the recovered call

    def raylet_call(self, method: str, *args, timeout: float | None = None):
        return self.io.run(self.raylet.call(method, *args), timeout)

    # ------------------------------------------------------------------ events

    def _record_event(self, **fields):
        fields["time"] = time.time()
        fields["worker_id"] = self.worker_id.hex()  # per-worker timeline lanes
        with self._events_lock:
            self._task_events.append(fields)
            if len(self._task_events) > CONFIG.event_buffer_size:
                del self._task_events[: len(self._task_events) // 2]

    async def _event_flush_loop(self):
        while self._connected:
            await asyncio.sleep(CONFIG.metrics_report_interval_s)
            # Backstop drain: refs dropped by GC with no later API activity.
            self.reference_counter.drain_deferred()
            # Dedup summaries for lines whose repeat window closed quietly.
            try:
                pending = self._log_dedup.flush_expired()
                if pending:
                    sys.stderr.write(pending)
                    sys.stderr.flush()
            except Exception:
                pass  # stderr may be closed at interpreter teardown; drop the summary
            with self._events_lock:
                batch, self._task_events = self._task_events, []
            if batch:
                try:
                    await self.gcs.call("report_task_events", batch)
                except rpc.RpcError:
                    pass

    # ------------------------------------------------------------------ put / get / wait

    def _owner_address(self) -> dict:
        return {"node_id": self.node_id, "worker_id": self.worker_id}

    def put_inline_owned(self, data: bytes, free_hook=None) -> ObjectRef:
        """Register a small owned object resolving to pre-serialized bytes,
        with an optional hook that runs when its last reference dies
        cluster-wide (device objects pin HBM behind these)."""
        self.reference_counter.drain_deferred()
        object_id = ObjectID.from_task(
            self.current_task_id, 0x50000000 + self._put_counter.next()
        )
        self.reference_counter.add_owned(object_id)
        self.memory_store.create_pending(object_id)
        self.memory_store.resolve(object_id, data, False, False)
        if free_hook is not None:
            self._owned_free_hooks[object_id] = free_hook
        return ObjectRef(object_id, self._owner_address())

    def put(self, value: Any) -> ObjectRef:
        self.reference_counter.drain_deferred()
        object_id = ObjectID.from_task(self.current_task_id, 0x40000000 + self._put_counter.next())
        # Capture refs embedded in the payload and pin them for the put
        # object's lifetime: the putter holds live refs at serialization time,
        # so the pin is sequenced (no fire-and-forget racing the owner's
        # free). Released in _free_owned_object when the put object dies —
        # the "contained_in" protection of the reference's reference_counter.
        prev_cap = getattr(self._tls, "ref_capture", None)
        self._tls.ref_capture = cap = []
        try:
            self._put_to_plasma(object_id, value, self._owner_address())
        finally:
            self._tls.ref_capture = prev_cap
        if cap:
            pins = []
            for eid, eowner in cap:
                self.reference_counter.add_local_ref(eid, eowner)
                pins.append(eid)
            self._put_embedded_pins[object_id] = pins
        self.reference_counter.add_owned(object_id)
        rec = self.memory_store.create_pending(object_id)
        rec.in_plasma = True
        rec.resolved = True
        rec.event.set()
        return ObjectRef(object_id, self._owner_address())

    def _put_to_plasma(self, object_id: ObjectID, value: Any, owner: dict):
        pickled, raw_buffers, total = serialization.serialized_size(value)
        self._write_plasma(object_id, pickled, raw_buffers, total, owner)

    def _write_plasma(self, object_id: ObjectID, pickled, raw_buffers, total: int,
                      owner: dict):
        """The single plasma write path: shared memory locally, RPC bytes for
        thin clients."""
        if self.remote_data_plane:
            self.raylet_call(
                "store_put_bytes", object_id,
                bytes(serialization.assemble(pickled, raw_buffers)), owner,
            )
            return
        if self._store_arena is not None and self._put_direct(
            object_id, pickled, raw_buffers, total, owner
        ):
            return
        shm_name = self.raylet_call("store_create", object_id, total)
        buf = self.reader.write_view(shm_name, total)
        serialization.write_parts(buf, pickled, raw_buffers)
        self.raylet_call("store_seal", object_id, total, owner)

    def _put_direct(self, object_id: ObjectID, pickled, raw_buffers, total: int,
                    owner: dict) -> bool:
        """Allocate, write, and seal straight in the shared arena; the raylet
        only learns about the sealed object via an async notify (location
        tracking + GCS directory). Falls back to the RPC path (returns False)
        when the arena is full — the raylet's create() spills LRU objects to
        disk, which only it can orchestrate.

        Reference: plasma clients memcpy into store-allocated buffers
        (`object_buffer_pool.h:32`); here even create/seal skip the socket."""
        from ray_tpu._private.object_store import _native_key

        key = _native_key(object_id)
        try:
            arena = self.reader._arena(self._store_arena)
        except Exception:
            self._store_arena = None  # arena gone (store restarted): RPC path
            return False
        try:
            off = arena.alloc(key, total)
        except FileExistsError:
            # Same id re-put (retry/reconstruction): if sealed it's already
            # readable — re-notify bookkeeping; otherwise another writer is
            # mid-put and the RPC path serializes against it.
            if arena.lookup(key) is None:
                return False
            self._notify_sealed(object_id, total, owner)
            return True
        except KeyError:
            return False
        if off is None:
            return False
        buf = arena.read(off, total)
        serialization.write_parts(buf, pickled, raw_buffers)
        arena.seal(key)
        self._notify_sealed(object_id, total, owner)
        return True

    def _notify_sealed(self, object_id: ObjectID, total: int, owner: dict):
        # Fire-and-forget: the arena itself is the source of truth for local
        # resolution; the notify only feeds the raylet's location bookkeeping
        # and the GCS object directory (cross-node discovery).
        self._queue_store_op(("sealed", object_id, total, owner))

    def _queue_store_op(self, op: tuple):
        """Batch store bookkeeping notifies (sealed/free): one IO-thread wakeup
        and one frame per window instead of per object. Order is preserved —
        seal-then-free of the same id must apply in order at the raylet."""
        with self._store_ops_lock:
            self._store_ops.append(op)
            if self._store_ops_flushing:
                return
            self._store_ops_flushing = True
        self.io.spawn(self._flush_store_ops())

    async def _flush_store_ops(self):
        await asyncio.sleep(CONFIG.object_report_flush_s / 2)
        with self._store_ops_lock:
            ops, self._store_ops = self._store_ops, []
            self._store_ops_flushing = False
        if ops and self.raylet is not None and not self.raylet.closed:
            try:
                await self.raylet.notify("store_ops_batch", ops)
            except Exception:
                pass  # raylet restart: unacked ops re-enter _store_ops via retry paths

    def _drain_store_ops_sync(self):
        """Flush pending store ops before disconnect so frees/seals aren't lost."""
        with self._store_ops_lock:
            ops, self._store_ops = self._store_ops, []
        if ops and self.raylet is not None and not self.raylet.closed:
            try:
                self.io.run(self.raylet.notify("store_ops_batch", ops))
            except Exception:
                pass

    def _get_direct(self, object_id: ObjectID):
        """Zero-RPC read of a locally-sealed object, or _MISS. The pinned view
        keeps the payload alive while any deserialized alias exists."""
        from ray_tpu._private.object_store import _native_key

        key = _native_key(object_id)
        try:
            arena = self.reader._arena(self._store_arena)
        except Exception:
            self._store_arena = None  # arena unopenable: stop trying per-get
            return _MISS
        try:
            found = arena.lookup(key)
            if found is None:
                return _MISS
            off, size = found
            buf = arena.read_pinned(key, off, size)
        except Exception:
            return _MISS  # evicted/spilled mid-read: resolve path re-locates
        return serialization.loads(buf)

    def _read_remote_object(self, object_id: ObjectID, size: int) -> bytes:
        """Thin-client read: stream the object over RPC in store-chunk units."""
        chunks = []
        offset = 0
        step = CONFIG.object_store_min_chunk_bytes
        while offset < size:
            data = self.raylet_call(
                "read_chunk", object_id, offset, min(step, size - offset)
            )
            if not data:
                raise ObjectLostError(object_id, "remote read returned no data")
            chunks.append(data)
            offset += len(data)
        return b"".join(chunks)

    def get(self, refs: list[ObjectRef], timeout: float | None = None) -> list[Any]:
        self.reference_counter.drain_deferred()
        deadline = None if timeout is None else time.monotonic() + timeout
        out = []
        for ref in refs:
            out.append(self._get_one(ref, deadline))
        return out

    @staticmethod
    def _decode_inline(rec: _Record):
        """Deserialize a resolved inline record, raising task errors in caller context."""
        value = serialization.loads(rec.data)
        if rec.error:
            raise value.as_instanceof_cause() if isinstance(value, RayTpuTaskError) else value
        return value

    def _get_one(self, ref: ObjectRef, deadline: float | None):
        rec = self.memory_store.get(ref.id)
        if rec is not None and not rec.resolved:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            if not rec.event.wait(remaining):
                raise GetTimeoutError(f"get() timed out waiting for {ref}")
        rec = self.memory_store.get(ref.id)
        if rec is not None and rec.resolved and not rec.in_plasma:
            return self._decode_inline(rec)
        # Local-arena fast path: a direct (pinning) lookup in shared memory
        # skips the resolve RPC entirely when the object lives on this node.
        if self._store_arena is not None:
            value = self._get_direct(ref.id)
            if value is not _MISS:
                if isinstance(value, RayTpuTaskError):
                    raise value.as_instanceof_cause()
                if isinstance(value, RayTpuError):
                    raise value
                return value
        # Plasma or borrowed: resolve via the raylet. "lost" (known object, zero live
        # copies) triggers lineage reconstruction: the owner re-runs the producing
        # task and the loop waits for the fresh copy to be sealed.
        hard_deadline = time.monotonic() + 300.0 if deadline is None else deadline
        recon_next = 0.0  # owner requests dedupe internally; borrowers back off
        while True:
            remaining = max(0.0, hard_deadline - time.monotonic())
            reply = self.raylet_call("resolve_object", ref.id, ref.owner, remaining, 0)
            if reply.get("error") == "lost":
                # A rebuild may already have routed an (inline) error result back.
                rec = self.memory_store.get(ref.id)
                if rec is not None and rec.resolved and not rec.in_plasma:
                    return self._decode_inline(rec)
                now = time.monotonic()
                if now >= hard_deadline:
                    raise GetTimeoutError(f"get() timed out waiting for {ref}")
                if now >= recon_next:
                    if not self._try_reconstruct(ref):
                        raise ObjectLostError(
                            ref.id,
                            f"{ref} was lost (all copies died) and could not be "
                            "reconstructed from lineage",
                        )
                    recon_next = now + 2.0
                time.sleep(0.1)
                continue
            break
        if reply.get("error"):
            if reply["error"] == "timeout":
                raise GetTimeoutError(f"get() timed out waiting for {ref}")
            raise ObjectLostError(ref.id, f"failed to resolve {ref}: {reply['error']}")
        if "inline" in reply:
            data = reply["inline"]
            value = serialization.loads(data)
        elif self.remote_data_plane:
            _shm_name, size = reply["shm"]
            try:
                raw = self._read_remote_object(ref.id, size)
            except rpc.RpcError:
                # Stale location (freed/evicted between resolve and read): one
                # re-resolve, mirroring the shared-memory branch below.
                reply = self.raylet_call("resolve_object", ref.id, ref.owner, remaining, 0)
                if reply.get("error") or "shm" not in reply:
                    raise ObjectLostError(ref.id, f"failed to re-resolve {ref}")
                _shm_name, size = reply["shm"]
                try:
                    raw = self._read_remote_object(ref.id, size)
                except rpc.RpcError as e:
                    raise ObjectLostError(
                        ref.id, f"object location stale twice for {ref}: {e}"
                    )
            value = serialization.loads(raw)
        else:
            shm_name, size = reply["shm"]
            try:
                buf = self.reader.read(shm_name, size)
            except (KeyError, FileNotFoundError, OSError):
                # Location went stale between resolve and read (the store spilled,
                # evicted, or freed+unlinked the object); one re-resolve gets the
                # new location. A second stale read means the object is gone.
                reply = self.raylet_call("resolve_object", ref.id, ref.owner, remaining, 0)
                if reply.get("error") or "shm" not in reply:
                    raise ObjectLostError(ref.id, f"failed to re-resolve {ref}")
                shm_name, size = reply["shm"]
                try:
                    buf = self.reader.read(shm_name, size)
                except (KeyError, FileNotFoundError, OSError) as e:
                    raise ObjectLostError(
                        ref.id, f"object location stale twice for {ref}: {e}"
                    )
            value = serialization.loads(buf)
        if isinstance(value, RayTpuTaskError):
            raise value.as_instanceof_cause()
        if isinstance(value, RayTpuError):
            raise value
        return value

    def wait(self, refs: list[ObjectRef], num_returns=1, timeout=None, fetch_local=True):
        self.reference_counter.drain_deferred()
        deadline = None if timeout is None else time.monotonic() + timeout
        pending = list(refs)
        ready: list[ObjectRef] = []
        while True:
            still = []
            for ref in pending:
                if self._is_ready(ref):
                    ready.append(ref)
                else:
                    still.append(ref)
            pending = still
            if len(ready) >= num_returns or not pending:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            time.sleep(CONFIG.get_poll_interval_s)
        return ready, pending

    def _is_ready(self, ref: ObjectRef) -> bool:
        rec = self.memory_store.get(ref.id)
        if rec is not None and rec.resolved:
            return True  # inline value present, or plasma object sealed (owner saw completion)
        owner = ref.owner
        if rec is not None and (
            owner is None or owner.get("worker_id") == self.worker_id
        ):
            # Self-owned pending object: completion lands in the memstore via
            # the task-reply/push path, so polling raylet/GCS per wait() cycle
            # adds pure RPC load (it cannot learn anything the memstore won't).
            return False
        # Borrowed ref: check the local/global store.
        try:
            info = self.raylet_call("store_info", ref.id)
        except rpc.RpcError:
            return False
        if info is not None:
            return True
        try:
            loc = self.gcs_call("object_locations", ref.id)
        except rpc.RpcError:
            return False
        return bool(loc and loc["locations"])

    def as_future(self, ref: ObjectRef) -> Future:
        return self._future_pool.submit(lambda: self.get([ref])[0])

    def _free_owned_object(self, object_id: ObjectID):
        rec = self.memory_store.get(object_id)
        self.memory_store.pop(object_id)
        self._drop_lineage(object_id)
        self._settle_embedded_on_free(object_id)
        for eid in self._put_embedded_pins.pop(object_id, ()):
            self.reference_counter.remove_local_ref(eid)
        hook = self._owned_free_hooks.pop(object_id, None)
        if hook is not None:
            try:
                hook()
            except Exception:
                pass
        if rec is not None and rec.in_plasma and self._connected:
            # Direct-arena eviction first: the block returns to the freelist
            # synchronously, so the next put reuses its (warm) pages instead of
            # faulting fresh ones. Pinned readers defer recycle to release.
            # The raylet notify keeps location bookkeeping + GCS in sync
            # (its own store.free of the already-evicted key is a no-op).
            if self._store_arena is not None:
                from ray_tpu._private.object_store import _native_key

                try:
                    self.reader._arena(self._store_arena).free(
                        _native_key(object_id), eager=True
                    )
                except Exception:
                    pass  # arena gone/object already evicted: the raylet free below is authoritative
            try:
                self._queue_store_op(("free", object_id))
            except Exception:
                pass

    def _report_borrow(self, object_id: ObjectID, owner: dict, delta: int,
                       borrower_key=None):
        """Route a borrow count change to `owner`. `borrower_key` defaults to
        this process; transitive mirrors pass the SUB-borrower's key so the
        true owner's table lists the actual holder."""
        if not self._connected or self.raylet is None:
            return
        key = borrower_key if borrower_key is not None else _addr_key(
            self._owner_address()
        )

        async def _send():
            delay = CONFIG.test_delay_borrow_report_ms
            if delay:  # fault injection: stress the reorder the sequenced
                await asyncio.sleep(delay / 1000)  # protocol must be immune to
            await self.raylet.notify(
                "report_borrow", object_id, owner, delta, key,
            )

        try:
            self.io.spawn(_send())
        except Exception:
            pass

    # ---------------------------------------------------- sequenced borrowing

    def _task_borrow_sink(self) -> dict | None:
        """The per-task borrow sink of the calling thread, if it is executing
        a task (executors defer borrow registration to the reply)."""
        return getattr(self._tls, "borrow_sink", None)

    def _note_serialized_ref(self, object_id: ObjectID, owner: dict | None):
        """ObjectRef.__reduce__ hook: capture refs pickled into task results."""
        cap = getattr(self._tls, "ref_capture", None)
        if cap is not None and owner is not None:
            cap.append((object_id, owner))

    def _note_embedded_materialized(self, object_id: ObjectID):
        """A pre-seeded result ref took its first local ref: its release now
        rides the normal borrow lifecycle, not the unmaterialized settle."""
        with self._embedded_lock:
            self._embedded_materialized.add(object_id)

    def _register_reply_embeds(self, payload: dict):
        """Caller side, BEFORE arg-pin release: absorb the reply's sequenced
        borrow handoffs."""
        src = payload.get("src")
        if src is None:
            return
        src_key = _addr_key(src)
        for oid in payload.get("borrows", ()):
            # The executor kept a borrowed arg ref beyond the task: count it
            # before releasing our pins (we are its borrow parent now).
            self.reference_counter.update_borrow(oid, +1, src_key)
        embeds = payload.get("result_refs") or ()
        pending = []
        for oid, _owner in embeds:
            if _owner is not None:
                self.reference_counter.record_true_owner(oid, _owner)
            if self.reference_counter.pre_register_borrow(oid, src):
                pending.append(oid)
            else:
                # We already own or borrow this id: the executor's pre-count
                # for us is unneeded — release it immediately (our existing
                # ref keeps the object alive through our own lifecycle).
                self._report_borrow(oid, src, -1)
        if pending:
            # Only returns still alive can carry the embedded refs to user
            # code; if every return was already dropped (fire-and-forget
            # submission), settle straight away.
            returns = {
                r["object_id"] for r in payload.get("results", ())
                if self.memory_store.get(r["object_id"]) is not None
            }
            if returns:
                with self._embedded_lock:
                    self._reply_embedded[payload["task_id"]] = {
                        "refs": pending, "returns": returns, "src": src,
                    }
            else:
                for oid in pending:
                    parent = self.reference_counter.settle_unmaterialized(oid)
                    if parent is not None:
                        self._report_borrow(oid, parent, -1)

    def _settle_embedded_on_free(self, freed_oid: ObjectID):
        """A result record was freed: embedded refs never materialized release
        back to the executor that pre-counted us."""
        if not self._reply_embedded:
            return
        candidates = []
        with self._embedded_lock:
            for task_id, entry in list(self._reply_embedded.items()):
                entry["returns"].discard(freed_oid)
                if entry["returns"]:
                    continue
                del self._reply_embedded[task_id]
                for oid in entry["refs"]:
                    if oid in self._embedded_materialized:
                        self._embedded_materialized.discard(oid)
                        continue
                    candidates.append(oid)
        # settle outside _embedded_lock: it takes the rc lock, and add_local_ref
        # orders rc._lock -> (after release) _embedded_lock.
        for oid in candidates:
            parent = self.reference_counter.settle_unmaterialized(oid)
            if parent is not None:
                self._report_borrow(oid, parent, -1)

    async def _borrow_audit_loop(self):
        """Reconcile borrowers that died without releasing: ping each borrower
        address; persistent unreachability drops its counts (reference:
        reference_counter subscribes to borrower death via the raylet)."""
        failures: dict[str, int] = {}
        stale: dict[tuple, int] = {}  # (borrower_key, oid) -> not-held strikes
        while self._connected:
            await asyncio.sleep(CONFIG.borrow_audit_interval_s)
            snapshot = self.reference_counter.borrower_snapshot()
            # Prune strikes whose borrower left entirely AND strikes whose oid
            # is no longer borrowed by that borrower (normal release between
            # audits) — otherwise (borrower, oid) keys accrete forever.
            stale = {k: v for k, v in stale.items()
                     if k[0] in snapshot and k[1] in snapshot[k[0]]}
            for key in snapshot:
                node_hex, worker_hex = key
                if node_hex == "?":
                    continue  # legacy unkeyed entry: no address to audit
                try:
                    alive = await self.raylet.call(
                        "check_worker_alive", node_hex, worker_hex, timeout=10.0
                    )
                except Exception:
                    continue  # raylet unreachable: no verdict this round
                if alive is None:
                    continue  # unreachable != dead: never free on a maybe
                if alive:
                    failures.pop(key, None)
                    # Liveness is not enough: a borrower that released into a
                    # crashed parent's void still has a count here (the -1
                    # never arrived). Ask what it actually still holds; three
                    # consecutive not-held verdicts (plus a wall-clock floor,
                    # below) reconcile the entry — fewer would race an
                    # in-flight handoff the holder hasn't learned about yet.
                    try:
                        resp = await self.raylet.call(
                            "check_borrows", node_hex, worker_hex,
                            snapshot[key], timeout=15.0,
                        )
                    except Exception:
                        resp = None
                    if not isinstance(resp, dict) or "held" not in resp:
                        continue
                    held = set(resp["held"])
                    now = time.monotonic()
                    for oid in snapshot[key]:
                        sk = (key, oid)
                        if oid in held:
                            stale.pop(sk, None)
                            continue
                        strikes, first_t = stale.get(sk, (0, now))
                        strikes += 1
                        # N consecutive not-held rounds AND a minimum
                        # wall-clock age: a sequenced handoff still in flight
                        # (reply not yet processed by the holder) must never
                        # be reconciled away on a fast audit interval.
                        if (strikes >= CONFIG.borrow_audit_strikes
                                and now - first_t >= CONFIG.borrow_audit_min_age_s):
                            stale.pop(sk, None)
                            self.reference_counter.drop_borrow_entry(oid, key)
                        else:
                            stale[sk] = (strikes, first_t)
                    continue
                failures[key] = failures.get(key, 0) + 1
                if failures[key] >= 2:  # two strikes: not a transient blip
                    failures.pop(key, None)
                    self.reference_counter.drop_borrower(key)

    # ------------------------------------------------------------------ lineage

    def _record_lineage(self, spec, promoted: list[ObjectID]):
        """Retain the producing task spec (+ pins on its promoted plasma args) until
        every return object is out of scope, so a lost object can be rebuilt by
        re-running the task (reference: TaskManager lineage, task_manager.h:177)."""
        if CONFIG.max_object_reconstructions <= 0 or not spec["return_ids"]:
            return False
        entry = {"spec": spec, "live": set(spec["return_ids"]), "promoted": promoted}
        with self._lineage_lock:
            for oid in spec["return_ids"]:
                self._lineage[oid] = entry
            overflow = len(self._lineage) - CONFIG.max_lineage_entries
            evicted = []
            if overflow > 0:
                for oid in list(self._lineage):
                    if overflow <= 0:
                        break
                    ev = self._lineage.pop(oid)
                    ev["live"].discard(oid)
                    if not ev["live"]:
                        evicted.append(ev)
                    overflow -= 1
        for ev in evicted:
            for pid in ev.get("promoted", ()):
                self.reference_counter.remove_local_ref(pid)
        return True

    def _drop_lineage(self, object_id: ObjectID):
        release = None
        with self._lineage_lock:
            self._recon_attempts.pop(object_id, None)
            self._reconstructing.discard(object_id)
            entry = self._lineage.pop(object_id, None)
            if entry is None:
                return
            entry["live"].discard(object_id)
            if not entry["live"]:
                release = entry.get("promoted", ())
        if release:
            for pid in release:
                self.reference_counter.remove_local_ref(pid)

    def _try_reconstruct_owned(self, object_id: ObjectID) -> bool:
        """Re-submit the producing task of a lost owned object. Returns True if a
        rebuild was started or is already in flight (reference:
        object_recovery_manager.h:41)."""
        with self._lineage_lock:
            entry = self._lineage.get(object_id)
            if entry is None:
                return False
            if object_id in self._reconstructing:
                return True
            attempts = self._recon_attempts.get(object_id, 0)
            if attempts >= CONFIG.max_object_reconstructions:
                return False
            spec = dict(entry["spec"])
            for oid in entry["live"]:
                self._recon_attempts[oid] = attempts + 1
                self._reconstructing.add(oid)
        spec["retries_left"] = max(1, spec.get("retries_left", 1))
        spec.pop("__direct__", None)  # rebuild rides the raylet, not a stale lease
        self._record_event(
            task_id=spec["task_id"].hex(), name=spec["name"], state="RECONSTRUCTING"
        )

        def unwedge():
            # The resubmission never reached the raylet: clear the in-flight marker
            # so a later get() attempts reconstruction again instead of spinning.
            with self._lineage_lock:
                for oid in spec["return_ids"]:
                    self._reconstructing.discard(oid)

        self._submit_when_ready(spec, on_send_failure=unwedge)
        return True

    def _try_reconstruct(self, ref: ObjectRef) -> bool:
        """Owner: rebuild locally. Borrower: ask the owner to rebuild."""
        if ref.owner and ref.owner.get("worker_id") != self.worker_id:
            try:
                reply = self.raylet_call(
                    "call_worker", ref.owner, "reconstruct_object",
                    {"object_id": ref.id},
                )
            except rpc.RpcError:
                return False
            return bool(isinstance(reply, dict) and reply.get("ok"))
        return self._try_reconstruct_owned(ref.id)

    # ------------------------------------------------------------------ task submission

    def _serialize_args(self, args, kwargs):
        """Each arg: inline bytes, plasma-promoted ref, or passed-through ObjectRef.

        Returns (args, kwargs, promoted_ids); the caller must release the promoted ids'
        refcounts once the consuming task completes (or pin them for actor lifetime).
        """
        promoted: list[ObjectID] = []

        def one(value):
            if isinstance(value, ObjectRef):
                # Pin every ref arg for the task's lifetime so a caller dropping its
                # handle right after .remote() can't free the arg out from under the
                # queued task. For borrowed refs the pin keeps this process's borrow
                # registered with the owner until the task completes.
                self.reference_counter.add_local_ref(value.id, value.owner)
                promoted.append(value.id)
                return {"ref": (value.id, value.owner)}
            pickled, raw_buffers, total = serialization.serialized_size(value)
            if total > CONFIG.max_direct_call_object_size:
                object_id = ObjectID.from_task(
                    self.current_task_id, 0x20000000 + self._put_counter.next()
                )
                self._write_plasma(
                    object_id, pickled, raw_buffers, total, self._owner_address()
                )
                self.reference_counter.add_owned(object_id)
                self.reference_counter.add_local_ref(object_id)
                promoted.append(object_id)
                rec = self.memory_store.create_pending(object_id)
                rec.in_plasma = True
                rec.resolved = True
                rec.event.set()
                return {"ref": (object_id, self._owner_address()), "promoted": True}
            header_parts = serialization.assemble(pickled, raw_buffers)
            return {"v": header_parts}

        return [one(a) for a in args], {k: one(v) for k, v in kwargs.items()}, promoted

    def submit_task(
        self,
        fn_key: bytes,
        name: str,
        args,
        kwargs,
        num_returns: int = 1,
        resources: dict | None = None,
        placement_group: dict | None = None,
        max_retries: int | None = None,
        scheduling_strategy=None,
        runtime_env: dict | None = None,
    ) -> list[ObjectRef]:
        self.reference_counter.drain_deferred()
        task_id = TaskID.from_random()
        ser_args, ser_kwargs, promoted = self._serialize_args(args, kwargs)
        streaming = num_returns == "streaming"
        return_ids = (
            [] if streaming else [ObjectID.from_task(task_id, i) for i in range(num_returns)]
        )
        owner = self._owner_address()
        spec = {
            "type": "task",
            "task_id": task_id,
            "name": name,
            "fn_key": fn_key,
            "args": ser_args,
            "kwargs": ser_kwargs,
            "num_returns": num_returns,
            "return_ids": return_ids,
            "resources": resources if resources is not None else {"CPU": 1},
            "placement_group": placement_group,
            "owner": owner,
            "retries_left": (
                max_retries if max_retries is not None else CONFIG.max_task_retries_default
            ),
            "scheduling_strategy": scheduling_strategy,
            "runtime_env": runtime_env,
        }
        refs = []
        for oid in return_ids:
            self.reference_counter.add_owned(oid)
            self.memory_store.create_pending(oid)
            refs.append(ObjectRef(oid, owner))
        # Two independent pins on promoted args: the flight pin (released when the
        # task's result arrives, guaranteeing args outlive the queued/running task)
        # and, when lineage is retained, a lineage pin (released when the last
        # return object dies, so a rebuild can re-materialize args).
        # Streamed items are not lineage-reconstructable (the stream is consumed
        # incrementally), so streaming tasks keep only the flight pin.
        if not streaming and self._record_lineage(spec, promoted):
            for pid in promoted:
                self.reference_counter.add_local_ref(pid)
        if promoted:
            self._pending_promoted[task_id] = promoted
        from ray_tpu.util import tracing

        tctx = tracing.propagation_context()
        if tctx:
            spec["trace_ctx"] = tctx
        self._record_event(task_id=task_id.hex(), name=name, state="SUBMITTED",
                           **tracing.event_fields(tctx))
        if streaming:
            self._streams[task_id] = _StreamState()
        if self._lease_eligible(spec):
            self._when_args_ready(spec, lambda: self._lease_submit(spec))
        else:
            self._submit_when_ready(spec)
        if streaming:
            return ObjectRefGenerator(task_id, self)
        return refs

    def _when_args_ready(self, spec, fn):
        """Dependency gating: run fn once owned pending ref-args resolve
        (DependencyResolver parity). fn may run on the caller thread (no deps)
        or on whatever thread resolves the last dependency."""
        dep_ids = []
        for loc in list(spec["args"]) + list(spec["kwargs"].values()):
            if "ref" in loc:
                oid = loc["ref"][0]
                rec = self.memory_store.get(oid)
                if rec is not None and not rec.resolved:
                    dep_ids.append(oid)
        if not dep_ids:
            fn()
            return
        remaining = {"n": len(dep_ids)}
        lock = threading.Lock()

        def on_done(_oid, _rec):
            with lock:
                remaining["n"] -= 1
                done = remaining["n"] == 0
            if done:
                fn()

        for oid in dep_ids:
            if not self.memory_store.add_done_callback(oid, on_done):
                on_done(oid, None)

    def _submit_when_ready(self, spec, target="submit_task", on_send_failure=None):
        async def send():
            try:
                await self.raylet.notify(target, spec)
            except Exception:
                if on_send_failure is not None:
                    on_send_failure()

        self._when_args_ready(spec, lambda: self.io.spawn(send()))

    # ------------------------------------------------------------------ actors

    def create_actor(
        self,
        cls_key: bytes,
        class_name: str,
        args,
        kwargs,
        *,
        name=None,
        namespace="",
        get_if_exists=False,
        num_returns: int = 0,
        resources=None,
        placement_group=None,
        max_restarts=0,
        max_concurrency=1,
        is_async=False,
        scheduling_strategy=None,
        method_names=(),
        runtime_env=None,
        concurrency_groups=None,
        method_groups=None,
        method_opts=None,
        allow_out_of_order_execution=False,
    ) -> ActorID:
        actor_id = ActorID.from_random()
        # Promoted/borrowed init args stay pinned while the actor can restart
        # (restarts re-run __init__); released when the creator's handle dies.
        ser_args, ser_kwargs, promoted = self._serialize_args(args, kwargs)
        spec = {
            "type": "actor_creation",
            "actor_id": actor_id,
            "cls_key": cls_key,
            "class_name": class_name,
            "args": ser_args,
            "kwargs": ser_kwargs,
            "name": name,
            "namespace": namespace,
            "get_if_exists": get_if_exists,
            "resources": dict(resources or {}),
            "placement_group": placement_group,
            "max_restarts": max_restarts,
            "max_concurrency": max_concurrency,
            "is_async": is_async,
            "scheduling_strategy": scheduling_strategy,
            "owner": self._owner_address(),
            "method_names": list(method_names),
            "runtime_env": runtime_env,
            "concurrency_groups": dict(concurrency_groups or {}),
            "method_groups": dict(method_groups or {}),
            "method_opts": dict(method_opts or {}),
            "allow_out_of_order_execution": bool(allow_out_of_order_execution),
        }
        reply = self.gcs_call("register_actor", actor_id, spec)
        actual_id = reply["actor_id"]
        existing = bool(reply.get("existing"))
        if promoted:
            if existing:
                # get_if_exists hit an existing actor: our spec (and its arg pins)
                # will never be used for a restart.
                for pid in promoted:
                    self.reference_counter.remove_local_ref(pid)
            else:
                self._actor_arg_pins[actual_id] = promoted
        # The caller's handle owns the arg pins only when this call actually
        # created the actor; a get_if_exists hit must return a non-owning handle
        # (its __del__ must not release the first creator's pins).
        return actual_id, not existing

    def release_actor_arg_pins(self, actor_id: ActorID):
        """The creator's handle died: the actor can still run, but this process no
        longer guards its init args (a restart after this frees-then-fails like the
        reference when the owner of the args is gone)."""
        for pid in self._actor_arg_pins.pop(actor_id, ()):  # noqa: B020
            self.reference_counter.remove_local_ref(pid)

    def submit_actor_task(
        self,
        actor_id: ActorID,
        method_name: str,
        args,
        kwargs,
        num_returns: int = 1,
        concurrency_group: str | None = None,
        out_of_order: bool = False,
    ) -> list[ObjectRef]:
        self.reference_counter.drain_deferred()
        task_id = TaskID.from_random()
        ser_args, ser_kwargs, promoted = self._serialize_args(args, kwargs)
        if promoted:
            self._pending_promoted[task_id] = promoted
        streaming = num_returns == "streaming"
        return_ids = (
            [] if streaming else [ObjectID.from_task(task_id, i) for i in range(num_returns)]
        )
        owner = self._owner_address()
        counter = self._actor_seq.setdefault(actor_id, _Counter())
        spec = {
            "type": "actor_task",
            "task_id": task_id,
            "actor_id": actor_id,
            "name": method_name,
            "method_name": method_name,
            "args": ser_args,
            "kwargs": ser_kwargs,
            "num_returns": num_returns,
            "return_ids": return_ids,
            "owner": owner,
            "caller_id": self.worker_id.binary(),
            "seq": counter.next(),
        }
        if concurrency_group:
            spec["concurrency_group"] = concurrency_group
        if out_of_order:
            spec["ooo"] = True
        refs = []
        for oid in return_ids:
            self.reference_counter.add_owned(oid)
            self.memory_store.create_pending(oid)
            refs.append(ObjectRef(oid, owner))
        from ray_tpu.util import tracing

        tctx = tracing.propagation_context()
        if tctx:
            spec["trace_ctx"] = tctx
        if streaming:
            self._streams[task_id] = _StreamState()
        # Hot path: push the call straight to the actor process over a cached
        # direct connection — no raylet hop per call (reference:
        # actor_task_submitter.h:67 direct gRPC to the actor after creation).
        # Streaming specs ride the SAME ordered direct queue (a raylet detour
        # would leave a hole at their seq and wedge every later call) but are
        # not flagged __direct__: their items/end still route via the raylet.
        use_direct = not self.remote_data_plane and self._submit_actor_direct(
            actor_id, spec
        )
        if not use_direct:
            self._submit_when_ready(spec, target="submit_actor_task")
        if streaming:
            return ObjectRefGenerator(task_id, self)
        return refs

    # ------------------------------------------------------------------ lease caching (normal tasks)

    def _lease_eligible(self, spec) -> bool:
        """The lease fast path serves plain tasks; anything needing the
        scheduler's policy zoo (placement groups, affinity, spread) or stream
        bookkeeping takes the classic raylet route."""
        return (
            not self.remote_data_plane
            and spec.get("placement_group") is None
            and spec.get("scheduling_strategy") is None
            and spec.get("num_returns") != "streaming"
        )

    def _lease_shape(self, spec) -> tuple:
        from ray_tpu._private import runtime_env as runtime_env_mod

        return (
            tuple(sorted((spec.get("resources") or {}).items())),
            runtime_env_mod.env_key(spec.get("runtime_env")),
        )

    def _lease_submit(self, spec):
        shape = self._lease_shape(spec)
        with self._lease_lock:
            st = self._leases.setdefault(
                shape, {"workers": {}, "queue": deque(), "requesting": False,
                        "classic_until": 0.0, "depth": _lease_depth_min()},
            )
            if time.monotonic() < st["classic_until"]:
                classic = True
            else:
                classic = False
                st["queue"].append(spec)
        if classic:
            self.io.spawn(self.raylet.notify("submit_task", spec))
            return
        self._lease_pump(shape)

    def _lease_pump(self, shape):
        """Assign queued specs to leased workers with free pipeline slots;
        request more leases while work outstrips them (one outstanding request
        per shape).

        Each worker takes up to lease_worker_slots in-flight tasks (reference:
        the owner pipelines pushes ahead of completions so small tasks never
        pay a full owner<->worker round trip between executions), and pushes
        ride a per-worker send queue whose drainer packs everything accumulated
        into one push_batch frame — a burst of .remote() calls coalesces into
        a few frames instead of one frame (and one event-loop wakeup) per task."""
        to_wake, request = [], False
        with self._lease_lock:
            st = self._leases.get(shape)
            if st is None or not st["queue"]:
                # Completion hot path: nothing queued means nothing to assign,
                # and any non-empty sendq already has its send loop running.
                return
            # Adaptive pipeline depth: start shallow (lease_pipeline_min_depth) so a
            # burst leaves work queued and lease requests fan it out across
            # workers; _lease_request doubles the depth toward
            # lease_worker_slots each time the raylet DENIES a lease with work
            # still queued (the node is saturated — parallelism is exhausted,
            # so pipeline deeper instead: bigger frames, fewer wakeups).
            slots = max(1, min(st.get("depth", _lease_depth_min()),
                               CONFIG.lease_worker_slots))
            # Round-robin one task per worker per pass: a greedy fill would
            # park a whole burst on the first worker while the rest idle;
            # breadth-first keeps execution parallel and the per-worker sendq
            # still coalesces everything a pass assigns into one frame.
            live = [
                w for w in st["workers"].values()
                if not w["conn"].closed and len(w["inflight"]) < slots
            ]
            while st["queue"] and live:
                for w in list(live):
                    if not st["queue"]:
                        break
                    spec = st["queue"].popleft()
                    spec["__direct__"] = True
                    w["inflight"][spec["task_id"]] = spec
                    w["sendq"].append(spec)
                    self._lease_inflight[spec["task_id"]] = (shape, w["worker_id"])
                    if len(w["inflight"]) >= slots:
                        live.remove(w)
            for w in st["workers"].values():
                if w["sendq"] and not w["sending"]:
                    w["sending"] = True
                    to_wake.append(w)
            if st["queue"] and not st["requesting"]:
                st["requesting"] = True
                request = True
        for w in to_wake:
            self.io.spawn(self._lease_send_loop(shape, w))
        if request:
            self.io.spawn(self._lease_request(shape))

    async def _lease_send_loop(self, shape, w):
        """Drain the worker's send queue, one frame per accumulated batch."""
        while True:
            with self._lease_lock:
                batch = list(w["sendq"])
                w["sendq"].clear()
                if not batch:
                    w["sending"] = False
                    return
            try:
                await w["conn"].notify("push_batch", batch)
            except Exception:
                with self._lease_lock:
                    w["sending"] = False
                self._lease_worker_lost(shape, w["worker_id"], w["conn"])
                return

    async def _lease_request(self, shape):
        resources, env_key = dict(shape[0]), shape[1]
        with self._lease_lock:
            st = self._leases.get(shape)
            sample = st["queue"][0] if st and st["queue"] else None
        renv = sample.get("runtime_env") if sample else None
        try:
            resp = await self.raylet.call(
                "request_lease", resources or {"CPU": 1}, renv, self.worker_id
            )
        except Exception:
            resp = None
        conn = None
        if resp and resp.get("ok"):
            try:
                conn = await rpc.connect(
                    *resp["direct_addr"], handler=self, name="lease-worker",
                    via=self.proxy,
                )
            except Exception:  # OSError or connect timeout: give the lease back
                conn = None
                self.io.spawn(self.raylet.notify("release_lease", resp["worker_id"]))
        drain_classic = []
        with self._lease_lock:
            st = self._leases.get(shape)
            if st is None:
                if conn is not None:
                    # The lease state vanished while we were connecting: give
                    # the lease back AND close the socket — nothing will ever
                    # use this conn, and an unclosed one lingers until GC.
                    self.io.spawn(self.raylet.notify("release_lease", resp["worker_id"]))
                    self.io.spawn(conn.close())
                return
            st["requesting"] = False
            if conn is not None:
                wid = resp["worker_id"]
                w = {"worker_id": wid, "conn": conn, "inflight": {},
                     "sendq": deque(), "sending": False}
                st["workers"][wid] = w
                st["retries"] = 0
                # Capacity exists again: go back to shallow pipelines so the
                # next burst spreads before it deepens.
                st["depth"] = _lease_depth_min()
                conn.on_close(lambda c: self._lease_worker_lost(shape, wid, c))
            elif resp and resp.get("infeasible"):
                # This node can never run the shape: hand everything queued to
                # the raylet (spillback machinery) and stop fast-pathing it
                # for a while.
                st["classic_until"] = time.monotonic() + 10.0
                while st["queue"]:
                    drain_classic.append(st["queue"].popleft())
            elif st["queue"]:
                # Denied with work queued: the node can't lease more workers
                # for this shape right now. Deepen the per-worker pipeline so
                # the backlog rides existing leases in large frames.
                st["depth"] = min(
                    max(st.get("depth", _lease_depth_min()), 1) * 2,
                    CONFIG.lease_worker_slots,
                )
                st["retries"] = st.get("retries", 0) + 1
                if st["retries"] > 40 and not st["workers"]:
                    # Long-denied with no leased worker: the node may be wedged
                    # by blocked parents (nested zero-slot tasks). The classic
                    # scheduler has the deadlock-avoidance spawn logic; use it.
                    st["classic_until"] = time.monotonic() + 10.0
                    st["retries"] = 0
                    while st["queue"]:
                        drain_classic.append(st["queue"].popleft())
                else:
                    # Busy node: retry while demand remains.
                    st["requesting"] = True
                    self.io.loop.call_later(
                        0.05, lambda: self.io.spawn(self._lease_request(shape))
                    )
        for spec in drain_classic:
            self.io.spawn(self.raylet.notify("submit_task", spec))
        # Pump in both cases: a grant added a worker; a denial deepened the
        # pipeline, so the backlog rides existing workers at the new depth
        # (`requesting` was re-armed above — pump won't double-request).
        self._lease_pump(shape)
        if conn is not None:
            # The queue may have drained while this grant was in flight (an
            # existing leased worker took the work): an unused grant must not
            # pin the worker forever.
            with self._lease_lock:
                st = self._leases.get(shape)
                w = st["workers"].get(resp["worker_id"]) if st else None
                idle = w is not None and not w["inflight"] and (not st["queue"])
            if idle:
                self._schedule_lease_release(shape, resp["worker_id"])

    def _schedule_lease_release(self, shape, wid):
        """Return the lease after a short grace if the worker is still idle —
        bursty submitters keep their warm worker. Must run on the io thread."""

        def maybe_release():
            with self._lease_lock:
                st = self._leases.get(shape)
                if st is None:
                    return
                w = st.get("workers", {}).get(wid)
                if w is None or w["inflight"] or st["queue"]:
                    return
                st["workers"].pop(wid, None)
                conn = w["conn"]
            self.io.spawn(self.raylet.notify("release_lease", wid))
            self.io.spawn(conn.close())

        self.io.loop.call_later(0.25, maybe_release)

    def _lease_task_finished(self, task_id):
        entry = self._lease_inflight.pop(task_id, None)
        if entry is None:
            return
        shape, wid = entry
        with self._lease_lock:
            st = self._leases.get(shape)
            if st is None:
                return
            w = st["workers"].get(wid)
            if w is not None:
                w["inflight"].pop(task_id, None)
                if not st["queue"] and not w["inflight"]:
                    self._schedule_lease_release(shape, wid)
        self._lease_pump(shape)

    def _lease_worker_lost(self, shape, wid, conn):
        """A leased worker died: retry its in-flight tasks or fail them."""
        failed = []
        with self._lease_lock:
            st = self._leases.get(shape)
            if st is None:
                return
            w = st["workers"].pop(wid, None)
            if w is None:
                return
            for respec in w["inflight"].values():
                self._lease_inflight.pop(respec["task_id"], None)
                if respec.get("retries_left", 0) > 0:
                    respec["retries_left"] -= 1
                    respec.pop("__direct__", None)
                    st["queue"].appendleft(respec)
                else:
                    failed.append(respec)
        if failed:
            from ray_tpu.exceptions import OutOfMemoryError, WorkerCrashedError

            oom_cause = self._lease_oom.pop(wid, None)
            for respec in failed:
                if oom_cause is not None:
                    err_obj = OutOfMemoryError(
                        f"task {respec.get('name')} failed: {oom_cause}"
                    )
                else:
                    err_obj = WorkerCrashedError(
                        f"task {respec.get('name')} failed: leased worker died during execution"
                    )
                err = serialization.dumps(err_obj)
                for oid in respec["return_ids"]:
                    self.memory_store.resolve(oid, err, True, False)
        self._lease_pump(shape)

    async def rpc_lease_oom(self, conn, payload):
        """Raylet forewarning: a leased worker is being OOM-killed for cause."""
        self._lease_oom[payload["worker_id"]] = payload["cause"]
        if len(self._lease_oom) > 256:  # bound stale entries
            self._lease_oom.pop(next(iter(self._lease_oom)))
        return True

    # ------------------------------------------------------------------ direct actor path

    def _submit_actor_direct(self, actor_id: ActorID, spec) -> bool:
        """Route an actor call over the direct worker connection.

        Returns True when the direct path owns delivery (possibly queued behind
        address resolution). The first submission per actor decides the path
        STICKILY — mixing transports would break per-caller seq ordering at the
        executor. Sends flush strictly in seq order, so the executor's
        first-arrival-sets-baseline logic always sees the lowest outstanding seq
        first (reference: ActorSubmitQueue sends in order even when dependencies
        resolve out of order).
        """
        with self._direct_lock:
            st = self._direct_send.get(actor_id)
            if st is None:
                if self._direct_actor.get(actor_id, "?") is None:
                    return False  # resolved earlier: raylet path forever
                st = self._direct_send[actor_id] = {
                    "next": spec["seq"], "ready": {}, "state": "resolving",
                }
                self.io.spawn(self._resolve_actor_direct(actor_id))
            elif st["state"] == "raylet":
                # Fallback decided: keep every later call on the raylet too.
                return False
        self._when_args_ready(spec, lambda: self._direct_mark_ready(actor_id, spec))
        return True

    def _direct_mark_ready(self, actor_id: ActorID, spec):
        with self._direct_lock:
            st = self._direct_send.get(actor_id)
            if st is None:
                self._submit_when_ready(spec, target="submit_actor_task")
                return
            if spec.pop("ooo", None):
                st["ooo"] = True
            st["ready"][spec["seq"]] = spec
        self._direct_flush(actor_id)

    def _direct_flush(self, actor_id: ActorID):
        fallback, drain = [], False
        with self._direct_lock:
            st = self._direct_send.get(actor_id)
            if st is None:
                return
            if st["state"] == "connected":
                while st["next"] in st["ready"]:
                    spec = st["ready"].pop(st["next"])
                    st["next"] += 1
                    if spec.get("num_returns") != "streaming":
                        spec["__direct__"] = True
                    self._direct_inflight[spec["task_id"]] = spec
                    st.setdefault("sendq", deque()).append(spec)
                # Out-of-order actors take no ordering guarantee end to end:
                # ship whatever is ready (args resolved) regardless of seq
                # continuity — the executor side skips gating symmetrically.
                # The flag is STICKY per actor (set by the first tagged spec),
                # so every pending spec ships even if some arrived through a
                # handle that predates the flag.
                if st.get("ooo"):
                    for seq in sorted(st["ready"]):
                        spec = st["ready"].pop(seq)
                        st["next"] = max(st["next"], seq + 1)
                        if spec.get("num_returns") != "streaming":
                            spec["__direct__"] = True
                        self._direct_inflight[spec["task_id"]] = spec
                        st.setdefault("sendq", deque()).append(spec)
                if st.get("sendq") and not st.get("draining"):
                    st["draining"] = True
                    drain = True
            elif st["state"] == "raylet":
                # Resolution failed after calls queued: replay them via the
                # raylet in seq order (legacy transport, legacy semantics).
                for seq in sorted(st["ready"]):
                    fallback.append(st["ready"].pop(seq))
        if drain:
            self.io.spawn(self._direct_drain(actor_id))
        for spec in fallback:
            self.io.spawn(self.raylet.notify("submit_actor_task", spec))

    async def _direct_drain(self, actor_id: ActorID):
        """Single in-flight drainer per actor: ships everything queued since the
        last write in ONE frame (push_batch) — a submit burst coalesces into a
        few pickles/syscalls instead of one per call."""
        while True:
            with self._direct_lock:
                st = self._direct_send.get(actor_id)
                if st is None:
                    return
                batch = list(st.get("sendq") or ())
                if st.get("sendq"):
                    st["sendq"].clear()
                if not batch:
                    st["draining"] = False
                    return
                conn = self._direct_actor.get(actor_id)
            if conn is None or getattr(conn, "closed", True):
                with self._direct_lock:
                    if st is self._direct_send.get(actor_id):
                        st["draining"] = False
                return
            try:
                if len(batch) == 1:
                    await conn.notify("push_task", batch[0])
                else:
                    await conn.notify("push_batch", batch)
            except Exception:
                with self._direct_lock:
                    st["draining"] = False
                self._direct_conn_lost(actor_id, conn)
                return

    async def _resolve_actor_direct(self, actor_id: ActorID):
        """Resolve the actor's direct address via the GCS and connect (io thread)."""
        conn = None
        dead = False
        try:
            for _attempt in range(3):
                info = await self.gcs.call("wait_actor_alive", actor_id, 60.0)
                if info is None or info["state"] == "DEAD":
                    dead = True
                    break
                if info["state"] == "ALIVE":
                    daddr = (info.get("address") or {}).get("direct_addr")
                    if daddr:
                        conn = await rpc.connect(
                            *daddr, handler=self,
                            name=f"direct->{actor_id.hex()[:8]}",
                            via=self.proxy,
                        )
                    break
                # PENDING/RESTARTING: wait again
        except Exception:
            conn = None
        with self._direct_lock:
            st = self._direct_send.get(actor_id)
            if conn is not None:
                self._direct_actor[actor_id] = conn
                if st is not None:
                    st["state"] = "connected"
                conn.on_close(lambda c: self._direct_conn_lost(actor_id, c))
            else:
                self._direct_actor[actor_id] = None
                if st is not None:
                    st["state"] = "raylet"
        self._direct_flush(actor_id)
        if dead:
            # Only for DEAD actors: a LIVE actor's "raylet" tombstone must stay
            # (dropping it would let a later call retry direct mid-stream and
            # break per-caller seq ordering across transports).
            self._direct_gc(actor_id)

    def _direct_conn_lost(self, actor_id: ActorID, conn):
        """Direct connection dropped (actor death or restart): fail the calls it
        carried — with the GCS-recorded cause — and re-resolve for later calls."""
        with self._direct_lock:
            if self._direct_actor.get(actor_id) is not conn:
                return  # stale callback (already re-resolved)
            self._direct_actor.pop(actor_id, None)
            st = self._direct_send.get(actor_id)
            if st is not None and st["state"] == "connected":
                if self._connected:
                    st["state"] = "resolving"
                    self.io.spawn(self._resolve_actor_direct(actor_id))
                else:
                    st["state"] = "raylet"  # shutting down: no re-resolution
            inflight = []
            for tid, s in list(self._direct_inflight.items()):
                if s.get("actor_id") == actor_id:
                    self._direct_inflight.pop(tid, None)
                    inflight.append(s)
        if inflight and self._connected:
            self.io.spawn(self._fail_direct_inflight(actor_id, inflight))
        else:
            # No in-flight calls to fail: reclaim the per-actor state here
            # (the only other gc site is _fail_direct_inflight).
            self._direct_gc(actor_id)

    async def _fail_direct_inflight(self, actor_id: ActorID, inflight: list):
        from ray_tpu.exceptions import ActorDiedError

        await asyncio.sleep(0.3)  # let the raylet report the death cause to GCS
        reason = "actor died (direct connection lost)"
        try:
            info = await self.gcs.call("get_actor_info", actor_id)
            if info is not None and info.get("death_cause"):
                reason = f"actor died: {info['death_cause']}"
            elif info is not None and info["state"] == "RESTARTING":
                reason = "actor died during method call (restarting)"
        except Exception:
            pass  # GCS unreachable: fall through to the generic death reason
        exc = ActorDiedError(actor_id, reason)
        err = serialization.dumps(exc)
        for spec in inflight:
            if spec.get("num_returns") == "streaming":
                st = self._streams.get(spec["task_id"])
                if st is not None:
                    with st.cond:
                        st.abort_error = exc
                        st.cond.notify_all()
            else:
                for oid in spec["return_ids"]:
                    self.memory_store.resolve(oid, err, True, False)
        self._direct_gc(actor_id)

    def _direct_gc(self, actor_id: ActorID):
        """Drop per-actor direct state once it holds nothing live — long-lived
        drivers churning thousands of short-lived actors must not accumulate
        send-state dicts and dead Connection objects forever."""
        with self._direct_lock:
            st = self._direct_send.get(actor_id)
            if st is not None and (st["ready"] or st.get("sendq") or
                                   st.get("draining") or
                                   st["state"] == "resolving"):
                return  # pending work or a resolver in flight: not yet
            conn = self._direct_actor.get(actor_id)
            if conn is not None and not getattr(conn, "closed", True):
                return
            self._direct_send.pop(actor_id, None)
            self._direct_actor.pop(actor_id, None)

    # ------------------------------------------------------------------ RPC handlers (io thread)

    async def rpc_task_results(self, conn, payloads: list):
        for payload in payloads:
            await self.rpc_task_result(conn, payload)

    async def rpc_task_result(self, conn, payload):
        with self._direct_lock:
            self._direct_inflight.pop(payload.get("task_id"), None)
        self._lease_task_finished(payload.get("task_id"))
        # Sequenced borrow handoff: the executor's kept borrows and result-ref
        # pre-registrations MUST be absorbed before the arg pins release — same
        # message, strict order, no reorder window (the race the round-1
        # fire-and-forget registration admitted).
        self._register_reply_embeds(payload)
        promoted = self._pending_promoted.pop(payload.get("task_id"), None)
        if promoted:
            for oid in promoted:
                self.reference_counter.remove_local_ref(oid)
        with self._lineage_lock:
            for result in payload["results"]:
                self._reconstructing.discard(result["object_id"])
        for result in payload["results"]:
            oid = result["object_id"]
            in_plasma = bool(result.get("in_plasma"))
            live = self.memory_store.resolve(
                oid, None if in_plasma else result["inline"],
                result.get("error", False), in_plasma,
            )
            if not live and in_plasma:
                # All refs were dropped before the result landed: free the orphan.
                try:
                    await self.raylet.notify("store_free", oid)
                except rpc.RpcError:
                    pass

    async def rpc_stream_item(self, conn, payload):
        """Owner side: one item of a streaming task arrived."""
        task_id, index, result = payload["task_id"], payload["index"], payload["result"]
        oid = result["object_id"]
        in_plasma = bool(result.get("in_plasma"))
        st = self._streams.get(task_id)
        if st is None:
            # Generator was dropped before this item landed: free an orphan.
            if in_plasma:
                try:
                    await self.raylet.notify("store_free", oid)
                except rpc.RpcError:
                    pass
            return True
        self.reference_counter.add_owned(oid)
        self.memory_store.create_pending(oid)
        # Sequenced handoff for refs yielded inside the item (mirrors
        # _register_reply_embeds for task results): pre-seed parents before
        # user code can deserialize them; settle when this item is freed.
        src = result.get("src")
        if src is not None:
            pending = []
            for roid, _o in result.get("result_refs") or ():
                if _o is not None:
                    self.reference_counter.record_true_owner(roid, _o)
                if self.reference_counter.pre_register_borrow(roid, src):
                    pending.append(roid)
                else:
                    self._report_borrow(roid, src, -1)
            if pending:
                with self._embedded_lock:
                    self._reply_embedded[("stream", oid)] = {
                        "refs": pending, "returns": {oid}, "src": src,
                    }
        self.memory_store.resolve(
            oid, None if in_plasma else result["inline"],
            result.get("error", False), in_plasma,
        )
        ref = ObjectRef(oid, self._owner_address())
        with st.cond:
            st.items[index] = ref
            st.cond.notify_all()
        return True

    async def rpc_stream_end(self, conn, payload):
        with self._direct_lock:
            self._direct_inflight.pop(payload.get("task_id"), None)
        st = self._streams.get(payload["task_id"])
        if st is not None:
            with st.cond:
                st.total = payload["count"]
                st.cond.notify_all()
        return True

    async def rpc_stream_abort(self, conn, payload):
        """The producing worker died with retries exhausted: unblock the consumer."""
        from ray_tpu.exceptions import WorkerCrashedError

        st = self._streams.get(payload["task_id"])
        if st is not None:
            with st.cond:
                st.abort_error = WorkerCrashedError(payload.get("reason", "stream lost"))
                st.cond.notify_all()
        return True

    async def rpc_borrow_check(self, conn, payload):
        """Audit probe: which of these ids does this process still hold (as a
        local ref, a sub-borrower parent, or an in-flight handoff)?"""
        rc = self.reference_counter
        held = []
        with rc._lock:
            for oid in payload["object_ids"]:
                if (
                    rc._counts.get(oid, 0) > 0
                    or rc._borrow_total_locked(oid) > 0
                    or oid in rc._preregistered
                    or oid in rc._task_deferred
                    or oid in rc._pending_upstream
                ):
                    held.append(oid)
        return {"held": held}

    async def rpc_borrow_update(self, conn, payload):
        self.reference_counter.update_borrow(
            payload["object_id"], payload["delta"],
            tuple(payload.get("borrower") or ("?", "?")),
        )
        return True

    async def rpc_reconstruct_object(self, conn, payload):
        """A borrower lost an object we own: rebuild it from lineage."""
        return {"ok": self._try_reconstruct_owned(payload["object_id"])}

    async def rpc_fetch_inline(self, conn, payload):
        rec = self.memory_store.get(payload["object_id"])
        if rec is None:
            return {"error": "unknown"}
        if not rec.resolved:
            return {"pending": True}
        if rec.in_plasma:
            return {"plasma": True}
        return {"data": rec.data}

    async def rpc_publish(self, conn, channel, message):
        if channel == "worker_logs" and self.mode != "worker":
            # Scope to this driver: a worker's lines are shipped tagged with the
            # owner of the work it is running (reference: log_monitor publishes
            # per-job and drivers subscribe to their own job's channel). Lines
            # from work owned by another driver are dropped; untagged lines
            # (idle-worker chatter, system actors) go to every driver.
            owner = message.get("owner")
            if owner is not None and owner != self.worker_id.hex():
                return True
            try:
                prefix = f"({message.get('kind', 'worker')} pid={message.get('pid')}, node={message.get('node', '')[:8]})"
                out = self._log_dedup.ingest(
                    prefix, message.get("pid"), message.get("lines", ())
                )
                if out:
                    sys.stderr.write(out)
                    sys.stderr.flush()
            except Exception:
                pass  # stderr may be closed at interpreter teardown; drop the lines
        return True

    async def rpc_push_task(self, conn, spec):
        if spec.get("__direct__") and conn is not self.raylet:
            # Pushed straight from the owner: results reply over this very
            # connection, no raylet hop (reference: PushTask replies carry
            # small results inline to the caller). The raylet guard covers a
            # retried/reconstructed spec whose stale flag survived — those are
            # raylet-dispatched and must answer via task_done.
            spec["__reply_conn__"] = conn
        if spec["type"] == "actor_task":
            self._enqueue_actor_task(spec)
        elif spec.get("__direct__"):
            self._lease_executor.submit(self._execute_task_guarded, spec)
        else:
            self._task_executor.submit(self._execute_task_guarded, spec)

    async def rpc_push_batch(self, conn, specs):
        for spec in specs:
            await self.rpc_push_task(conn, spec)

    async def rpc_chan_pull(self, conn, name, reader, index, poll: float = 25.0):
        """Cross-node channel long-poll: serve one ring item to a remote reader
        (ring lives in this process — see experimental/channel.py RpcChannel).
        Poll interval backs off 0.5ms -> 10ms so a hot pipeline sees sub-ms
        latency while an idle one doesn't spin the shared event loop."""
        from ray_tpu.experimental.channel import _ring_pull

        deadline = time.monotonic() + min(poll, 25.0)
        delay = CONFIG.channel_poll_min_s
        while True:
            resp = _ring_pull(name, reader, index)
            if "wait" not in resp and "unknown" not in resp:
                return resp
            if time.monotonic() > deadline:
                return resp  # reader loop retries (keeps conns live/cancellable)
            await asyncio.sleep(delay)
            delay = min(delay * 1.5, CONFIG.channel_poll_max_s)

    async def rpc_chan_close(self, conn, name):
        from ray_tpu.experimental.channel import _ring_close

        return _ring_close(name)

    async def rpc_chan_detach(self, conn, name, reader):
        """Multicast dead-subscriber unwind: stop counting one reader slot
        toward the named ring's back-pressure (experimental/channel.py)."""
        from ray_tpu.experimental.channel import _ring_detach

        return _ring_detach(name, reader)

    async def rpc_init_actor(self, conn, actor_id: ActorID, spec):
        fut = self._task_executor.submit(self._init_actor, actor_id, spec)
        return await asyncio.wrap_future(fut)

    async def rpc_exit(self, conn):
        os._exit(0)

    # ------------------------------------------------------------------ execution

    def _materialize(self, loc):
        if "v" in loc:
            value = serialization.loads(loc["v"])
            return value
        oid, owner = loc["ref"]
        ref = ObjectRef(oid, owner)
        return self.get([ref])[0]

    def _materialize_args(self, spec):
        args = [self._materialize(a) for a in spec["args"]]
        kwargs = {k: self._materialize(v) for k, v in spec["kwargs"].items()}
        return args, kwargs

    def _init_actor(self, actor_id: ActorID, spec) -> dict:
        try:
            from ray_tpu._private import runtime_env as runtime_env_mod

            # The actor owns this worker process: its runtime env applies for life.
            runtime_env_mod.apply_permanent(spec.get("runtime_env"))
            cls = self.functions.load(spec["cls_key"])
            args, kwargs = self._materialize_args(spec)
            instance = cls.__new__(cls)
            # Identity is visible DURING __init__ (reference:
            # get_runtime_context().get_actor_id() works in constructors —
            # e.g. replicas registering themselves with coordinators).
            self.actor_id = actor_id
            instance.__init__(*args, **kwargs)
            self.actor_runtime = _ActorRuntime(
                instance, spec.get("max_concurrency", 1), spec.get("is_async", False),
                concurrency_groups=spec.get("concurrency_groups"),
                method_groups=spec.get("method_groups"),
                out_of_order=spec.get("allow_out_of_order_execution", False),
            )
            return {"ok": True}
        except Exception:
            self.actor_id = None
            return {"ok": False, "error": traceback.format_exc()}

    def _enqueue_actor_task(self, spec):
        """Per-caller sequence ordering (ActorSchedulingQueue parity). Runs on io thread."""
        rt = self.actor_runtime
        if rt is None:
            return
        if rt.out_of_order:
            # Explicit out-of-order mode (reference:
            # out_of_order_actor_submit_queue.cc): dispatch on arrival, no
            # seq gating — threaded actors trade ordering for latency.
            self._dispatch_actor_task(rt, spec)
            return
        caller = spec["caller_id"]
        # First message from a caller sets the baseline: after an actor restart the
        # caller's sequence counter keeps counting, and the old incarnation's numbers
        # must not wedge the new one. Per-caller transport is ordered, so the first
        # arrival is the lowest outstanding seq.
        expected = rt.expected_seq.get(caller)
        if expected is None:
            expected = spec["seq"]
        rt.buffered[(caller, spec["seq"])] = spec
        while (caller, expected) in rt.buffered:
            ready = rt.buffered.pop((caller, expected))
            expected += 1
            rt.expected_seq[caller] = expected
            self._dispatch_actor_task(rt, ready)

    def _dispatch_actor_task(self, rt, spec):
        """Route a released call to its concurrency group's executor. Dispatch
        never blocks on execution, so a wedged group cannot stall another."""
        group = rt.group_of(spec)
        if group is not None and group not in rt.concurrency_groups:
            # Unknown group: fail THIS call with a proper error result instead
            # of wedging the queue (validated caller-side too when declared).
            spec["__invalid_group__"] = (
                f"actor has no concurrency group {group!r} "
                f"(declared: {sorted(rt.concurrency_groups)})"
            )
            group = None
        if rt.is_async:
            asyncio.run_coroutine_threadsafe(
                self._execute_async_actor_task(spec), rt.async_loop
            )
        else:
            executor = rt.group_executors.get(group, rt.executor)
            executor.submit(self._execute_task_guarded, spec)

    def _resolve_actor_method(self, instance, method_name: str):
        """Method lookup plus the __rtpu_apply__ escape hatch: run an arbitrary
        function against the actor instance (parity: the reference's __ray_call__,
        used by compiled DAGs to install their pinned exec loops)."""
        if method_name == "__rtpu_apply__":
            def apply(fn, *args, **kwargs):
                res = fn(instance, *args, **kwargs)
                if asyncio.iscoroutine(res):
                    # Coroutine fns let callers avoid stalling an async
                    # actor's event loop (the async executor awaits the
                    # returned coroutine); on sync actors run it to completion
                    # on this executor thread.
                    try:
                        asyncio.get_running_loop()
                        return res
                    except RuntimeError:
                        return asyncio.run(res)
                return res

            return apply
        return getattr(instance, method_name)

    async def _execute_async_actor_task(self, spec):
        from ray_tpu.util import tracing

        rt = self.actor_runtime
        group = rt.group_of(spec)
        sem = rt.group_semaphores.get(group, rt.semaphore)
        async with sem:
            method = self._resolve_actor_method(rt.instance, spec["method_name"])
            # Trace-context parity with the sync executor path: each call runs
            # inside its own asyncio.Task, so activating the caller's span here
            # is Task-scoped (contextvars) and nested .remote() calls made by
            # the async method continue ONE trace across processes — the serve
            # proxy -> router -> replica chain is async actors end to end.
            trace_token = tracing.activate(spec.get("trace_ctx"))
            self._record_event(
                task_id=spec["task_id"].hex(), name=spec["name"],
                state="RUNNING", **tracing.event_fields(spec.get("trace_ctx")))
            # The sink outlives the materializer thread: refs the async method
            # keeps past completion ride the reply's sequenced handoff exactly
            # like sync tasks (packaging and handoff are synchronous sections
            # on the loop thread, so their thread-locals cannot interleave).
            sink: dict = {}

            def _materialize_sinked():
                self._tls.borrow_sink = sink
                try:
                    return self._materialize_args(spec)
                finally:
                    self._tls.borrow_sink = None

            args = kwargs = result = None
            try:
                if "__invalid_group__" in spec:
                    raise ValueError(spec["__invalid_group__"])
                args, kwargs = await asyncio.get_running_loop().run_in_executor(
                    None, _materialize_sinked
                )
                result = method(*args, **kwargs)
                if asyncio.iscoroutine(result):
                    result = await result
                if spec.get("num_returns") == "streaming":
                    await self._run_streaming_async(spec, result)
                    results = []
                else:
                    results = self._package_results(spec, result)
                state = "FINISHED"
            except Exception as e:
                if spec.get("num_returns") == "streaming":
                    await asyncio.get_running_loop().run_in_executor(
                        None, self._stream_failure, spec, e
                    )
                    results = []
                else:
                    results = self._package_error(spec, e)
                state = "FAILED"
            args = kwargs = result = None  # noqa: F841 — drop frame refs first
            tracing.deactivate(trace_token)
            self._record_event(
                task_id=spec["task_id"].hex(), name=spec["name"], state=state,
                **tracing.event_fields(spec.get("trace_ctx")))
            self.reference_counter.drain_deferred()
            self._reply_actor_result(spec, results, self._borrow_handoff(spec, sink))

    def _reply_actor_result(self, spec, results, extra: dict | None = None):
        """Route actor-call results: straight back over the owner's direct
        connection when the call arrived on one, else via the raylet."""
        extra = extra or {}
        rconn = spec.pop("__reply_conn__", None)
        if rconn is not None and not rconn.closed:
            self.io.spawn(
                rconn.notify("task_result",
                             {"task_id": spec["task_id"], "results": results, **extra})
            )
            return
        self.io.spawn(
            self.raylet.notify("actor_task_done", spec["owner"], spec["task_id"],
                               results, extra)
        )

    def _execute_task_guarded(self, spec):
        try:
            self._execute_task(spec)
        except Exception:
            traceback.print_exc()

    def _execute_task(self, spec):
        from ray_tpu.util import tracing

        prev_task = getattr(self._tls, "task_id", None)
        prev_sink = getattr(self._tls, "borrow_sink", None)
        self._tls.task_id = spec["task_id"]
        # Borrowed refs first seen during this task defer registration to the
        # reply (the caller's arg pins protect them meanwhile).
        self._tls.borrow_sink = {}
        trace_token = tracing.activate(spec.get("trace_ctx"))
        self._record_event(task_id=spec["task_id"].hex(), name=spec["name"], state="RUNNING",
                           **tracing.event_fields(spec.get("trace_ctx")))
        try:
            from ray_tpu._private import runtime_env as runtime_env_mod

            # The env applies BEFORE function load / arg deserialization: both may
            # depend on py_modules/working_dir being importable.
            with runtime_env_mod.applied(spec.get("runtime_env")):
                if spec["type"] == "actor_task":
                    if "__invalid_group__" in spec:
                        raise ValueError(spec["__invalid_group__"])
                    fn = self._resolve_actor_method(
                        self.actor_runtime.instance, spec["method_name"]
                    )
                else:
                    fn = self.functions.load(spec["fn_key"])
                args, kwargs = self._materialize_args(spec)
                result = fn(*args, **kwargs)
            if spec.get("num_returns") == "streaming":
                self._run_streaming(spec, result)
                results = []
            else:
                results = self._package_results(spec, result)
            state = "FINISHED"
        except Exception as e:  # noqa: BLE001 - report any user failure to the owner
            from ray_tpu._private import debugger

            if debugger.post_mortem_enabled():
                # Park the failing frame: advertise a debug session and block
                # this task (only this task) until an operator's `ray_tpu
                # debug` drives pdb over the socket, or the wait expires;
                # the error then propagates exactly as it would have
                # (reference: RAY_DEBUG_POST_MORTEM + util/rpdb.py).
                try:
                    debugger.park_post_mortem(self, spec, e)
                except Exception:
                    pass
            if spec.get("num_returns") == "streaming":
                # Pre-iteration failure (fn load / arg materialization): the
                # stream must still terminate with an error ref, not hang.
                self._stream_failure(spec, e)
                results = []
            else:
                results = self._package_error(spec, e)
            state = "FAILED"
        finally:
            # Drop the frame's own arg/result refs and apply their deferred
            # releases BEFORE snapshotting the sink: `kept` must mean "the task
            # body stored the ref somewhere", not "the executing frame hasn't
            # exited yet" — otherwise every borrowed arg ships a useless
            # +1/-1 pair per call.
            args = kwargs = result = None  # noqa: F841
            self.reference_counter.drain_deferred()
            sink = getattr(self._tls, "borrow_sink", None) or {}
            self._tls.task_id = prev_task
            self._tls.borrow_sink = prev_sink
            tracing.deactivate(trace_token)
        extra = self._borrow_handoff(spec, sink)
        self._record_event(task_id=spec["task_id"].hex(), name=spec["name"], state=state,
                           **tracing.event_fields(spec.get("trace_ctx")))
        if spec["type"] == "actor_task":
            self._reply_actor_result(spec, results, extra)
        else:
            rconn = spec.pop("__reply_conn__", None)
            if rconn is not None and not rconn.closed:
                # Leased direct task: results go straight to the owner; the
                # raylet holds no per-task state for it. Batched per
                # connection — a burst of small-task completions coalesces
                # into a few frames instead of one send per result.
                self._queue_direct_result(
                    rconn, {"task_id": spec["task_id"], "results": results, **extra}
                )
            else:
                self.io.spawn(self.raylet.notify(
                    "task_done", spec["task_id"], results, extra
                ))

    def _borrow_handoff(self, spec, sink: dict) -> dict:
        """Build the reply's sequenced borrow metadata (see ReferenceCounter).

        - `borrows`: borrowed arg refs this executor still holds; the caller
          counts us as borrower before releasing its arg pins.
        - `result_refs`: refs pickled into the results; we pre-count the caller
          as sub-borrower HERE, before the reply leaves, so its first local ref
          is already covered whenever it lands.
        """
        caller = spec.get("owner")
        if caller is None:
            return {}
        kept = {
            oid: owner for oid, owner in sink.items()
            if self.reference_counter.num_refs(oid) > 0
            or self.reference_counter.num_borrows(oid) > 0
        }
        if kept:
            self.reference_counter.promote_task_borrows(kept, caller)
        # The sub-borrows were pre-counted at capture time (_package_results);
        # the reply only needs the lists. captured_kept = borrowed args that
        # were returned in the results (promoted at capture, must be in
        # `borrows` even though the frame dropped their last local ref).
        result_refs = list(getattr(self._tls, "result_refs", None) or ())
        captured_kept = list(getattr(self._tls, "captured_kept", None) or ())
        self._tls.result_refs = None
        self._tls.captured_kept = None
        borrows = list({*kept.keys(), *captured_kept})
        if not borrows and not result_refs:
            return {}
        return {
            "borrows": borrows,
            "result_refs": result_refs,
            "src": self._owner_address(),
        }

    def _queue_direct_result(self, rconn, payload: dict):
        key = id(rconn)
        with self._result_lock:
            self._result_queues.setdefault(key, (rconn, []))[1].append(payload)
            if key in self._result_sending:
                return
            self._result_sending.add(key)
        self.io.spawn(self._result_send_loop(key))

    async def _result_send_loop(self, key):
        while True:
            with self._result_lock:
                entry = self._result_queues.get(key)
                if entry is None or not entry[1]:
                    self._result_sending.discard(key)
                    self._result_queues.pop(key, None)
                    return
                rconn, pending = entry
                batch = pending[:]
                pending.clear()
            try:
                await rconn.notify("task_results", batch)
            except Exception:
                with self._result_lock:
                    self._result_sending.discard(key)
                    self._result_queues.pop(key, None)
                return  # owner gone: its raylet re-routes or fails the tasks

    def _package_results(self, spec, result) -> list:
        num_returns = spec["num_returns"]
        if num_returns == 0:
            values = []
        elif num_returns == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != num_returns:
                raise ValueError(
                    f"task {spec['name']} declared num_returns={num_returns} "
                    f"but returned {len(values)} values"
                )
        # Capture refs pickled into the results: the reply hands the caller a
        # sequenced borrow on each (see _borrow_handoff).
        self._tls.ref_capture = cap = []
        try:
            packaged = [
                self._package_one(oid, value, spec["owner"])
                for oid, value in zip(spec["return_ids"], values)
            ]
        finally:
            self._tls.ref_capture = None
        caller = spec.get("owner")
        if caller is not None:
            # Pre-count the caller RIGHT HERE, while the executing frame still
            # holds its own refs: the frame's refs drop (and may free) before
            # the reply is built, and the sub-borrow must already be in place.
            caller_key = _addr_key(caller)
            for oid, _owner in cap:
                self.reference_counter.add_sub_borrow(oid, caller_key)
            # A returned BORROWED arg must survive the frame drop with its
            # registration intact: re-parent it to the caller now and force it
            # into the reply's `borrows` list (the frame may hold its only ref).
            self._tls.captured_kept = self.reference_counter.promote_captured(
                [oid for oid, _ in cap], caller
            )
        self._tls.result_refs = cap
        return packaged

    def _package_one(self, oid: ObjectID, value, owner: dict) -> dict:
        pickled, raw_buffers, total = serialization.serialized_size(value)
        if total > CONFIG.max_direct_call_object_size:
            # Rides the zero-RPC direct-arena path when available.
            self._write_plasma(oid, pickled, raw_buffers, total, owner)
            return {"object_id": oid, "in_plasma": True, "size": total}
        return {"object_id": oid, "inline": serialization.assemble(pickled, raw_buffers)}

    def _stream_results(self, spec) -> "callable":
        """Build the per-item sender for a streaming task: each yielded value is
        packaged and pushed to the owner immediately (ObjectRefStream parity)."""
        owner = spec["owner"]
        task_id = spec["task_id"]
        state = {"index": 0}

        def send(value, error: bool = False):
            index = state["index"]
            state["index"] = index + 1
            oid = ObjectID.from_task(task_id, 0x10000000 + index)
            if error:
                out = {"object_id": oid, "inline": serialization.dumps(value), "error": True}
            else:
                # Refs yielded into the stream ride the same sequenced handoff
                # as task results: pre-count the consumer before the item
                # leaves, and re-parent deferred arg borrows so they survive
                # the generator frame (see ReferenceCounter docstring).
                self._tls.ref_capture = cap = []
                try:
                    out = self._package_one(oid, value, owner)
                finally:
                    self._tls.ref_capture = None
                if cap:
                    okey = _addr_key(owner)
                    for roid, _o in cap:
                        self.reference_counter.add_sub_borrow(roid, okey)
                    self.reference_counter.promote_captured(
                        [roid for roid, _o in cap], owner
                    )
                    out["result_refs"] = cap
                    out["src"] = self._owner_address()
            self.io.run(self.raylet.notify("stream_item", owner, task_id, index, out))

        def finish():
            self.io.run(self.raylet.notify("stream_end", owner, task_id, state["index"]))

        return send, finish

    def _run_streaming(self, spec, result):
        """Drive a (sync) generator result, pushing each item to the owner.

        Never raises: a broken raylet link means this worker is about to die
        (worker mode exits when its raylet conn closes) and the raylet-side
        failure path will abort the owner's stream. Raising into the caller's
        generic handler would restart the stream at index 0 and silently
        truncate it at the owner.
        """
        try:
            send, finish = self._stream_results(spec)
            try:
                for value in result:
                    send(value)
            except rpc.RpcError:
                return
            except Exception as e:  # noqa: BLE001 - mid-stream error becomes an error ref
                send(RayTpuTaskError.from_exception(spec["name"], e), error=True)
            finish()
        except Exception:
            traceback.print_exc()

    def _stream_failure(self, spec, exc: Exception):
        send, finish = self._stream_results(spec)
        send(RayTpuTaskError.from_exception(spec["name"], exc), error=True)
        finish()

    async def _run_streaming_async(self, spec, result):
        """Drive an async (or sync) generator inside an async actor. Never raises
        (see _run_streaming)."""
        loop = asyncio.get_running_loop()
        try:
            send, finish = self._stream_results(spec)
            try:
                if hasattr(result, "__anext__"):
                    async for value in result:
                        await loop.run_in_executor(None, send, value)
                else:
                    for value in result:
                        await loop.run_in_executor(None, send, value)
            except rpc.RpcError:
                return
            except asyncio.CancelledError:
                # Stream cancelled mid-flight (serve cancel plane / actor
                # teardown): close the producer so its finally-blocks release
                # what they hold, then finish the stream cleanly — the owner
                # sees a short stream, not a failed task.
                try:
                    if hasattr(result, "aclose"):
                        await result.aclose()
                    elif hasattr(result, "close"):
                        result.close()
                except Exception:
                    pass  # producer teardown is best-effort: the stream still
                    # finishes below, and the generator's own finally already
                    # released what it held before the close raised
                await loop.run_in_executor(None, finish)
                return
            except Exception as e:  # noqa: BLE001
                err = RayTpuTaskError.from_exception(spec["name"], e)
                await loop.run_in_executor(None, lambda: send(err, error=True))
            await loop.run_in_executor(None, finish)
        except Exception:
            traceback.print_exc()

    def _package_error(self, spec, exc: Exception) -> list:
        err = RayTpuTaskError.from_exception(spec["name"], exc)
        data = serialization.dumps(err)
        return [
            {"object_id": oid, "inline": data, "error": True} for oid in spec["return_ids"]
        ]
