"""Scheduler: iteration-level admission + step assembly for the decode engine.

Design parity: Orca's iteration-level scheduling and vLLM's chunked-prefill
scheduler (`vllm/core/scheduler.py`) — the engine no longer admits work
request-at-a-time. Every iteration the scheduler assembles ONE step from the
waiting/running queues: prefills are split into bucketed chunks drawn from
the engine's fixed `_prefill_buckets` table (so no new traffic shape compiles
a new program) and interleaved with batched decode / speculative-verify
phases under a token budget. Decode and verify tokens are reserved FIRST;
prefill chunks fill the remainder — a long prompt therefore cannot stall
in-flight decodes for more than one budget's worth of prefill compute, and a
steady decode load cannot starve prefill because the head-of-line prefill
request is always granted at least one minimum-bucket chunk per iteration.

Multi-tenant admission (docs/multitenancy.md): the waiting set is PER-TENANT
queues drained by stride-weighted fair queueing — each admission charges its
tenant's virtual pass `(prompt_len + max_tokens) / weight`, and the minimum-
pass tenant goes next — so under saturation each tenant's token share tracks
its configured weight instead of its submission rate (`wfq=False` restores
the single arrival-order FIFO as the A/B control). Per-tenant quotas
(`llm_tenant_max_queue_depth`) bound each queue independently: one tenant's
overload raises `EngineOverloadedError` for THAT tenant while the others
keep flowing. Admission is adapter-aware: a request whose LoRA adapter is
resident in the engine's AdapterCache is preferred (bounded skip-ahead, the
skipped tenant is not charged), and cold head-of-line tenants trigger their
page-ins at admission so uploads batch ahead of the next decode dispatch.

The scheduler is pure host bookkeeping: it never touches a device (the
injected adapter_acquire callback dispatches async H2D work but never
blocks). The engine's stepper thread calls `next_plan()` and executes the
returned phases (chunk dispatch -> spec verify -> batched decode);
`submit()` is the only cross-thread entry point and is guarded by the
admission lock.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

import numpy as np

from ray_tpu.llm.kvcache.manager import PrefixLease
from ray_tpu.util import xprof


class EngineOverloadedError(RuntimeError):
    """The engine's admission queue is at its configured depth cap
    (`llm_max_queue_depth`), or the submitting tenant's own queue is at its
    quota (`llm_tenant_max_queue_depth`); the submit was rejected without
    enqueueing. Callers should shed load or retry with backoff."""


class Slot:
    """One decode slot's host-side state. `active` means the slot is in the
    decode phase (prompt fully prefilled, emitting tokens); a slot being
    chunk-prefilled is reserved via its Request and is not yet active."""

    __slots__ = ("active", "generated", "params", "callback", "prompt_len",
                 "tokens", "host_len", "adapter", "history", "tenant",
                 "adapter_handle", "rec", "rid", "constraint")

    def __init__(self):
        self.active = False
        self.generated = 0
        self.params = None          # SamplingParams
        self.callback = None
        self.prompt_len = 0
        self.tokens: List[int] = []       # generated tokens
        self.host_len = 0  # kv rows present for this slot (host mirror of lens)
        self.adapter = 0   # stable adapter uid (kvcache namespace, metering)
        self.tenant = ""
        self.adapter_handle = None  # pin released when the slot finishes
        self.rec = None    # flight-recorder RequestRecord (host-side only)
        self.rid = None    # request id: the engine cancel() lookup key
        self.constraint = None  # guided-decoding ConstraintState (or None)
        # prompt + generated tokens: the draft providers' lookup corpus
        self.history: List[int] = []


def _host_drawn(sampling, constraint) -> bool:
    """Whether a slot's decode rounds draw its row on the host, from what the slot
    carries: a guided slot's mask is a host automaton's `[V]` row a step, and a
    top-k filter at a temperature is a sort of the row, which the decode programs'
    sampler does not hold (at temperature 0 the host's draw ignores `top_k`, and so
    does the device). The engine's rounds and the plan's steps both ask here: such a
    slot's token cannot feed the next step inside a program, so it holds a plan to
    one step (`Scheduler._choose_multi_step`)."""
    return constraint is not None or (sampling.temperature > 0 and sampling.top_k > 0)


class Request:
    """One admitted unit of work, from submit() to slot activation.

    kind "prompt": a prompt to prefill (possibly in several chunks, possibly
    behind a prefix-cache lease). kind "prefilled": a PD-disagg transfer —
    the KV prefix rides in and the request feeds the running queue directly
    (attach + first sample, no prefill chunks).

    `adapter` is the STABLE registry uid (prefix-cache namespace, metering);
    `adapter_slot` is the device-table row resolved at admission by the
    AdapterCache pin (`adapter_handle`) — the two diverge once paging moves
    adapters between slots.
    """

    __slots__ = ("kind", "prompt", "sampling", "callback", "adapter",
                 "prompt_len", "prefilled", "slot", "lease", "cached_offset",
                 "kv", "first_logits", "chunks", "tenant", "adapter_slot",
                 "adapter_handle", "seq", "rec", "rid", "constraint")

    def __init__(self, kind: str, *, prompt: Optional[List[int]] = None,
                 sampling=None, callback=None, adapter: int = 0,
                 prompt_len: int = 0, kv: Optional[np.ndarray] = None,
                 first_logits: Optional[np.ndarray] = None,
                 tenant: str = ""):
        self.kind = kind
        self.prompt = prompt or []
        self.sampling = sampling
        self.callback = callback
        self.adapter = adapter
        self.tenant = tenant
        self.prompt_len = prompt_len or len(self.prompt)
        self.prefilled = 0          # prompt tokens whose KV is in the slot
        self.slot: Optional[int] = None
        self.lease: Optional[PrefixLease] = None  # pending attach
        self.cached_offset = 0      # tokens served by the prefix cache
        self.kv = kv                # transferred KV ("prefilled" kind)
        self.first_logits = first_logits
        self.chunks = 0             # prefill chunks dispatched so far
        self.adapter_slot = 0       # device-table row (pinned at admission)
        self.adapter_handle = None
        self.seq = 0                # arrival order (the FIFO control's key)
        self.rec = None             # flight-recorder RequestRecord (or None)
        self.rid = None             # caller request id (cancel lookup key)
        self.constraint = None      # guided ConstraintState (begin()..release())


class ScheduledChunk:
    """One prefill chunk (or a transferred-prefix attach) for one request."""

    __slots__ = ("request", "slot", "offset", "tokens", "bucket",
                 "is_first", "is_last")

    def __init__(self, request: Request, offset: int, tokens: List[int],
                 bucket: int, is_first: bool, is_last: bool):
        self.request = request
        self.slot = request.slot
        self.offset = offset        # absolute KV row where this chunk lands
        self.tokens = tokens        # [] for kind "prefilled" (attach-only)
        self.bucket = bucket
        self.is_first = is_first
        self.is_last = is_last


class Plan:
    """One engine iteration: chunks -> spec verify -> batched decode.

    The phase order is load-bearing: speculative verify writes k+1 rows into
    EVERY slot's cache (non-participants behind a write gate), so plain
    decode must dispatch after verify to land the canonical row last.
    """

    __slots__ = ("chunks", "decode_slots", "spec_slots", "proposals",
                 "multi_step", "steps_max", "limit", "prefill_tokens",
                 "decode_tokens", "verify_tokens", "idle")

    def __init__(self):
        self.chunks: List[ScheduledChunk] = []
        self.decode_slots: List[int] = []
        self.spec_slots: List[int] = []
        self.proposals: Dict[int, np.ndarray] = {}
        self.multi_step = 1
        self.steps_max = 1          # the scheduler's multi_step
        self.limit = "off"          # why multi_step < steps_max: one of LIMITS
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.verify_tokens = 0
        self.idle = True


# What held a plan's decode phase to `multi_step` steps, in the order
# `Scheduler._decide_steps` tests them (the first that holds is the plan's
# `limit`; docs/scheduler.md): multi-step switched off; no slot decoding; a
# prefill chunk (or attach) in this plan; a speculative phase; a request
# admitted whose chunks are not in this plan; a non-empty queue; a slot whose
# row the host draws (`_host_drawn`: guided, or a top-k filter at a temperature);
# a slot with fewer than `steps_max` tokens left; none.
LIMITS = ("off", "no_decode", "chunk", "spec", "prefilling", "queue",
          "sampling", "tail", "none")


class _TenantState:
    """One tenant's queue + WFQ bookkeeping + token meters."""

    __slots__ = ("queue", "weight", "pass_", "resid_skips", "admitted",
                 "rejected", "prefill_tokens", "decode_tokens")

    def __init__(self, weight: float):
        self.queue: deque = deque()
        self.weight = max(1e-6, float(weight))
        self.pass_ = 0.0            # stride virtual time
        self.resid_skips = 0        # consecutive residency skip-aheads
        self.admitted = 0
        self.rejected = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0


class Scheduler:
    """Owns waiting/prefilling/running state and assembles one Plan per
    engine iteration. Thread contract: `submit`/`queue_depth`/
    `set_tenant_weight` may be called from any thread (lock-guarded);
    everything else runs on the engine's stepper thread only."""

    # A min-pass tenant whose adapter is cold may be skipped for a resident
    # one at most this many consecutive admissions; then it is force-picked
    # (its page-in dispatches) so residency preference can't starve anyone.
    RESIDENT_SKIP_MAX = 2

    def __init__(self, *, num_slots: int, buckets, max_seq: int,
                 token_budget: int, max_queue_depth: int, multi_step: int = 1,
                 lookup: Optional[Callable] = None, name: str = "",
                 wfq: bool = True,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_quota: Optional[int] = None,
                 adapter_acquire: Optional[Callable] = None,
                 adapter_resident: Optional[Callable] = None):
        self.slots = [Slot() for _ in range(num_slots)]
        self._buckets = tuple(buckets)
        self._bucket_min = self._buckets[0]
        self.T = max_seq
        # 0 = unbudgeted: whole-prompt prefill in one chunk (the legacy
        # request-at-a-time admission shape, kept for A/B benching).
        self.token_budget = max(0, int(token_budget))
        self._max_queue_depth = max(0, int(max_queue_depth))
        self.multi_step = max(1, int(multi_step))
        self._lookup = lookup       # prefix-cache lookup(prompt, adapter)
        self.wfq = bool(wfq)
        from ray_tpu._private.config import CONFIG

        if tenant_quota is None:
            tenant_quota = CONFIG.llm_tenant_max_queue_depth
        self._tenant_quota = max(0, int(tenant_quota))
        # Offline batch admission (docs/generation.md): the batch tenant is
        # PINNED to the floor weight — a policy, not a weight the autopilot
        # or operators can raise — so online traffic always preempts it.
        self._batch_tenant = CONFIG.llm_batch_tenant
        self._batch_weight = max(1e-6, float(CONFIG.llm_batch_weight))
        self._weights: Dict[str, float] = dict(tenant_weights or {})
        # adapter uid -> AdapterHandle | None (engine-injected; None = the
        # cache is fully pinned, leave the request queued)
        self._adapter_acquire = adapter_acquire
        self._adapter_resident = adapter_resident
        self._tenants: Dict[str, _TenantState] = {}
        self._vtime = 0.0           # global WFQ virtual time
        self._seq = 0
        self._depth = 0             # total queued across tenants
        self._prefilling: List[Request] = []   # slot-assigned, chunks pending
        self._lock = threading.Lock()
        from ray_tpu.util.metrics import Counter, Gauge

        tag = {"engine": name or f"{id(self):x}"}
        self._queue_gauge = Gauge(
            "llm_engine_queue_depth",
            "requests waiting in the engine admission queue",
            tag_keys=("engine",),
        ).set_default_tags(tag)
        # Per-tenant metering (docs/multitenancy.md). ALL metric mutation
        # happens on the REPORT path (stats()): gauges export the current
        # plain-int state, counters flush deltas since the last stats()
        # call. The submit/decode paths only touch plain ints — a metric
        # mutation there can block on the GCS flush inside Metric (RL901).
        self._tenant_metrics = {
            "queue": Gauge(
                "llm_tenant_queue_depth",
                "requests waiting in one tenant's admission queue",
                tag_keys=("engine", "tenant"),
            ).set_default_tags(tag),
            "rejected": Counter(
                "llm_tenant_rejected_total",
                "tenant submits rejected at a quota or the global cap",
                tag_keys=("engine", "tenant"),
            ).set_default_tags(tag),
            "prefill": Counter(
                "llm_tenant_prefill_tokens",
                "prompt tokens prefilled, by tenant",
                tag_keys=("engine", "tenant"),
            ).set_default_tags(tag),
            "decode": Counter(
                "llm_tenant_decode_tokens",
                "completion tokens emitted, by tenant",
                tag_keys=("engine", "tenant"),
            ).set_default_tags(tag),
        }
        self._flushed_tokens: Dict[str, List[int]] = {}  # tenant -> [pf, dec, rej]
        # Per-phase occupancy: tokens assembled into the most recent
        # iteration, by phase (prefill-chunk vs decode vs spec-verify).
        # _note() records the plain tuple; stats() exports the gauges.
        self._last_plan_tokens = (0, 0, 0)  # (prefill, decode, verify)
        self._occ_gauges = {
            phase: Gauge(
                f"llm_sched_{phase}_tokens",
                f"{phase} tokens assembled into the current engine iteration",
                tag_keys=("engine",),
            ).set_default_tags(tag)
            for phase in ("prefill", "decode", "verify")
        }
        self._counters = {
            "iterations": 0, "interleaved_iterations": 0,
            "prefill_tokens": 0, "decode_tokens": 0, "verify_tokens": 0,
            "prefill_chunks": 0, "admitted": 0, "spec_rounds": 0,
            "rejected": 0, "resident_preferred": 0,
        }
        # Per `Plan.limit`, since process start: plans run, decode tokens they
        # planned, and what `decode_slots x multi_step` would have planned.
        self._by_limit = {
            limit: {"iterations": 0, "decode_tokens": 0,
                    "decode_tokens_possible": 0}
            for limit in LIMITS
        }

    # -- cross-thread API ---------------------------------------------------
    def _tenant(self, name: str) -> _TenantState:
        """Caller holds the lock."""
        t = self._tenants.get(name)
        if t is None:
            if name == self._batch_tenant:
                # Batch rides the SAME stride machinery as online tenants,
                # at the floor weight: its per-token stride is enormous, so
                # any online tenant's queued work wins every admission race
                # while otherwise-idle capacity still drains batch rows.
                t = self._tenants[name] = _TenantState(self._batch_weight)
            else:
                t = self._tenants[name] = _TenantState(
                    self._weights.get(name, 1.0)
                )
        return t

    def set_tenant_weight(self, tenant: str, weight: float):
        """Priority classes ride on weights: a tenant with weight w gets a
        w-proportional share of admitted tokens under saturation."""
        if tenant == self._batch_tenant:
            return  # the batch tenant's floor weight is not reshareable
        with self._lock:
            self._weights[tenant] = float(weight)
            if tenant in self._tenants:
                self._tenants[tenant].weight = max(1e-6, float(weight))

    def submit(self, request: Request):
        """Bounded admission: reject at the submitting TENANT's quota (other
        tenants keep flowing) or at the global depth cap, instead of growing
        the queue (and resident prompt copies) without limit under
        overload."""
        with self._lock:
            t = self._tenant(request.tenant)
            if self._tenant_quota and len(t.queue) >= self._tenant_quota:
                t.rejected += 1
                self._counters["rejected"] += 1
                raise EngineOverloadedError(
                    f"tenant {request.tenant!r} admission queue is full "
                    f"({len(t.queue)} >= llm_tenant_max_queue_depth="
                    f"{self._tenant_quota}); this tenant should shed load or "
                    f"retry with backoff (other tenants are unaffected)"
                )
            if self._max_queue_depth and self._depth >= self._max_queue_depth:
                t.rejected += 1
                self._counters["rejected"] += 1
                raise EngineOverloadedError(
                    f"engine admission queue is full ({self._depth} >= "
                    f"llm_max_queue_depth={self._max_queue_depth}); shed load "
                    f"or retry with backoff"
                )
            request.seq = self._seq
            self._seq += 1
            if not t.queue:
                # A tenant going idle must not bank credit: its pass resumes
                # at the current virtual time (standard stride re-entry).
                t.pass_ = max(t.pass_, self._vtime)
            t.queue.append(request)
            self._depth += 1

    def queue_depth(self) -> int:
        with self._lock:
            return self._depth

    def prefilling(self) -> int:
        """Requests admitted (a slot assigned) whose chunks are still to run."""
        return len(self._prefilling)

    def drain(self) -> List[Request]:
        """Remove every queued and in-prefill request (stepper death and
        engine shutdown path): the engine fails their callbacks so submitters
        don't hang. Idempotent, and exception-safe per request — one lease
        whose release raises must not leave the remaining requests leased
        (and their submitters hung): every request is still returned."""
        with self._lock:
            queued: List[Request] = []
            for t in self._tenants.values():
                queued.extend(t.queue)
                t.queue.clear()
            queued.sort(key=lambda r: r.seq)
            self._depth = 0
        queued.extend(self._prefilling)
        self._prefilling = []
        for r in queued:
            if r.lease is not None:
                lease, r.lease = r.lease, None
                try:
                    lease.release()
                except Exception:
                    pass  # pool poisoned mid-death; the callbacks must still fail
            if r.adapter_handle is not None:
                handle, r.adapter_handle = r.adapter_handle, None
                try:
                    handle.release()
                except Exception:
                    pass  # cache poisoned mid-death; keep failing callbacks
            if r.constraint is not None:
                state, r.constraint = r.constraint, None
                try:
                    state.release()
                except Exception:
                    pass  # leaksan books must balance even mid-death
        return queued

    def cancel_queued(self, rid: str) -> Optional[Request]:
        """Remove one still-queued request by its id (ANY thread — the
        client-disconnect path races the stepper's admission here, and the
        admission lock arbitrates). Returns the request — its callback,
        record, and constraint state are the caller's to fail/release — or
        None when the id is not queued (it may be prefilling or active,
        which only the stepper may touch; the engine's pending-cancel set
        covers those within one scheduler iteration)."""
        if not rid:
            return None
        with self._lock:
            for t in self._tenants.values():
                for r in t.queue:
                    if r.rid == rid:
                        t.queue.remove(r)
                        self._depth -= 1
                        return r
        return None

    def cancel_prefilling(self, rid: str) -> Optional[Request]:
        """Remove one slot-assigned, still-chunk-prefilling request by id
        (STEPPER THREAD ONLY: _prefilling is stepper-owned). Its prefix
        lease and adapter pin release here; KV rows the dispatched chunks
        already wrote are dead weight the slot's next occupant overwrites
        write-before-read (same contract as rejected spec proposals)."""
        for r in self._prefilling:
            if r.rid == rid:
                self._prefilling.remove(r)
                if r.lease is not None:
                    lease, r.lease = r.lease, None
                    try:
                        lease.release()
                    except Exception:
                        pass  # a poisoned pool must not block the cancel
                if r.adapter_handle is not None:
                    handle, r.adapter_handle = r.adapter_handle, None
                    try:
                        handle.release()
                    except Exception:
                        pass  # a poisoned adapter cache must not block the cancel
                return r
        return None

    # -- stepper-thread API -------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self.T

    def _pop_candidate_locked(self, skipped) -> Optional[Request]:
        """Pop the next request under the admission policy (caller holds the
        lock; the pass charge happens only after the adapter pin succeeds,
        via _charge). FIFO mode: global arrival order. WFQ mode: min-pass
        tenant first, with a BOUNDED skip-ahead to the nearest tenant whose
        head adapter is already resident (the skipped tenant is not charged,
        stays min-pass, and is force-picked after RESIDENT_SKIP_MAX skips so
        residency preference cannot starve a cold tenant)."""
        nonempty = [(name, t) for name, t in self._tenants.items()
                    if t.queue and name not in skipped]
        if not nonempty:
            return None
        if not self.wfq:
            name, t = min(nonempty, key=lambda nt: nt[1].queue[0].seq)
        else:
            nonempty.sort(key=lambda nt: (nt[1].pass_, nt[1].queue[0].seq))
            name, t = nonempty[0]
            if (self._adapter_resident is not None
                    and t.queue[0].adapter
                    and not self._adapter_resident(t.queue[0].adapter)
                    and t.resid_skips < self.RESIDENT_SKIP_MAX):
                for cand_name, cand in nonempty[1:]:
                    head = cand.queue[0]
                    if head.adapter == 0 or self._adapter_resident(head.adapter):
                        t.resid_skips += 1
                        self._counters["resident_preferred"] += 1
                        name, t = cand_name, cand
                        break
        t.resid_skips = 0
        req = t.queue.popleft()
        self._depth -= 1
        return req

    def _charge_locked(self, req: Request):
        """Advance the admitting tenant's pass by its expected service
        (prompt + generation budget tokens) over its weight — the stride
        step that makes long-run token share track weights."""
        t = self._tenant(req.tenant)
        t.admitted += 1
        if not self.wfq:
            return
        cost = req.prompt_len
        if req.sampling is not None:
            cost += max(1, int(req.sampling.max_tokens))
        self._vtime = t.pass_
        t.pass_ += max(1, cost) / t.weight

    def _requeue_head_locked(self, req: Request):
        t = self._tenant(req.tenant)
        t.queue.appendleft(req)
        self._depth += 1

    def _admit_waiting(self):
        """Assign free slots to waiting requests under the WFQ policy.
        Prefix-cache lookup happens here — once per request, before its
        first chunk — so chunk plans cover only the uncached suffix. The
        adapter pin ALSO happens here: a request whose adapter cannot page
        in (every slot pinned) goes back to its queue head uncharged and its
        tenant is skipped for the iteration — back-pressure, not a crash.
        Cold head-of-line tenants page in at admission, so several uploads
        batch ahead of the next decode dispatch."""
        reserved = {r.slot for r in self._prefilling}
        free = [i for i, s in enumerate(self.slots)
                if not s.active and i not in reserved]
        admitted = 0
        skipped: set = set()
        while free:
            with self._lock:
                req = self._pop_candidate_locked(skipped)
            if req is None:
                break
            if req.adapter and self._adapter_acquire is not None:
                resident = (self._adapter_resident is None
                            or self._adapter_resident(req.adapter))
                handle = self._adapter_acquire(req.adapter)
                if handle is None:
                    with self._lock:
                        self._requeue_head_locked(req)
                    skipped.add(req.tenant)
                    continue
                req.adapter_handle = handle
                req.adapter_slot = handle.slot
                if req.rec is not None and not resident:
                    # Cold adapter paged in at admission (docs/multitenancy.md)
                    req.rec.mark("adapter-page-in", adapter=req.adapter,
                                 adapter_slot=handle.slot)
            with self._lock:
                self._charge_locked(req)
            req.slot = free.pop(0)
            if (req.kind == "prompt" and self._lookup is not None):
                lease = self._lookup(req.prompt, req.adapter)
                if lease is not None:
                    req.lease = lease
                    req.cached_offset = lease.matched_tokens
                    req.prefilled = lease.matched_tokens
            if req.rec is not None:
                # Queue phase ends here: slot assigned, cache lease resolved.
                # The same instant in a profiler trace, on the device's
                # clock, its duration from the record's own stamps.
                t = req.rec.mark("admitted", slot=req.slot,
                                 cached_tokens=req.cached_offset)
                with xprof.span("rt.sched.admit", rid=req.rec.rid,
                                slot=req.slot, cached_tokens=req.cached_offset,
                                queue_us=int((t - req.rec.t_submit) * 1e6),
                                waiting=self._depth):
                    pass
            self._prefilling.append(req)
            admitted += 1
        if admitted:
            self._counters["admitted"] += admitted

    def next_plan(self, draft=None) -> Plan:
        """Assemble one iteration. Budget policy: decode (1 token/slot) and
        spec verify (k+1 tokens/slot) are reserved first; the remaining
        budget is granted to prefill chunks head-of-line-first, rounded to
        the bucket table. The head prefill request always gets at least a
        minimum-bucket chunk, so neither phase can starve the other."""
        self._admit_waiting()
        plan = Plan()
        active = [i for i, s in enumerate(self.slots) if s.active]

        # -- speculative phase: greedy slots with a live proposal ----------
        if draft is not None and active:
            k = draft.k
            for i in active:
                s = self.slots[i]
                if not self._spec_ok(s, k) or not draft.eligible(i, s):
                    continue
                proposal = draft.propose(i, s)
                if proposal is None or len(proposal) == 0:
                    continue
                plan.spec_slots.append(i)
                plan.proposals[i] = np.asarray(proposal, np.int32)
            plan.verify_tokens = sum(
                len(plan.proposals[i]) + 1 for i in plan.spec_slots
            )
        plan.decode_slots = [i for i in active if i not in plan.spec_slots]
        plan.decode_tokens = len(plan.decode_slots)

        # -- prefill chunks under the remaining budget ---------------------
        # FCFS, ONE prompt chunk per iteration (vLLM's chunked-prefill
        # discipline): the chunk bucket is then a stable function of the
        # budget, so mixed traffic exercises one or two compiled bucket
        # programs instead of spraying a different leftover-budget bucket
        # per queued request. Attach-only admissions (transferred prefixes)
        # cost no prefill compute and are never serialized behind a chunk.
        budget = self.token_budget
        spent = plan.decode_tokens + plan.verify_tokens
        chunked = False
        for req in self._prefilling:
            remaining = req.prompt_len - req.prefilled
            if req.kind == "prefilled":
                # Transferred prefix: attach-only, no prefill compute.
                plan.chunks.append(ScheduledChunk(
                    req, 0, [], self._bucket(req.prompt_len),
                    is_first=True, is_last=True,
                ))
                continue
            if remaining <= 0:
                continue
            if budget <= 0:                       # unbudgeted: whole suffix,
                grant = remaining                 # every waiting request
            elif chunked:
                continue
            else:
                # Head-of-line progress guarantee: at least one min bucket
                # even when decode reserved the whole budget.
                left = max(budget - spent, self._bucket_min)
                grant = min(remaining, self._largest_bucket(left))
                chunked = True
            bucket = self._bucket(grant)
            chunk = ScheduledChunk(
                req, req.prefilled,
                req.prompt[req.prefilled:req.prefilled + grant], bucket,
                is_first=(req.chunks == 0),
                is_last=(req.prefilled + grant >= req.prompt_len),
            )
            plan.chunks.append(chunk)
            plan.prefill_tokens += bucket

        # -- multi-step decode: only when the engine is otherwise idle -----
        plan.steps_max = self.multi_step
        plan.multi_step, plan.limit = self._decide_steps(plan)
        plan.decode_tokens = len(plan.decode_slots) * plan.multi_step

        plan.idle = not (plan.chunks or plan.decode_slots or plan.spec_slots)
        if not plan.idle:
            self._note(plan)
        return plan

    def _spec_ok(self, s: Slot, k: int) -> bool:
        return (
            s.params is not None
            and s.params.temperature == 0.0
            and s.params.top_k in (0, 1)
            # verify writes k+1 rows at host_len; past the cache end XLA
            # would CLAMP the dynamic_update_slice start and corrupt valid
            # history — the final rounds near the cap fall back to decode.
            and s.host_len + k + 1 <= self.T
        )

    def _largest_bucket(self, budget: int) -> int:
        """Largest bucket-table entry <= budget (floor at the min bucket)."""
        best = self._bucket_min
        for b in self._buckets:
            if b <= budget:
                best = b
        return best

    def _decide_steps(self, plan: Plan):
        """(decode steps of this plan, what held them under `multi_step`):
        more than one step only in a plan with nothing else to run. The
        tests in the order of LIMITS; the first that holds names the plan."""
        if self.multi_step <= 1:
            return 1, "off"
        if not plan.decode_slots:
            return 1, "no_decode"
        if plan.chunks:
            return 1, "chunk"
        if plan.spec_slots:
            return 1, "spec"
        if self._prefilling:
            return 1, "prefilling"
        if self.queue_depth() > 0:
            return 1, "queue"
        return self._choose_multi_step(plan.decode_slots)

    def _choose_multi_step(self, decode_slots: List[int]):
        """Tokens per decode dispatch, and why not `multi_step` of them: >1
        only when the program draws every slot's token itself (the argmax at
        temperature 0, the device's sampler at a plain temperature), capped
        at the smallest remaining budget and power-of-two bucketed to bound
        the jit cache."""
        if any(_host_drawn(self.slots[i].params, self.slots[i].constraint)
               for i in decode_slots):
            # A row the host draws (a guided slot's mask, a top-k filter at a
            # temperature) has to come back before the slot's next step: the
            # on-device chain of steps can feed itself neither.
            return 1, "sampling"
        remaining = min(
            self.slots[i].params.max_tokens - self.slots[i].generated
            for i in decode_slots
        )
        n = max(1, min(self.multi_step, remaining))
        bucket = 1
        while bucket * 2 <= n:
            bucket *= 2
        return bucket, ("tail" if remaining < self.multi_step else "none")

    # -- state transitions (engine-driven) ----------------------------------
    def chunk_done(self, chunk: ScheduledChunk):
        req = chunk.request
        req.prefilled += len(chunk.tokens)
        req.chunks += 1
        self._counters["prefill_chunks"] += 1
        if chunk.tokens:
            with self._lock:
                self._tenant(req.tenant).prefill_tokens += len(chunk.tokens)

    def note_emitted(self, slot: int, n: int = 1):
        """Meter n completion tokens to the slot's tenant (decode, spec-emit,
        and the admission first-token all flow through the engine's _emit)."""
        s = self.slots[slot]
        with self._lock:
            self._tenant(s.tenant).decode_tokens += n

    def start_decode(self, req: Request, first_token: int):
        """Prompt fully in the KV cache and first token sampled: the slot
        joins the running (decode) set. The adapter pin moves from the
        request to the slot; the engine releases it when the slot
        finishes."""
        s = self.slots[req.slot]
        s.active = True
        s.generated = 1
        s.params = req.sampling
        s.callback = req.callback
        s.prompt_len = req.prompt_len
        s.host_len = req.prompt_len
        s.adapter = req.adapter
        s.tenant = req.tenant
        s.adapter_handle, req.adapter_handle = req.adapter_handle, None
        s.rec = req.rec  # the decode phase records against the slot
        s.rid = req.rid
        # The constraint state rides the same request->slot handoff as the
        # adapter pin: the engine releases it when the slot finishes.
        s.constraint, req.constraint = req.constraint, None
        s.tokens = [first_token]
        s.history = list(req.prompt) + [first_token]
        if req in self._prefilling:
            self._prefilling.remove(req)

    def stats(self) -> dict:
        out = dict(self._counters)
        out["queue_depth"] = self.queue_depth()
        out["prefilling"] = self.prefilling()
        out["running"] = sum(1 for s in self.slots if s.active)
        out["token_budget"] = self.token_budget
        out["wfq"] = self.wfq
        out["tenant_quota"] = self._tenant_quota
        out["plans"] = {
            "steps_max": self.multi_step,
            "by_limit": {k: dict(v) for k, v in self._by_limit.items()},
        }
        tenants = {}
        with self._lock:
            for name, t in self._tenants.items():
                tenants[name] = {
                    "queued": len(t.queue), "weight": t.weight,
                    "admitted": t.admitted, "rejected": t.rejected,
                    "prefill_tokens": t.prefill_tokens,
                    "decode_tokens": t.decode_tokens,
                }
        out["tenants"] = tenants
        self._flush_tenant_tokens(tenants)
        try:
            self._queue_gauge.set(float(out["queue_depth"]))
            pf, dec, ver = self._last_plan_tokens
            self._occ_gauges["prefill"].set(float(pf))
            self._occ_gauges["decode"].set(float(dec))
            self._occ_gauges["verify"].set(float(ver))
        except Exception:
            pass  # metrics must never break the serving path
        return out

    def _flush_tenant_tokens(self, tenants: Dict[str, dict]):
        """Report-path metrics export: push the per-tenant token/reject
        counter DELTAS since the last flush and the current queue gauges
        (never from the submit or decode paths)."""
        for name, t in tenants.items():
            seen = self._flushed_tokens.setdefault(name, [0, 0, 0])
            if len(seen) < 3:
                seen.append(0)
            dp = t["prefill_tokens"] - seen[0]
            dd = t["decode_tokens"] - seen[1]
            dr = t["rejected"] - seen[2]
            seen[0], seen[1] = t["prefill_tokens"], t["decode_tokens"]
            seen[2] = t["rejected"]
            try:
                if dp:
                    self._tenant_metrics["prefill"].inc(
                        dp, tags={"tenant": name})
                if dd:
                    self._tenant_metrics["decode"].inc(
                        dd, tags={"tenant": name})
                if dr:
                    self._tenant_metrics["rejected"].inc(
                        dr, tags={"tenant": name})
                self._tenant_metrics["queue"].set(
                    float(t["queued"]), tags={"tenant": name})
            except Exception:
                pass  # metrics must never break the serving path

    def _note(self, plan: Plan):
        c = self._counters
        c["iterations"] += 1
        c["prefill_tokens"] += plan.prefill_tokens
        c["decode_tokens"] += plan.decode_tokens
        c["verify_tokens"] += plan.verify_tokens
        if plan.spec_slots:
            c["spec_rounds"] += 1
        if plan.prefill_tokens and (plan.decode_slots or plan.spec_slots):
            c["interleaved_iterations"] += 1
        row = self._by_limit[plan.limit]
        row["iterations"] += 1
        row["decode_tokens"] += plan.decode_tokens
        row["decode_tokens_possible"] += len(plan.decode_slots) * plan.steps_max
        # Plain tuple only: the occupancy GAUGES export from stats() — a
        # Metric mutation here would ride every planner iteration (RL901).
        self._last_plan_tokens = (
            plan.prefill_tokens, plan.decode_tokens, plan.verify_tokens)
