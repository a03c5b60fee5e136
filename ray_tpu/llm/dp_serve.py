"""Data-parallel LLM serving: dp_size engine replicas as ONE logical engine.

Design parity: reference `python/ray/llm/_internal/serve/deployments/
data_parallel/dp_server.py` + `dp_rank_assigner.py` — each replica claims a
unique dp rank from a rank-assigner actor at startup, and requests fan out
across the rank set. TPU shape: every rank is a full DecodeEngine on its own
slice/chip; the serve handle's power-of-two router spreads requests, and the
rank identity travels in responses for placement-aware callers (e.g. a KV
router pinning conversations to a rank).
"""

from __future__ import annotations

import asyncio
import inspect
from collections import OrderedDict
from typing import Dict, List, Optional, Union

import ray_tpu
from ray_tpu.llm import LLMConfig, LLMServer, resolve_tokenizer


class DPRankAssigner:
    """Rank handout keyed by the holder's ACTOR identity, with health-checked
    reclamation: a replica that crashes (or a whole app deleted and redeployed)
    leaves a DEAD holder whose rank is reclaimed the next time demand exceeds
    the free list. Parity: dp_rank_assigner.DPRankAssigner."""

    def __init__(self, dp_size: int):
        self._dp_size = dp_size
        self._free = list(range(dp_size))
        self._held: dict = {}  # holder actor-id hex -> rank

    def ensure_size(self, dp_size: int) -> int:
        """An app built under a name that is still alive gets this actor as it
        stands (`get_if_exists`), with the rank count of the app that made it:
        grow it to the new app's size. Ranks are never taken away here; a
        smaller app simply leaves the upper ones free."""
        if dp_size > self._dp_size:
            self._free.extend(range(self._dp_size, dp_size))
            self._dp_size = dp_size
        return self._dp_size

    def _reclaim_dead(self):
        from ray_tpu.util.state import list_actors

        # Replicas claim ranks DURING __init__, while their actor is still
        # PENDING_CREATION — any not-confirmed-dead state counts as live, or a
        # loading replica's rank could be handed out twice.
        alive = {a["actor_id"].hex() for a in list_actors()
                 if a.get("state") != "DEAD"}
        for token in [t for t in self._held if t not in alive]:
            self._free.append(self._held.pop(token))
        self._free.sort()

    def assign(self, replica_token: str) -> int:
        if replica_token in self._held:
            return self._held[replica_token]
        if not self._free:
            self._reclaim_dead()
        if not self._free:
            raise RuntimeError(f"all {self._dp_size} dp ranks assigned")
        rank = self._free.pop(0)
        self._held[replica_token] = rank
        return rank

    def release(self, replica_token: str) -> bool:
        rank = self._held.pop(replica_token, None)
        if rank is None:
            return False
        self._free.append(rank)
        self._free.sort()
        return True

    def ranks(self) -> dict:
        return dict(self._held)


class DPLLMServer(LLMServer):
    """One DP rank: a full engine replica that claims its rank at startup."""

    def __init__(self, config: LLMConfig, assigner):
        # Token = this replica ACTOR's id: stable for the replica's lifetime
        # and auditable by the assigner's liveness reclamation when it dies.
        self._replica_token = (
            ray_tpu.get_runtime_context().get_actor_id().hex()
        )
        self._assigner = assigner
        self._rank_released = False
        self.dp_rank = ray_tpu.get(assigner.assign.remote(self._replica_token))
        from ray_tpu.devtools import leaksan

        leaksan.track("dp_rank_token", token=self._replica_token)
        super().__init__(config)

    async def get_dp_rank(self) -> int:
        return self.dp_rank

    async def generate(self, prompt: Union[str, List[int]], **kw) -> dict:
        out = await super().generate(prompt, **kw)
        out["dp_rank"] = self.dp_rank
        return out

    async def cache_stats(self) -> dict:
        """Engine prefix-cache counters, rank-tagged for the DP router's
        aggregate view (docs/kvcache.md)."""
        stats = await super().cache_stats()
        return {"dp_rank": self.dp_rank, **(stats or {})}

    async def scheduler_stats(self) -> dict:
        """Iteration-level scheduler occupancy + spec acceptance, rank-tagged
        (docs/scheduler.md)."""
        stats = await super().scheduler_stats()
        return {"dp_rank": self.dp_rank, **stats}

    async def adapter_stats(self) -> dict:
        """AdapterCache residency/paging counters, rank-tagged — the fleet
        view of where each adapter is actually paged in
        (docs/multitenancy.md)."""
        stats = await super().adapter_stats()
        return {"dp_rank": self.dp_rank, **(stats or {})}

    async def recorder_stats(self) -> dict:
        """Flight-recorder counters, rank-tagged; calling it flushes this
        rank's pending SLO metrics and trace spans
        (docs/observability.md)."""
        stats = await super().recorder_stats()
        return {"dp_rank": self.dp_rank, **stats}

    async def autopilot_signals(self) -> dict:
        """Autopilot signal probe, rank-tagged (docs/autoscale.md)."""
        sig = await super().autopilot_signals()
        return {"dp_rank": self.dp_rank, **sig}

    async def capture_profile(self, duration_s: float = 3.0,
                              log_dir: Optional[str] = None) -> dict:
        """Profiler capture, rank-tagged (docs/observability.md)."""
        out = await super().capture_profile(duration_s, log_dir)
        return {"dp_rank": self.dp_rank, **out}

    def _release_rank(self):
        """Idempotent: hand the dp rank back to the assigner exactly once
        (double release would free a rank a LIVE successor already claimed).
        Returns the in-flight ref, or None when already released."""
        if self._rank_released:
            return None
        self._rank_released = True
        from ray_tpu.devtools import leaksan

        leaksan.untrack("dp_rank_token", token=self._replica_token)
        return self._assigner.release.remote(self._replica_token)

    async def shutdown(self):
        """Explicit retirement: release the rank NOW (the assigner's lazy
        dead-actor reclamation is the backstop, not the path) and stop the
        engine."""
        ref = self._release_rank()
        if ref is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: ray_tpu.get(ref, 5)
            )
        await super().shutdown()

    def __del__(self):
        try:
            self._release_rank()  # fire-and-forget: __del__ cannot block; assigner audits stale tokens
        except Exception:
            pass


class DPRouter:
    """Front door over the DP rank set, cache-aware (SGLang's cache-aware
    scheduler shape): the router fingerprints each prompt as a hash chain
    over its first `llm_router_fingerprint_blocks` KV blocks, remembers which
    replica last served every chain prefix, and routes a new request to the
    replica with the LONGEST expected prefix-cache match — that replica's
    paged KV pool (docs/kvcache.md) then prefills suffix-only. Requests with
    no trackable prefix (or when the preferred replica is overloaded) fall
    back to the serve handle's power-of-two-choices balancing (parity:
    dp_server's request fanout); `ranks()` exposes the live rank map."""

    # Don't chase a prefix hit onto a replica carrying this many more
    # in-flight requests than the least-loaded one: recomputing a prefix is
    # cheaper than queueing behind a hot spot (SGLang's balanced fallback).
    IMBALANCE_TOLERANCE = 8
    # Per-replica LRU cap on remembered chain hashes (ints; memory is tiny,
    # the cap bounds staleness relative to the replica's real pool).
    FINGERPRINT_CAP = 4096
    # Per-replica LRU cap on remembered adapter names (residency broadcast):
    # generously above any engine's device-slot count, so the cap only
    # bounds staleness, never correctness (a stale entry just means one
    # page-in on the replica that evicted it).
    ADAPTER_CAP = 256
    # Hot-prefix memory for scale-up bootstrap (docs/autoscale.md): the
    # router remembers the most-routed whole-block prefixes so a replica
    # the autopilot just spawned can pull them from current holders and
    # join WARM instead of recomputing the working set request by request.
    HOT_PREFIX_CAP = 32
    BOOTSTRAP_TOP_K = 4

    def __init__(self, server_handle, assigner, config: Optional[LLMConfig] = None):
        from ray_tpu._private.config import CONFIG

        self._server = server_handle
        self._assigner = assigner
        self._tokenizer = (
            resolve_tokenizer(config.tokenizer) if config is not None else None
        )
        self._block = max(1, CONFIG.llm_kv_block_size)
        self._fp_blocks = max(1, CONFIG.llm_router_fingerprint_blocks)
        # replica actor_id -> LRU of chain hashes it has (probably) cached
        self._fingerprints: Dict[object, OrderedDict] = {}
        # replica actor_id -> LRU of adapter names (probably) paged in there:
        # recorded on every routed request, exactly like the prefix
        # fingerprints, so tenants land where their adapter (and their
        # prefix cache, which is namespaced BY adapter) is already hot.
        self._adapter_res: Dict[object, OrderedDict] = {}
        # chain tuple -> {"token_ids", "adapter", "hits"}: the bootstrap
        # source material. Replica ids already offered a bootstrap live in
        # _bootstrapped so each new replica is primed at most once.
        self._hot_prefixes: OrderedDict = OrderedDict()
        self._bootstrapped: set = set()
        self._routing = {"cache_routed": 0, "balanced": 0, "untracked": 0,
                         "adapter_routed": 0, "remote_fetched": 0,
                         "remote_fetch_failed": 0, "bootstrap_fetched": 0,
                         "bootstrap_failed": 0, "retired_pruned": 0}

    # -- prefix fingerprints -----------------------------------------------
    def _chain(self, token_ids: List[int]) -> List[int]:
        """Hash chain over the first N whole blocks: chain[i] identifies the
        (i+1)-block prefix, so set membership of chain[i] implies the replica
        has seen (and likely still holds) that whole prefix."""
        bs = self._block
        h = 0
        out: List[int] = []
        for i in range(min(len(token_ids) // bs, self._fp_blocks)):
            h = hash((h, tuple(token_ids[i * bs : (i + 1) * bs])))
            out.append(h)
        return out

    def _record(self, actor_id, chain: List[int], adapter: str = ""):
        fps = self._fingerprints.setdefault(actor_id, OrderedDict())
        for h in chain:
            fps.pop(h, None)
            fps[h] = None
        while len(fps) > self.FINGERPRINT_CAP:
            fps.popitem(last=False)
        if adapter:
            res = self._adapter_res.setdefault(actor_id, OrderedDict())
            res.pop(adapter, None)
            res[adapter] = None
            while len(res) > self.ADAPTER_CAP:
                res.popitem(last=False)

    def _note_hot_prefix(self, chain: List[int], token_ids: List[int],
                         adapter: str):
        """Remember this request's whole-block prefix as bootstrap material
        (bounded LRU with hit counts; plain dict ops, hot-path safe)."""
        covered = len(chain) * self._block
        key = tuple(chain)
        info = self._hot_prefixes.pop(key, None)
        if info is None:
            info = {"token_ids": list(token_ids[:covered]),
                    "adapter": adapter, "hits": 0}
        info["hits"] += 1
        self._hot_prefixes[key] = info
        while len(self._hot_prefixes) > self.HOT_PREFIX_CAP:
            self._hot_prefixes.popitem(last=False)

    def _match_len(self, actor_id, chain: List[int]) -> int:
        fps = self._fingerprints.get(actor_id) or ()
        m = 0
        for h in chain:
            if h not in fps:
                break
            m += 1
        return m

    def _pick(self, chain: List[int], adapter: str = ""):
        """(replica, router, mode, holder). Preference order: a replica
        already holding the request's ADAPTER (longest prefix match among
        holders as the tie-break, least-loaded otherwise — the shared
        affinity_pick helper behind serve multiplexing), then the
        longest-expected-prefix replica, then the balanced pow-2 pick. Every
        preference is imbalance-guarded: paging an adapter (or recomputing a
        prefix) is cheaper than queueing behind a hot spot.

        `holder` is the best prefix-holding replica when the CHOSEN replica
        is a different one (holder overloaded, or adapter routing won) —
        the cluster prefix plane's fetch source (docs/kvcache.md): instead
        of recomputing, the chosen replica can pull the prefix from the
        holder's cache over a DeviceChannel stream."""
        from ray_tpu.serve.handle import affinity_pick

        router = self._server.generate._get_router()
        replicas = router.replicas()
        live = {r._actor_id for r in replicas}
        for aid in [a for a in self._fingerprints if a not in live]:
            del self._fingerprints[aid]  # replica died or was redeployed
        for aid in [a for a in self._adapter_res if a not in live]:
            del self._adapter_res[aid]
        self._bootstrapped = {a for a in self._bootstrapped if a in live}
        # A replica this router has never seen (an autopilot scale-up) gets
        # one background bootstrap: pull the hottest prefixes from their
        # current holders so it joins warm (docs/autoscale.md).
        for r in replicas:
            if r._actor_id in self._bootstrapped:
                continue
            self._bootstrapped.add(r._actor_id)
            if (len(replicas) > 1 and self._hot_prefixes
                    and self._remote_fetch_enabled()):
                try:
                    asyncio.get_running_loop().create_task(
                        self.bootstrap_replica(r))
                except RuntimeError:
                    pass  # no running loop (sync test harness): skip
        loads = router.loads() if len(replicas) > 1 else {}

        def overloaded(r):
            if len(replicas) <= 1:
                return False
            least = min(loads.get(x._actor_id, 0) for x in replicas)
            return loads.get(r._actor_id, 0) - least > self.IMBALANCE_TOLERANCE

        # Best prefix holder fleet-wide (fetch source when the pick differs).
        best, best_len = None, 0
        for r in replicas:
            m = self._match_len(r._actor_id, chain)
            if m > best_len:
                best, best_len = r, m

        def result(picked, mode):
            holder = None
            if (best is not None
                    and picked._actor_id != best._actor_id):
                holder = best
            return picked, router, mode, holder

        if adapter:
            holder_ids = {
                aid for aid, res in self._adapter_res.items() if adapter in res
            }
            if holder_ids:
                # Among adapter holders, a prefix match wins; otherwise the
                # least-loaded holder (the multiplex affinity primitive).
                abest, abest_len = None, 0
                for r in replicas:
                    if r._actor_id not in holder_ids:
                        continue
                    m = self._match_len(r._actor_id, chain)
                    if abest is None or m > abest_len:
                        abest, abest_len = r, m
                if abest is not None and abest_len == 0:
                    abest = affinity_pick(replicas, holder_ids, loads)
                if abest is not None and not overloaded(abest):
                    return result(router.pick_replica(abest), "adapter_routed")
        if best is not None and not overloaded(best):
            return result(router.pick_replica(best), "cache_routed")
        return result(router.pick(""), "balanced")

    @staticmethod
    def _remote_fetch_enabled() -> bool:
        from ray_tpu._private.config import CONFIG

        return bool(CONFIG.llm_kv_remote_fetch)

    async def _remote_fetch(self, holder, replica, token_ids: List[int],
                            adapter: str) -> bool:
        """Pull token_ids' prefix from `holder`'s cache into `replica`'s:
        export on the holder (lease + background DeviceChannel send), import
        on the destination (stream recv + cache insert). Control calls ride
        the replicas' ordinary handle_request path; the KV payload rides the
        stream — it never passes through this router. Best-effort by
        contract: any failure means the destination just recomputes."""
        loop = asyncio.get_running_loop()

        def fetch() -> bool:
            try:
                desc = ray_tpu.get(
                    holder.handle_request.remote(
                        "export_prefix", (list(token_ids),), {"lora": adapter}
                    ), 30,
                )
                if not desc:
                    return False
                inserted = ray_tpu.get(
                    replica.handle_request.remote(
                        "import_prefix", (desc, list(token_ids)),
                        {"lora": adapter},
                    ), 30,
                )
                return bool(inserted)
            except Exception:
                return False

        return await loop.run_in_executor(None, fetch)

    def _submit(self, router, replica, args: tuple, kwargs: dict):
        """Dispatch to the chosen replica with the handle's exact in-flight
        bookkeeping and dead-replica failover (resubmits rebalance)."""
        from ray_tpu.serve.handle import DeploymentResponse

        def submit_to(r):
            ref = r.handle_request.remote("generate", args, kwargs)
            ray_tpu.global_worker().memory_store.add_done_callback(
                ref.id, lambda *_a, _r=r: router.done(_r)
            ) or router.done(r)
            return ref

        def resubmit():
            router.evict()  # stale table: the picked replica was dead
            return submit_to(router.pick(""))

        return DeploymentResponse(submit_to(replica), resubmit)

    # -- request path ------------------------------------------------------
    async def generate(self, prompt: Union[str, List[int]], **kw) -> dict:
        token_ids: Optional[List[int]] = None
        if isinstance(prompt, (list, tuple)):
            token_ids = list(prompt)
        elif self._tokenizer is not None:
            token_ids = self._tokenizer.encode(prompt)
        chain = self._chain(token_ids) if token_ids else []
        adapter = kw.get("lora") or ""
        routable = getattr(self._server.generate, "_get_router", None)
        if (not chain and not adapter) or routable is None:
            # No whole-block prefix and no adapter to track (or a handle
            # without routing machinery, e.g. a plain callable in tests):
            # balanced fanout.
            self._routing["untracked"] += 1
            return await self._server.generate.remote(prompt, **kw)
        replica, router, mode, holder = self._pick(chain, adapter)
        if (holder is not None and token_ids is not None
                and self._remote_fetch_enabled()):
            # Cluster prefix plane (docs/kvcache.md): the chosen replica
            # pulls the prefix from the holder's cache over a DeviceChannel
            # stream BEFORE the request lands, so its local lookup hits and
            # prefill is suffix-only. N replicas' memory (plus their disk
            # tiers) act as one logical prefix store; a failed fetch is a
            # recompute, never an error.
            if await self._remote_fetch(holder, replica, token_ids, adapter):
                mode = "remote_fetch"
                self._routing["remote_fetched"] += 1
            else:
                self._routing["remote_fetch_failed"] += 1
        if mode != "remote_fetch":
            self._routing[mode] += 1
        self._record(replica._actor_id, chain, adapter)
        if chain and token_ids is not None:
            self._note_hot_prefix(chain, token_ids, adapter)
        # Router-side tokenization rides along: replicas accept token lists.
        # The routing reason rides too — the replica's flight recorder stamps
        # it into the request's trace and timing breakdown.
        kw = dict(kw)
        kw.setdefault("route", mode)
        args = (token_ids,) if token_ids is not None else (prompt,)
        return await self._submit(router, replica, args, kw)

    async def generate_stream(self, prompt: Union[str, List[int]], **kw):
        """Streaming twin of generate(): the SAME cache/adapter-aware pick,
        remote-fetch, and routing bookkeeping, then per-token deltas streamed
        from the chosen rank (docs/generation.md). Closing this generator
        mid-stream rides the serve cancel plane down to the rank's engine —
        the finally closes the inner stream, which fires cancel_stream on the
        replica, and the decode slot frees within one scheduler iteration."""
        token_ids: Optional[List[int]] = None
        if isinstance(prompt, (list, tuple)):
            token_ids = list(prompt)
        elif self._tokenizer is not None:
            token_ids = self._tokenizer.encode(prompt)
        chain = self._chain(token_ids) if token_ids else []
        adapter = kw.get("lora") or ""
        routable = getattr(self._server.generate, "_get_router", None)
        if (not chain and not adapter) or routable is None:
            self._routing["untracked"] += 1
            stream = self._server.options(stream=True).generate_stream.remote(
                prompt, **kw
            )
            try:
                async for delta in stream:
                    yield delta
            finally:
                stream.close()
            return
        replica, router, mode, holder = self._pick(chain, adapter)
        if (holder is not None and token_ids is not None
                and self._remote_fetch_enabled()):
            if await self._remote_fetch(holder, replica, token_ids, adapter):
                mode = "remote_fetch"
                self._routing["remote_fetched"] += 1
            else:
                self._routing["remote_fetch_failed"] += 1
        if mode != "remote_fetch":
            self._routing[mode] += 1
        self._record(replica._actor_id, chain, adapter)
        if chain and token_ids is not None:
            self._note_hot_prefix(chain, token_ids, adapter)
        kw = dict(kw)
        kw.setdefault("route", mode)
        args = (token_ids,) if token_ids is not None else (prompt,)
        # Stream from the SPECIFIC routed replica with the handle's exact
        # cancel plane (token + cancel_stream thunk) and load bookkeeping.
        import uuid

        from ray_tpu.serve._replica import STREAM_CANCEL_KWARG
        from ray_tpu.serve.handle import DeploymentResponseGenerator

        cancel_token = uuid.uuid4().hex
        ref_gen = replica.handle_request_streaming.options(
            num_returns="streaming"
        ).remote("generate_stream", args,
                 {**kw, STREAM_CANCEL_KWARG: cancel_token})

        def cancel():
            replica.cancel_stream.remote(cancel_token)  # raylint: disable=RL501 (fire-and-forget cancel; the stream's own finish is the observable)

        gen = DeploymentResponseGenerator(
            ref_gen, on_done=lambda: router.done(replica), cancel=cancel
        )
        try:
            async for delta in gen:
                yield delta
        finally:
            gen.close()

    async def ranks(self) -> dict:
        return await asyncio.get_running_loop().run_in_executor(
            None, lambda: ray_tpu.get(self._assigner.ranks.remote())
        )

    # -- autopilot hooks (docs/autoscale.md) --------------------------------
    async def retire_replica(self, actor_id) -> dict:
        """Explicit scale-down prune: the serve controller calls this
        BEFORE retiring a replica so its prefix fingerprints and
        adapter-residency entries leave the routing tables while the actor
        is still alive — without it, cache-affine traffic keeps chasing the
        corpse until the lazy dead-replica pruning notices on a later pick."""
        hexid = actor_id.hex() if hasattr(actor_id, "hex") else str(actor_id)

        def _hex(aid):
            return aid.hex() if hasattr(aid, "hex") else str(aid)

        fingerprints = adapters = 0
        for aid in [a for a in self._fingerprints if _hex(a) == hexid]:
            fingerprints += len(self._fingerprints.pop(aid))
        for aid in [a for a in self._adapter_res if _hex(a) == hexid]:
            adapters += len(self._adapter_res.pop(aid))
        self._bootstrapped = {
            a for a in self._bootstrapped if _hex(a) != hexid
        }
        self._routing["retired_pruned"] += 1
        return {"fingerprints": fingerprints, "adapters": adapters}

    async def bootstrap_replica(self, replica) -> int:
        """Prefix-fingerprint bootstrap for a fresh replica: pull the
        hottest remembered prefixes from their best current holders into
        `replica`'s cache over the cluster prefix plane, so an
        autopilot-spawned replica serves its first requests suffix-only.
        Best-effort: a failed fetch is a recompute, never an error."""
        if not self._remote_fetch_enabled():
            return 0
        top = sorted(self._hot_prefixes.items(),
                     key=lambda kv: -kv[1]["hits"])[:self.BOOTSTRAP_TOP_K]
        fetched = 0
        for chain_key, info in top:
            chain = list(chain_key)
            router = self._server.generate._get_router()
            best, best_len = None, 0
            for r in router.replicas():
                if r._actor_id == replica._actor_id:
                    continue
                m = self._match_len(r._actor_id, chain)
                if m > best_len:
                    best, best_len = r, m
            if best is None:
                continue
            if await self._remote_fetch(best, replica, info["token_ids"],
                                        info["adapter"]):
                self._record(replica._actor_id, chain[:best_len],
                             info["adapter"])
                self._routing["bootstrap_fetched"] += 1
                fetched += 1
            else:
                self._routing["bootstrap_failed"] += 1
        return fetched

    async def set_tenant_weight(self, tenant: str, weight: float) -> float:
        """Fan one tenant's adapted WFQ weight out to every DP rank (the
        autopilot's weight broadcasts also reach the DPLLMServer replicas
        directly; this is the operator/API path)."""
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None,
            lambda: self._server.set_tenant_weight.broadcast(tenant, weight),
        )
        return float(weight)

    async def load_lora(self, name: str, layer_weights: dict,
                        alpha: float = 1.0) -> List[int]:
        """Register an adapter on EVERY replica (the fleet-wide registry:
        registration is host-side and cheap — docs/multitenancy.md — so
        broadcasting keeps any replica able to serve any tenant, paging the
        weights in only where traffic actually lands)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None,
            lambda: self._server.load_lora.broadcast(name, layer_weights, alpha),
        )

    async def autopilot_signals(self) -> dict:
        """Autopilot probe for the router deployment itself. The router does
        no engine work — queued/running stay 0 so it can never trigger
        replica scaling — but it must answer the probe because it answers
        set_tenant_weight: the autopilot's sticky managed set pairs the two
        (signal ⇒ weight broadcasts), and raylint RL1003 pins the pairing."""
        return {
            "role": "dp_router",
            "queued": 0,
            "running": 0,
            "tracked_replicas": len(self._fingerprints),
            "cache_routed": self._routing["cache_routed"],
            "balanced": self._routing["balanced"],
        }

    async def routing_stats(self) -> dict:
        """Cache-aware + adapter-aware routing counters, fingerprint and
        residency footprints."""
        return {
            **self._routing,
            "tracked_replicas": len(self._fingerprints),
            "fingerprints": sum(len(v) for v in self._fingerprints.values()),
            "adapter_residency": {
                str(aid): list(res) for aid, res in self._adapter_res.items()
            },
        }

    async def cache_stats(self) -> List[dict]:
        """Rank-tagged engine prefix-cache stats from EVERY replica (the
        router-level view of where prefixes actually live)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._server.cache_stats.broadcast()
        )

    async def scheduler_stats(self) -> List[dict]:
        """Rank-tagged scheduler occupancy + spec acceptance from EVERY
        replica: the fleet-level view of prefill/decode/verify interleaving
        (docs/scheduler.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._server.scheduler_stats.broadcast()
        )

    async def adapter_stats(self) -> List[dict]:
        """Rank-tagged AdapterCache stats from EVERY replica: the ground
        truth behind the router's optimistic residency map
        (docs/multitenancy.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._server.adapter_stats.broadcast()
        )

    async def recorder_stats(self) -> List[dict]:
        """Rank-tagged flight-recorder stats from EVERY replica; the
        broadcast is the fleet-wide report path that flushes each rank's
        pending SLO metrics and trace spans (docs/observability.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._server.recorder_stats.broadcast()
        )

    async def capture_profile(self, duration_s: float = 3.0) -> List[dict]:
        """Fan a profiler capture out to EVERY replica and gather the
        rank-tagged trace artifacts (docs/observability.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self._server.capture_profile.broadcast(duration_s)
        )

    async def __call__(self, request) -> dict:
        body = request.json() if hasattr(request, "json") else dict(request)
        if inspect.isawaitable(body):  # ASGI-style request objects
            body = await body
        model = body.get("model", "")
        lora = model.split(":", 1)[1] if ":" in model else ""
        stop = body.get("stop_token_id")
        return await self.generate(
            body.get("prompt", ""),
            max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            stop_token_id=None if stop is None else int(stop),
            lora=lora,
        )


def build_dp_openai_app(config: LLMConfig, *, dp_size: int = 2):
    """A data-parallel serving app: dp_size engine replicas + rank assigner
    behind one cache-aware router (parity: build_dp_openai_app / DPServer).

    DP x TP composition (docs/serving_tp.md): with `config.tp > 1` every
    replica is itself a mesh-sharded engine, and its per-replica accelerator
    demand scales by the TP device count so the scheduler reserves each
    replica's whole device gang atomically (cross-host gangs reserve through
    `cluster_utils.reserve_tp_slice` placement groups)."""
    from ray_tpu import serve
    from ray_tpu.llm import replica_actor_options

    assigner = ray_tpu.remote(num_cpus=0)(DPRankAssigner).options(
        name=f"DPRankAssigner-{config.model_id}", get_if_exists=True,
        namespace="llm_dp",
    ).remote(dp_size)
    ray_tpu.get(assigner.ensure_size.remote(dp_size))
    server = serve.deployment(
        name=f"DPLLMServer-{config.model_id}",
        num_replicas=dp_size,
        ray_actor_options=replica_actor_options(config),
        max_ongoing_requests=config.num_slots * 4,
    )(DPLLMServer).bind(config, assigner)
    router = serve.deployment(name=f"DPRouter-{config.model_id}")(DPRouter)
    return router.bind(server, assigner, config)


__all__ = ["DPRankAssigner", "DPLLMServer", "DPRouter", "build_dp_openai_app"]
