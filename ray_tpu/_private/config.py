"""Central flag table, env-var overridable.

Design parity: reference `src/ray/common/ray_config_def.h` (RAY_CONFIG(type, name, default)
table, 226 entries, each overridable by a `RAY_<name>` env var) compiled into a `RayConfig`
singleton (`ray_config.h:60`). Here the table is a plain dict of typed defaults; every entry
is overridable via `RAY_TPU_<NAME>` environment variables, resolved once at first access.
"""

from __future__ import annotations

import json
import os
from typing import Any

_ENV_PREFIX = "RAY_TPU_"

# name -> (type, default, doc)
_DEFS: dict[str, tuple[type, Any, str]] = {
    # --- core runtime ---
    "max_direct_call_object_size": (int, 100 * 1024, "objects <= this many bytes are returned inline through the owner's memory store instead of the shared-memory store"),
    "max_task_retries_default": (int, 3, "default max_retries for remote functions"),
    "max_object_reconstructions": (int, 3, "how many times a lost plasma object may be rebuilt by re-running its producing task (0 disables lineage reconstruction)"),
    "max_lineage_entries": (int, 10000, "max owned objects whose producing task spec is retained for reconstruction; oldest entries are evicted first"),
    "worker_register_timeout_s": (float, 60.0, "how long the raylet waits for a spawned worker to register (covers slow interpreter+jax imports on loaded hosts)"),
    "idle_worker_kill_s": (float, 300.0, "kill idle workers after this many seconds"),
    "get_poll_interval_s": (float, 0.002, "poll interval for blocking gets"),
    "heartbeat_interval_s": (float, 1.0, "raylet -> GCS resource/health report interval"),
    "node_death_timeout_s": (float, 30.0, "GCS marks a node dead after missing heartbeats for this long; must outlast a host-wide stall (starting the TPU runtime freezes a v5e host for about 8 s); a raylet that dies is seen at once by its closed connection"),
    "object_store_memory_fraction": (float, 0.3, "fraction of system memory for the per-node shared-memory object store"),
    "store_pretouch_bytes": (int, 1 << 30, "fault in this much of the shm arena at store startup so first puts run at warm-page speed (0 disables)"),
    "object_report_flush_s": (float, 0.02, "raylet batching window for GCS object-directory reports/frees"),
    "pull_chunk_window": (int, 8, "pipelined in-flight chunk requests per remote object pull"),
    "pull_budget_bytes": (int, 1 << 30, "cap on total bytes of concurrently in-flight remote pulls (backpressure)"),
    "object_store_min_chunk_bytes": (int, 1024 * 1024, "chunk size for node-to-node object transfer"),
    # --- memory / OOM defense ---
    "memory_monitor_refresh_ms": (int, 250, "node memory poll interval for the OOM monitor; 0 disables worker killing (reference: memory_monitor_refresh_ms)"),
    "memory_usage_threshold": (float, 0.95, "kill workers when node memory usage crosses this fraction (reference: memory_usage_threshold)"),
    "memory_monitor_min_wait_s": (float, 1.0, "usage must stay above threshold this long before a kill (debounce against transient spikes)"),
    "meminfo_path": (str, "/proc/meminfo", "meminfo source; tests point this at a fake file to simulate pressure"),
    # --- scheduling ---
    "lease_worker_slots": (int, 32, "tasks the owner pipelines ahead per leased worker (execution stays sequential at the worker); deep pipelines coalesce submit bursts into few large frames"),
    "lease_pipeline_min_depth": (int, 2, "starting per-worker pipeline depth for the lease fast path; lease denials ramp it toward lease_worker_slots"),
    "borrow_audit_interval_s": (float, 30.0, "how often owners audit registered borrowers for liveness (crashed borrowers are reconciled)"),
    "borrow_audit_strikes": (int, 3, "consecutive not-held audit verdicts before a live borrower's lost-release entry is reconciled away"),
    "borrow_audit_min_age_s": (float, 2.0, "minimum wall-clock age of a not-held entry before reconciliation (protects slow in-flight handoffs)"),
    "test_delay_borrow_report_ms": (int, 0, "fault injection: delay legacy borrow-report notifies by this long (stress the sequenced protocol)"),
    # --- logging / observability ---
    "event_buffer_size": (int, 10000, "per-worker task event buffer entries"),
    "metrics_report_interval_s": (float, 5.0, "metrics push interval"),
    "gcs_max_task_events": (int, 100000, "task events retained by the GCS before the oldest half is dropped (reference: task_events_max_num_task_in_gcs)"),
    "export_events_dir": (str, "", "when set, the GCS appends structured JSONL export events (tasks/actors/nodes/placement groups) under this directory (reference: export_*.proto + ray_event_recorder)"),
    "gcs_export_queue_size": (int, 1024, "bounded queue between the GCS loop and the export-event writer thread; overflow sheds oldest batches"),
    "gcs_store_fsync_window_s": (float, 0.01, "group-commit window: one fsync covers every GCS store append in the window (RAY_TPU_GCS_STORE_FSYNC picks the mode: always|group|off)"),
    "gcs_store_compact_threshold": (int, 50000, "rewrite the GCS append log once it holds this many records"),
    "gcs_rpc_timeout_s": (float, 30.0, "total deadline for one GCS request across reconnect retries (exponential backoff + jitter); the control plane may restart under live clients, so this bounds how long a call rides through the outage before surfacing ConnectionLost"),
    "gcs_replicas": (int, 1, "GCS head candidates: 1 = the classic single process (restart-recovery only), 3+ = lease-based quorum HA — the primary majority-acks every durable mutation to follower candidates and a follower promotes itself when the primary's lease lapses (docs/fault_tolerance.md)"),
    "gcs_lease_s": (float, 2.0, "primary lease window: the primary renews through the quorum at a third of this period and stops serving when it cannot confirm a majority within it; followers start an election after this much primary silence, so failover lands within ~2x the window"),
    "gcs_quorum_timeout_s": (float, 5.0, "how long a primary waits for a majority of candidates to ack a replicated mutation before demoting itself and failing the call back to the client (who retries against the new primary)"),
    "log_dedup_window_s": (float, 5.0, "repeat window for driver-side worker-log deduplication summaries"),
    "post_mortem": (bool, False, "park failing tasks at the raising frame for `ray_tpu debug` (reference: RAY_DEBUG_POST_MORTEM)"),
    "post_mortem_wait_s": (float, 120.0, "how long a parked task waits for a debugger before its error propagates"),
    "post_mortem_external": (bool, False, "bind the post-mortem pdb server on all interfaces instead of loopback; the socket is an UNAUTHENTICATED interactive interpreter — only enable inside a trusted network boundary (reference: ray debugger_external)"),
    # --- channels / client ---
    "channel_poll_min_s": (float, 0.0005, "cross-node channel long-poll floor: a hot pipeline sees sub-ms latency"),
    "channel_poll_max_s": (float, 0.01, "cross-node channel long-poll backoff ceiling for idle rings"),
    "channel_default_slots": (int, 4, "in-flight values a compiled-graph channel ring holds by default"),
    "channel_tensor_min_bytes": (int, 1024, "array leaves at least this large ride the channel tensor fast path (raw-buffer frame, no cloudpickle of array data; docs/device_channels.md); -1 disables the fast path"),
    "channel_reconnect_s": (float, 5.0, "RpcChannel readers ride transient writer-connection failures (RpcError/OSError) with backoff+jitter for this long before declaring the writer dead (ChannelClosed); dead sockets are evicted from the per-process conn cache so a restarted writer gets a fresh dial"),
    "llm_channel_chunk_bytes": (int, 1 << 20, "chunk size for DeviceChannel staged transfers (PD KV handoff, device_objects.get/transfer): device->host, wire, and host->device legs pipeline at this granularity through a small ring instead of one blocking full-tensor copy (docs/device_channels.md)"),
    "devobj_stream_slots": (int, 4, "ring depth, in chunks, of device-object transfer streams; depth > 1 is what lets the D2H / wire / H2D legs overlap"),
    "devobj_stream_min_bytes": (int, 8 << 20, "device-object fetches at least this large ride the chunked DeviceChannel stream; smaller payloads take the one-hop object-plane blob, whose fixed cost is lower than a stream setup (docs/device_channels.md)"),
    "dag_buffer_size_bytes": (int, 8 << 20, "per-edge channel slot capacity for compiled DAGs (reference: buffer_size_bytes)"),
    "dag_max_inflight_executions": (int, 10, "default bound on in-flight compiled-DAG executions (reference: RAY_CGRAPH_max_inflight_executions)"),
    "dag_execute_timeout_s": (float, 60.0, "compiled-DAG submission/read timeout"),
    "client_proxy_node_cache_s": (float, 5.0, "client proxy's cache TTL for the cluster's registered-endpoint allowlist"),
    # --- train / libraries ---
    "train_ckpt_async": (bool, True, "sharded checkpoints persist on a background writer thread; the step loop pays only one batched device->host snapshot per save (0 = write+commit inline, docs/checkpoint.md)"),
    "train_ckpt_inflight": (int, 2, "bounded in-flight async checkpoint saves per process; a save past the budget backpressures the step loop instead of growing host memory with unpersisted snapshots"),
    "train_ckpt_commit_timeout_s": (float, 120.0, "how long the committing rank waits for every process's shard spec before abandoning the commit (the directory stays manifest-less, i.e. garbage)"),
    "train_flight_records": (int, 64, "per-step flight records kept in each train worker's recorder ring (docs/observability.md): data-wait/step-compute/report-blocked/checkpoint-blocked phase attribution per report(), exported only from train_stats()/Result (0 disables)"),
    "serve_http_port": (int, 8000, "default HTTP port each node's serve proxy binds (reference: serve DEFAULT_HTTP_PORT)"),
    "serve_handle_max_retries": (int, 3, "deployment-handle resubmissions after replica death before the call fails"),
    "serve_control_loop_interval_s": (float, 0.25, "serve controller reconcile interval"),
    "serve_router_cache_ttl_s": (float, 2.0, "deployment-handle routing-table refresh TTL (scale-ups become visible to existing handles within this window)"),
    "llm_multi_step": (int, 8, "decode tokens per engine dispatch (on-device chunks of steps, each drawing its own token; 1 disables)"),
    "llm_prefill_bucket_min": (int, 16, "smallest prompt padding bucket for compiled prefill programs"),
    "llm_kv_block_size": (int, 16, "token rows per paged KV prefix-cache block; prefixes are reused at whole-block granularity (docs/kvcache.md)"),
    "llm_prefix_cache_bytes": (int, 32 << 20, "host bytes for the per-engine paged KV prefix cache; repeated prompt prefixes attach cached KV and prefill suffix-only (0 disables)"),
    "llm_kv_device_bytes": (int, 0, "device-resident hot-tier byte budget of the tiered prefix cache (docs/kvcache.md): the hottest blocks keep a device copy (mesh-sharded on TP engines) so warm attaches skip the host->device leg entirely; LRU device copies drop back to the host tier past the budget (0 disables the hot tier)"),
    "llm_kv_spill_dir": (str, "", "local directory for the disk spill tier of the tiered prefix cache (docs/kvcache.md): host-tier eviction spills blocks here (atomic tmp+fsync+rename commits — torn spills are invisible) instead of discarding them, and later lookups promote spilled chains back through the host pool (empty disables spilling)"),
    "llm_kv_spill_bytes": (int, 256 << 20, "byte cap on the disk spill tier; the oldest committed spill files are unlinked past it (0 = unbounded)"),
    "llm_kv_remote_fetch": (bool, True, "cluster-wide prefix plane (docs/kvcache.md): when the DP router's fingerprints say another replica computed a request's prefix but the request must route elsewhere, the chosen replica fetches the prefix cross-node over a DeviceChannel stream instead of recomputing it"),
    "llm_max_queue_depth": (int, 256, "engine admission queue cap; submits beyond it raise EngineOverloadedError instead of growing memory unboundedly (0 = unbounded)"),
    "llm_max_jit_programs": (int, 64, "per-engine cap on cached jitted programs (prefill/attach/spec bucket variants); past it the oldest program is evicted so an adversarial prompt-length mix can't grow compilation memory unboundedly (0 = unbounded)"),
    "llm_router_fingerprint_blocks": (int, 8, "prefix blocks hashed into the DP router's per-replica fingerprints for cache-aware routing"),
    "llm_sched_token_budget": (int, 256, "per-iteration scheduler token budget (docs/scheduler.md): decode and spec-verify tokens are reserved first, the remainder is granted to bucketed prefill chunks, so a long prefill cannot stall in-flight decodes for more than one budget of compute (0 = unbudgeted whole-prompt prefill)"),
    "llm_spec_ngram": (int, 3, "trailing n-gram length the ngram/REST speculative draft matches against the slot history and the cross-request continuation store"),
    "llm_spec_store_entries": (int, 4096, "bounded LRU entries in the ngram draft's cross-request continuation store; repeated greedy traffic re-proposes earlier completions from it (0 disables the shared store, leaving prompt-lookup only)"),
    "llm_adapter_cache_bytes": (int, 0, "HBM byte budget for the engine's pageable LoRA adapter table (docs/multitenancy.md): device slots = budget // per-adapter slot bytes, registered-but-evicted adapters stay host-side and page back in on demand (one device_put per page-in, LRU eviction of unpinned adapters); 0 sizes the table to lora_config max_loras (every registered adapter resident, the pre-paging shape)"),
    "llm_tenant_max_queue_depth": (int, 64, "per-tenant admission quota on the engine's weighted-fair queues: one tenant's overload raises EngineOverloadedError for THAT tenant while other tenants keep flowing (0 disables the per-tenant quota, leaving only the global llm_max_queue_depth cap)"),
    "llm_flight_records": (int, 256, "finished request records kept in each engine's flight-recorder ring (docs/observability.md): per-request phase events (queue/prefill-chunk/verify/decode/adapter/PD) recorded host-side off the dispatch path, flushed to metrics and trace spans only from stats()/report paths (0 disables the recorder)"),
    "llm_slo_ttft_s": (float, 0.5, "time-to-first-token SLO: completions whose TTFT exceeds this count as SLO breaches in the llm_slo_* burn/goodput counters (docs/observability.md)"),
    "llm_slo_tpot_s": (float, 0.05, "per-request mean inter-token-latency SLO: completions whose mean TPOT exceeds this count as SLO breaches (docs/observability.md)"),
    "llm_slo_error_budget": (float, 0.01, "allowed SLO breach fraction: llm_slo_burn_rate = windowed breach fraction / this budget, so burn > 1 means the error budget is being exhausted"),
    "llm_guided_max_states": (int, 4096, "DFA state cap for guided-decoding constraint compilation (docs/generation.md): a regex/schema/grammar whose subset construction exceeds this raises at compile time instead of growing compile memory unboundedly"),
    "llm_guided_max_depth": (int, 8, "bounded-recursion inlining rounds for grammar constraints: a <rule> reference surviving this many substitution rounds is unbounded CFG recursion and fails compilation (it cannot lower to a finite token-mask DFA)"),
    "llm_guided_cache_entries": (int, 32, "compiled-constraint LRU entries per server/tokenizer (docs/generation.md): repeated guided requests against the same schema skip DFA construction and reuse the cached per-state token masks"),
    "llm_stream_buffer_tokens": (int, 4096, "undelivered buffered tokens a TokenStream holds before cancelling its own request (docs/generation.md): a stalled streaming consumer sheds the slot instead of growing host memory without bound (0 disables the guard)"),
    "llm_batch_tenant": (str, "batch", "the WFQ tenant name offline batch traffic (data/llm.py EngineStage) is admitted under on live serve replicas (docs/generation.md): this tenant is pinned to llm_batch_weight and excluded from autopilot SLO signals, so online traffic always preempts batch and batch pressure never scales the fleet"),
    "llm_batch_weight": (float, 1e-6, "the floor WFQ weight pinned on the llm_batch_tenant queues: batch admissions take enormous stride-pass steps, so they only drain when no online tenant has queued work (set_tenant_weight cannot raise it — the floor is structural)"),
    "llm_batch_max_inflight": (int, 16, "bounded in-flight window for EngineStage batch submission: at most this many rows ride the engine/serve queues concurrently, so one batch block cannot flood an online replica's admission queue (0 = submit the whole block up front)"),
    # --- serve autopilot (docs/autoscale.md) ---
    "serve_autopilot": (bool, False, "closed-loop SLO autopilot inside the serve controller: scales DP replicas on burn-rate/queue pressure, nudges per-tenant WFQ weights toward SLO attainment, and rebalances the prefill:decode split (docs/autoscale.md)"),
    "serve_autopilot_interval_s": (float, 1.0, "autopilot control-law evaluation interval; signals are probed and laws evaluated at most this often inside the controller's control loop"),
    "serve_autopilot_min_replicas": (int, 1, "default replica floor for autopilot-managed deployments without an AutoscalingConfig (0 enables scale-to-zero; a deployment's own AutoscalingConfig bounds win when set)"),
    "serve_autopilot_max_replicas": (int, 8, "default replica ceiling for autopilot-managed deployments without an AutoscalingConfig"),
    "serve_autopilot_burn_high": (float, 1.0, "scale-up pressure threshold on llm_slo_burn_rate: burn >= this (budget exhausting) counts a hot tick"),
    "serve_autopilot_queue_high": (float, 8.0, "scale-up pressure threshold on mean queued requests per replica: queue/replica >= this counts a hot tick even when burn is still low (queue growth leads breach by a window)"),
    "serve_autopilot_sustain_ticks": (int, 2, "consecutive autopilot ticks a pressure (or idle) condition must hold before any action fires — the hysteresis that keeps a one-tick spike from scaling"),
    "serve_autopilot_upscale_cooldown_s": (float, 5.0, "minimum seconds between scale-up actions on one deployment (persisted: a restarted controller honors the remaining cooldown instead of flapping)"),
    "serve_autopilot_downscale_cooldown_s": (float, 30.0, "minimum seconds between scale-down actions on one deployment; deliberately long so capacity added for a surge is not shed on the first quiet window"),
    "serve_autopilot_cold_start_guard_s": (float, 60.0, "after a scale-to-zero wake (first request found zero replicas), the deployment may not scale back to zero for this long — the cold-start guard against wake/retire thrash"),
    "serve_autopilot_weight_step": (float, 0.25, "max fractional change to one tenant's WFQ weight per autopilot action (bounded step: weight moves by at most this fraction per decision)"),
    "serve_autopilot_weight_floor": (float, 0.25, "WFQ weight floor no tenant is nudged below — the starvation guard: a compliant tenant keeps at least this share-weight while a breaching tenant is boosted"),
    "serve_autopilot_weight_max": (float, 8.0, "WFQ weight ceiling the autopilot will not boost a breaching tenant past"),
    "serve_autopilot_weight_deadband": (float, 0.25, "burn-rate deadband around 1.0 inside which tenant weights are left alone (attainment hysteresis: only clearly-breaching or clearly-healthy tenants move)"),
    "serve_autopilot_pd_ratio_tol": (float, 2.0, "prefill:decode rebalance trigger: when TTFT pressure exceeds TPOT pressure by this factor (or vice versa), one replica shifts between the prefill and decode pools"),
    "serve_autopilot_decision_log": (int, 256, "bounded entries in the autopilot decision log surfaced through serve_stats()/`ray_tpu status` (rule fired, signal values, action taken)"),
    "metrics_series_ttl_s": (float, 300.0, "collect-time TTL for cluster metric series: entries whose reporting worker is gone (not the driver, no live actor) AND whose last flush is older than this are pruned from the GCS KV metrics namespace instead of living forever"),
    "tune_checkpoint_period_s": (float, 1.0, "experiment-state snapshot interval for Tuner.restore"),
    "data_block_target_bytes": (int, 128 * 1024 * 1024, "target block size for ray_tpu.data"),
    "data_output_queue_size": (int, 8, "blocks buffered between the streaming executor and the consuming iterator (backpressure depth)"),
    "data_max_inflight_factor": (int, 2, "per-operator in-flight task cap as a multiple of its actor/worker pool size"),
    "tune_trial_poll_timeout_s": (float, 60.0, "driver-side timeout for polling a trial actor's buffered results"),
}


_MISSING = object()


def _unknown_flag_message(name: str) -> str:
    """KeyError text for a flag absent from _DEFS, with a did-you-mean
    suggestion so a typo'd read points straight at the intended flag
    instead of silently running on a default (raylint RL1004 catches the
    static cases; this is the runtime complement)."""
    import difflib

    close = difflib.get_close_matches(name, list(_DEFS), n=1)
    hint = f" — did you mean {close[0]!r}?" if close else ""
    return f"unknown config flag {name!r}{hint}"


class _Config:
    """Singleton flag table with env overrides (RAY_TPU_<NAME>=value)."""

    def __init__(self):
        self._cache: dict[str, Any] = {}

    def __getattr__(self, name: str):
        if name.startswith("_"):
            # Dunder/underscore probes (hasattr, copy, pickle protocols)
            # must keep raising AttributeError, never KeyError.
            raise AttributeError(name)
        cache = self.__dict__["_cache"]
        if name in cache:
            return cache[name]
        if name not in _DEFS:
            raise KeyError(_unknown_flag_message(name))
        typ, default, _doc = _DEFS[name]
        raw = os.environ.get(_ENV_PREFIX + name.upper())
        if raw is None:
            value = default
        elif typ is bool:
            value = raw.lower() in ("1", "true", "yes", "on")
        elif typ in (dict, list):
            value = json.loads(raw)
        else:
            value = typ(raw)
        cache[name] = value
        return value

    def get(self, name: str, default: Any = _MISSING):
        """Dynamic read with the same typo defense as attribute access:
        unknown flags raise KeyError with a did-you-mean suggestion unless
        an explicit default is supplied."""
        if name in _DEFS:
            return getattr(self, name)
        if default is not _MISSING:
            return default
        raise KeyError(_unknown_flag_message(name))

    def _reset(self):
        self.__dict__["_cache"] = {}

    def _all(self) -> dict[str, Any]:
        return {name: getattr(self, name) for name in _DEFS}


CONFIG = _Config()


_LOOPBACK = ("127.0.0.1", "localhost", "::1", "0.0.0.0")


def get_node_ip(probe_host: str | None = None) -> str:
    """The IP this node should advertise to cluster peers.

    Resolution order (reference: `python/ray/_private/services.py`
    get_node_ip_address — UDP-connect trick, env overridable):
    1. `RAY_TPU_NODE_IP` env var, set by the autoscaler startup script or the
       operator on multi-host deployments.
    2. If the GCS (or any probe host) is non-loopback, the source IP the kernel
       picks to reach it — the interface actually routable from the cluster.
    3. Loopback, for single-host clusters and tests.
    """
    ip = os.environ.get(_ENV_PREFIX + "NODE_IP")
    if ip:
        return ip
    if probe_host and probe_host not in _LOOPBACK:
        import socket

        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                s.connect((probe_host, 80))
                return s.getsockname()[0]
            finally:
                s.close()
        except OSError:
            # Registering loopback on a multi-host cluster makes every peer
            # dial itself for this node — degrade loudly, not silently.
            import logging

            logging.getLogger("ray_tpu").warning(
                "could not determine a routable node IP (probe host %s); "
                "falling back to 127.0.0.1 — set RAY_TPU_NODE_IP on "
                "multi-host clusters", probe_host,
            )
    return "127.0.0.1"


def bind_host_for(node_ip: str) -> str:
    """Listen host for a server whose address is advertised as `node_ip`.

    Loopback nodes stay loopback-only. Routable nodes listen on all interfaces
    rather than `node_ip` alone: local peers (workers, drivers, the raylet's
    own GCS connection) dial 127.0.0.1 while remote peers dial the advertised
    IP, and both must reach the same socket. The RPC plane is unauthenticated —
    same trust model as the reference's gRPC servers, which also listen
    beyond loopback inside the cluster's network boundary."""
    return "127.0.0.1" if node_ip in _LOOPBACK else "0.0.0.0"
