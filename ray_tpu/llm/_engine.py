"""TPU decode engine: continuous-batching generation over transformer weights.

Design parity: reference `python/ray/llm/_internal/serve/deployments/llm/vllm/` —
the role vLLM's AsyncLLM plays behind Ray Serve (slot-based continuous batching,
prefill + steady-state decode). Rebuilt TPU-first instead of wrapping a CUDA
engine: static-shaped jitted prefill (per length bucket) and a single jitted
decode step over B fixed slots with per-slot KV caches and length masks — no
dynamic shapes anywhere, so XLA compiles exactly two core programs and the MXU
stays on the batched matmul path. What the programs compute is the model
block's: a module of pure functions over its parameter tree, looked up by
`ModelConfig.block` (`ray_tpu/models/__init__.py`; `models/llama.py` is the
dense block over the flax Transformer's tree in its scan_layers=False layout).
This module holds no model code and knows no block by name.

Control plane: the engine no longer schedules itself. An iteration-level
`Scheduler` (`ray_tpu/llm/scheduler/`, docs/scheduler.md) owns the
waiting/running queues and assembles every stepper iteration — bucketed
prefill CHUNKS interleaved with batched decode and speculative-verify phases
under a token budget — while this module owns the compiled programs and
device state the plans execute against. Every chunk shape is drawn from the
same static `_prefill_buckets` table whole-prompt prefill uses, so chunked
prefill adds ZERO new compiled programs.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.adapters import (
    AdapterCache,
    UnknownAdapterError,
)
from ray_tpu.llm.flight_recorder import FlightRecorder, ServeMetrics
from ray_tpu.llm.scheduler.scheduler import (
    EngineOverloadedError,
    Plan,
    Request,
    ScheduledChunk,
    Scheduler,
    _host_drawn,
)
from ray_tpu.llm.tp import (
    ShardedKVPool,
    build_tp_mesh,
    checkpoint_shardings,
    kv_prefix_sharding,
    mesh_signature,
    replicated,
    shard_decode_params,
    single_device_shardings,
    tp_degree,
)
from ray_tpu import models
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.util import xprof
from ray_tpu.util.xprof import named

_NEG_INF = -1e30

# Prefix-cache inserts that may be in flight at once (docs/kvcache.md): each
# holds one gathered [L, 2, bucket, Hkv, D] device buffer until its copy to the
# host has landed. A further insert first finishes the oldest.
_MAX_PENDING_KV_INSERTS = 2


class _PendingKVInsert(NamedTuple):
    """An admitted prompt's whole blocks on their way to the prefix cache:
    `kv` is the gather program's output, its copy to the host already started."""

    tokens: List[int]
    namespace: int
    kv: jax.Array  # [L, 2, bucket >= len(tokens), Hkv, D]
    rid: str


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0            # 0 = no top-k filter
    stop_token_id: Optional[int] = None


def _rid(req: Request) -> str:
    """The id a request's `rt.engine.*` spans carry: its flight record's, so
    that a profiler trace and `request_timing()` name the request alike."""
    return req.rec.rid if req.rec is not None else (req.rid or "")


def _sample_host(logits_row: np.ndarray, sampling: SamplingParams,
                 rng: np.random.Generator) -> int:
    """One row drawn on the host: a request's first token (one pull a prefill),
    and in a decode round the rows `_host_drawn` names."""
    if sampling.temperature <= 0:
        return int(np.argmax(logits_row))
    scaled = logits_row / sampling.temperature
    if sampling.top_k > 0:
        thresh = np.sort(scaled)[-sampling.top_k]
        scaled = np.where(scaled < thresh, _NEG_INF, scaled)
    scaled = scaled - scaled.max()
    probs = np.exp(scaled)
    probs /= probs.sum()
    return int(rng.choice(len(probs), p=probs))


def _any_hot(temps, gate):
    """Whether a round draws: one of its stepping rows has a temperature."""
    return jnp.any((temps > 0) & gate)


def _sample_device(logits, temps, gate, key):
    """One token a slot from `[B, V]` float32 logits, in the single-step program: the
    row's first maximum at temperature 0 (what `np.argmax` of the pulled row
    gives), and at T > 0 a draw from `softmax(logits / T)` by Gumbel-max, one
    pass over the row and no sort. Returns (tokens [B] int32, the key to carry).
    A round none of whose stepping rows has a temperature takes the other branch
    of the `cond`: no noise is generated and the key stands. The draw goes a row
    at a time, in a scan: as one `[B, V]` fusion it costs a third of that (42 us
    for 142 at 12 x 92544), but with it in the program the TPU's compiler starts
    every layer's prefetch of its norm scales too late to hide, in either branch,
    and the dense block's step is 0.3 ms longer (PERF.md §6, PR 37)."""
    hot = temps > 0

    def draw(key):
        key, sub = jax.random.split(key)

        def row(_, x):
            row_logits, temp, row_hot, row_key = x
            noise = jax.random.gumbel(row_key, row_logits.shape, row_logits.dtype)
            scaled = row_logits / jnp.where(row_hot, temp, 1.0) + noise
            return None, jnp.argmax(jnp.where(row_hot, scaled, row_logits))

        _, tokens = jax.lax.scan(
            row, None, (logits, temps, hot, jax.random.split(sub, logits.shape[0])))
        return tokens, key

    tokens, key = jax.lax.cond(
        _any_hot(temps, gate), draw, lambda key: (jnp.argmax(logits, axis=-1), key), key)
    return tokens.astype(jnp.int32), key


def _sample_device_flat(logits, temps, gate, key):
    """`_sample_device`'s tokens and key, token for token, with no control flow: the
    form the multi-step programs' loop body takes. The same noise (one key a row,
    from one split of the key) is generated for every row of every step and used on
    the rows at a temperature; the key moves only in a round that draws. Inside the
    loop's body a `cond`, or a loop over the rows, cost the dense block's step 0.55
    to 0.95 ms on the chip in either branch (the TPU's compiler prefetches fewer of
    a layer's kernels past nested control flow); this form costs a greedy step the
    noise it throws away (PERF.md §6, PR 44)."""
    hot = temps > 0
    new, sub = jax.random.split(key)
    noise = jax.vmap(lambda k: jax.random.gumbel(k, logits.shape[1:], logits.dtype))(
        jax.random.split(sub, logits.shape[0]))
    scaled = logits / jnp.where(hot, temps, 1.0)[:, None] + noise
    tokens = jnp.argmax(jnp.where(hot[:, None], scaled, logits), axis=-1)
    return tokens.astype(jnp.int32), jnp.where(_any_hot(temps, gate), new, key)


def _traced_on(mesh):
    """What a program's body traces its block's call under: the TP engine's mesh, so that
    the block can see it (`models/llama.py` runs its attention kernel inside a `shard_map`
    over it; everything else is GSPMD's, from the arguments' shardings), and nothing on one
    device."""
    return contextlib.nullcontext() if mesh is None else mesh


class DecodeEngine:
    """B-slot continuous-batching engine. Thread-safe submit(); a background
    stepper thread executes the scheduler's per-iteration plans.

    `params` is the model's tree, in whatever type it was trained or saved, or a function of
    no arguments that returns it. The engine keeps `self.params`, the tree as its block serves
    it (`models/__init__.py`: `serving_params`), in `cfg.dtype` wherever the programs
    multiply in it. Given the function, nothing else holds the tree that came in, and each of
    its leaves is freed as it is cast."""

    def __init__(self, cfg: ModelConfig, params, *, num_slots: int = 4,
                 max_seq: Optional[int] = None, seed: int = 0,
                 lora_config: Optional[dict] = None, decode_loop: bool = True,
                 spec_config: Optional[dict] = None,
                 multi_step: Optional[int] = None,
                 prefix_cache=None,
                 max_queue_depth: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 wfq: bool = True,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_quota: Optional[int] = None,
                 tp: Any = 1):
        assert not cfg.scan_layers, "engine expects scan_layers=False param layout"
        from ray_tpu._private.config import CONFIG
        from ray_tpu.parallel.mesh import unbox

        self.cfg = cfg
        # All the engine knows of the model is its block's module (`models/__init__.py`):
        # the cache, the programs' bodies, what it counts and what it cannot take,
        # which is refused here, by name.
        self._block = models.block_module(cfg)
        for asked, feature in ((lora_config, "lora"), (spec_config, "speculation"),
                               (tp != 1, "tp"), (prefix_cache, "prefix_cache")):
            if asked:
                models.require(cfg, feature)
        if "prefix_cache" not in self._block.SUPPORTS:
            prefix_cache = False  # the default from the config flags is off for it too
        # The tree as the block's programs read it (`serving_params`: for the dense block the
        # kernels and the table in `cfg.dtype`, cast once here and not in every program). A
        # caller that hands over a function holds no tree itself, so each wider leaf is free
        # as soon as it is cast: start-up never holds both trees beside the slabs.
        tree = unbox(params() if callable(params) else params)  # strips flax's boxes; the dicts are new
        del params
        given = [leaf.dtype for leaf in jax.tree_util.tree_leaves(tree)]
        self.params = self._block.serving_params(cfg, tree)
        served = jax.tree_util.tree_leaves(self.params)
        # scheduler_stats()["model"]: the tree's bytes, and those of them cast here
        self._weight_bytes = sum(leaf.nbytes for leaf in served)
        self._weight_bytes_cast = sum(leaf.nbytes for was, leaf in zip(given, served) if leaf.dtype != was)
        self.B = num_slots
        self.T = max_seq or cfg.max_seq
        # Two generators from the one seed: this one draws a request's first token
        # and the rows `_host_drawn` names; `_sample_key` is the decode programs'
        # sampler's, carried on the device from round to round (`_decode_sample`,
        # `_decode_multi`).
        self._np_rng = np.random.default_rng(seed)
        # Tensor parallelism (docs/serving_tp.md): tp > 1 (or a mesh-axes
        # dict) shards the WHOLE decode plane — params, per-slot KV pool,
        # adapter tables — over a jax.sharding.Mesh; GSPMD partitions every
        # compiled program from its input shardings. tp=1 keeps the exact
        # single-device code path (no mesh, no resharding device_puts).
        self._mesh = build_tp_mesh(tp)
        self.tp = tp_degree(self._mesh)
        self._mesh_sig = mesh_signature(self._mesh)
        self._kv_pool = None
        if self._mesh is not None:
            self.params = shard_decode_params(self.params, self._mesh)
            from ray_tpu.devtools import leaksan as _leaksan

            self._param_shard_token = f"engine-{id(self):x}"
            _leaksan.track("tp_param_shards", token=self._param_shard_token)
        # Multi-LoRA: an HBM-budgeted pageable AdapterCache backs the stacked
        # device table (slot 0 = base model, zero factors), so one jitted
        # program serves any adapter mix in a batch AND "hundreds of tenants"
        # are no longer bounded by what fits the table — registered adapters
        # live host-side and page into a fixed set of device slots on demand
        # (docs/multitenancy.md; reference: LoraConfig + vLLM multi-LoRA,
        # S-LoRA unified paging). lora_config keys: max_loras (registry cap),
        # rank (rank bucket), cache_bytes / cache_slots (HBM budget override;
        # default from llm_adapter_cache_bytes, 0 = every adapter resident).
        self._lora_cfg = lora_config
        self._adapters: Optional[AdapterCache] = None
        if lora_config:
            budget = lora_config.get("cache_bytes")
            if budget is None:
                budget = CONFIG.llm_adapter_cache_bytes
            self._adapters = AdapterCache(
                n_layers=cfg.n_layers, hidden=cfg.hidden,
                q_out=cfg.n_heads * cfg.head_dim,
                v_out=cfg.n_kv_heads * cfg.head_dim,
                rank=int(lora_config.get("rank", 8)), dtype=cfg.dtype,
                max_adapters=int(lora_config.get("max_loras", 4)),
                budget_bytes=int(budget),
                cache_slots=lora_config.get("cache_slots"),
                name=f"engine-{id(self):x}",
                mesh=self._mesh,
            )
        self._adapter_ids = np.zeros((num_slots,), np.int32)
        if self._mesh is not None:
            # Mesh-resident per-slot KV pool: shards allocate at their
            # kv-head-split layout directly (never materialized whole on any
            # one device); freed by shutdown via the tracked pool handle.
            self._kv_pool = ShardedKVPool(
                n_layers=cfg.n_layers, shape=(self.B, self.T, cfg.n_kv_heads, cfg.head_dim),
                dtype=cfg.dtype, mesh=self._mesh, n_kv_heads=cfg.n_kv_heads,
                name=f"engine-{id(self):x}",
            )
            self._caches = self._kv_pool.take()
        else:
            self._caches = self._block.init_caches(cfg, self.B, self.T)
        # Per-slot lengths and last tokens are HOST-native (numpy): the
        # stepper reads and writes them every step, and a device-canonical
        # copy would force a blocking device->host pull per step just to do
        # slot bookkeeping. The decode/prefill dispatches ship them
        # host->device per call (a few async bytes, off the critical path).
        self._lens = np.zeros((self.B,), np.int32)
        self._last_token = np.zeros((self.B,), np.int32)
        # Per-slot temperature of the decode program's sampler (0: the argmax;
        # also 0 for a slot whose rows the host draws). It changes at admission
        # only (`_start_slot`), so the device copy is made there and a dispatch
        # hands on an array that is already on the device.
        self._temps = np.zeros((self.B,), np.float32)
        self._temps_dev = self._resident(self._temps)
        self._sample_key = self._resident(jax.random.PRNGKey(seed))
        # rows the decode rounds drew, by where (scheduler_stats())
        self._rows_sampled = {"device": 0, "host": 0}
        self._stop = False
        # Cross-thread cancel plane (docs/generation.md): cancel() resolves
        # still-QUEUED requests synchronously under the scheduler's
        # admission lock; anything already prefilling or decoding goes into
        # this set and the stepper retires it at the TOP of its next
        # iteration — a mid-stream disconnect frees the slot, lease,
        # adapter pin, and constraint state within one scheduler iteration.
        self._pending_cancels: set = set()
        self._cancel_lock = threading.Lock()
        # Set when the stepper thread dies on an exception; submitters check it
        # instead of waiting forever on callbacks that will never fire.
        self.error: Optional[BaseException] = None
        # Compute-plane observatory hooks (docs/observability.md "compute
        # plane"): every program this engine builds registers with the
        # per-process ProgramRegistry (compile wall time, invocations,
        # warmup-vs-retrace accounting) and the engine reports its device
        # bytes through one memory-ledger owner. Registry mutation is plain
        # host-side arithmetic; export happens only from scheduler_stats().
        # The ledger holds a weakref so a dropped engine is collectable.
        import weakref

        self._xprof = xprof.registry()
        self._xprof_owner = f"engine-{id(self):x}"
        _self_ref = weakref.ref(self)

        def _ledger_row():
            eng = _self_ref()
            return eng._memory_owner_report() if eng is not None else {}

        xprof.register_memory_owner(self._xprof_owner, _ledger_row)
        self._jit_prefill = {}
        # Every program returns its block's counts last (small arrays, none where
        # the block counts nothing), added on the device into one running sum
        # that only scheduler_stats() reads.
        self._stats_acc = self._block.init_stats(cfg)
        # the running sum as the last report read it, and the reports' total
        self._stats_seen = tuple(np.zeros(a.shape, np.uint32) for a in self._stats_acc)
        self._stats_totals = tuple(np.zeros(a.shape, np.int64) for a in self._stats_acc)
        self._stats_lock = threading.Lock()  # reports come from any thread
        self._jit_decode = self._xprof.instrument(
            self._xprof_owner, ("decode",),
            jax.jit(named("rt_decode", self._decode_sample), donate_argnums=(4,)),
        )
        # Multi-step decode: N tokens per dispatch (a lax.scan over decode steps
        # with the device sampler inside: argmax for a greedy slot, a draw at its
        # temperature for the others) — one host round trip per CHUNK instead of
        # per token; the role of vLLM's multi-step scheduling (num_scheduler_steps).
        # A plan takes single steps only while some slot's token is the host's to
        # draw (a top-k filter at a temperature, a constraint: `scheduler.py:_host_drawn`);
        # host-side stop/max_tokens handling rolls per-slot state back after the readback.
        if multi_step is None:
            multi_step = CONFIG.llm_multi_step
        self._multi_step = max(1, int(multi_step))
        # Explicit prefill bucket table: every compiled prefill/attach
        # program is keyed by a value from this (log-sized) set, never by a
        # raw prompt length — the structural guarantee that the program
        # caches stay small. llm_max_jit_programs is the backstop cap for
        # the cross products ((prefix, suffix) suffix programs, spec k's):
        # past it the oldest program is dropped (insertion order).
        buckets = []
        b = max(1, CONFIG.llm_prefill_bucket_min)
        while b < self.T:
            buckets.append(b)
            b *= 2
        buckets.append(self.T)
        self._prefill_buckets = tuple(buckets)
        self._max_jit_programs = max(0, int(CONFIG.llm_max_jit_programs))
        # Paged KV prefix cache (docs/kvcache.md): host-side ref-counted block
        # pool + radix prefix index. A repeated prompt prefix attaches its
        # cached KV through the padded-bucket attach path and prefills only
        # the suffix. prefix_cache=None builds one from the config flags;
        # False disables; a PrefixCacheManager instance is used as-is. With
        # llm_kv_device_bytes / llm_kv_spill_dir set the cache is the TIERED
        # hierarchy (kvcache/tiers.py): a device-resident hot tier above the
        # host pool (mesh-sharded on TP engines, so hot attaches are
        # zero-H2D) and an async disk spill tier below it.
        if prefix_cache is None and CONFIG.llm_prefix_cache_bytes > 0:
            if CONFIG.llm_kv_device_bytes > 0 or CONFIG.llm_kv_spill_dir:
                from ray_tpu.llm.kvcache import TieredPrefixCacheManager

                prefix_cache = TieredPrefixCacheManager(
                    CONFIG.llm_kv_block_size, CONFIG.llm_prefix_cache_bytes,
                    name=f"engine-{id(self):x}",
                    device_bytes=CONFIG.llm_kv_device_bytes,
                    to_device=self._kv_block_to_device,
                    spill_dir=CONFIG.llm_kv_spill_dir,
                    spill_bytes=CONFIG.llm_kv_spill_bytes,
                )
            else:
                from ray_tpu.llm.kvcache import PrefixCacheManager

                prefix_cache = PrefixCacheManager(
                    CONFIG.llm_kv_block_size, CONFIG.llm_prefix_cache_bytes,
                    name=f"engine-{id(self):x}",
                )
        self._prefix_cache = prefix_cache or None
        # An admitted prompt's rows reach that cache off the stepper's
        # critical path (`_insert_prompt_kv`): one gather program per prefill
        # bucket, an asynchronous copy, and the pool insert once the copy has
        # landed, on a worker thread of the engine's own (`_kv_insert_loop`).
        # The stepper appends to the queue without a lock; `_kv_lock` is held
        # from an insert's copy-out until the pool has it and the queue drops
        # it, so completions keep their order and whoever takes the lock next
        # sees them done. The counters are plain numbers, reported with
        # scheduler_stats()["prefix_cache"] (no Metric.inc on the loop).
        self._jit_kv_gather = {}
        self._kv_pending: collections.deque = collections.deque()
        self._kv_lock = threading.Lock()
        self._kv_wake = threading.Event()
        self._kv_counters = {
            "inserts_issued": 0, "inserts_completed": 0,
            "insert_waits": 0, "insert_wait_s": 0.0,
        }
        if max_queue_depth is None:
            max_queue_depth = CONFIG.llm_max_queue_depth
        if token_budget is None:
            token_budget = CONFIG.llm_sched_token_budget
        # Iteration-level scheduler (docs/scheduler.md): owns the
        # waiting/running queues, slot states, the per-iteration token
        # budget, and the chunked-prefill policy. The prefix-cache lookup is
        # injected so admission plans chunks over the uncached suffix only;
        # the adapter pin callbacks make admission adapter-aware
        # (docs/multitenancy.md): resident adapters are preferred, cold ones
        # page in at admission, and a fully-pinned cache back-pressures the
        # tenant instead of crashing the stepper.
        lookup = self._lookup_prefix if self._prefix_cache is not None else None
        adapter_acquire = adapter_resident = None
        if self._adapters is not None:
            adapter_acquire = self._adapters.try_acquire
            adapter_resident = self._adapters.is_resident
        self._sched = Scheduler(
            num_slots=self.B, buckets=self._prefill_buckets, max_seq=self.T,
            token_budget=token_budget, max_queue_depth=max_queue_depth,
            multi_step=self._multi_step, lookup=lookup, name=f"{id(self):x}",
            wfq=wfq, tenant_weights=tenant_weights, tenant_quota=tenant_quota,
            adapter_acquire=adapter_acquire, adapter_resident=adapter_resident,
        )
        # Request-lifecycle flight recorder + per-tenant SLO metrics
        # (docs/observability.md): phase events accrue host-side off the
        # dispatch path; metric/span export happens ONLY from the
        # scheduler_stats()/recorder_stats() report paths.
        self._recorder = FlightRecorder(name=f"engine-{id(self):x}")
        self._serve_metrics = ServeMetrics(name=f"{id(self):x}")
        # Diagnostics for benches/tests: shape of the most recent prefill
        # dispatch (offset > 0 means a prefix-cache hit prefilled suffix-only)
        # and of the most recent cache attach (which tier served the rows).
        self.last_prefill: Optional[dict] = None
        self.last_attach: Optional[dict] = None
        # One multi-step program per step count n, built on first use and
        # named for it (`rt_decode_multi_n<n>`): the registry counts each n's
        # compile, and a device trace says how many steps an execution held.
        self._jit_decode_multi = {}
        # Speculative decoding as a scheduler-scheduled phase (docs/
        # scheduler.md): a DraftProvider proposes up to k tokens per eligible
        # slot, and ONE batched gated verify forward scores every
        # participating slot. Greedy output is token-identical to plain
        # decode by construction; acceptance only affects speed.
        self._draft = None
        self._jit_spec_verify = {}
        self._spec_counters = {
            "rounds": 0, "proposed_tokens": 0, "accepted_tokens": 0,
            "emitted_tokens": 0,
        }
        self._spec_metrics = None
        self._flushed_spec = [0, 0]  # [proposed, accepted] already exported
        if spec_config:
            self._draft = self._build_draft(dict(spec_config), unbox)
            from ray_tpu.util.metrics import Counter, Gauge

            tag = {"engine": f"{id(self):x}"}
            self._spec_metrics = {
                "proposed": Counter(
                    "llm_spec_proposed_tokens",
                    "draft tokens proposed to the verify phase",
                    tag_keys=("engine",),
                ).set_default_tags(tag),
                "accepted": Counter(
                    "llm_spec_accepted_tokens",
                    "proposed tokens accepted by the target model",
                    tag_keys=("engine",),
                ).set_default_tags(tag),
                "accept_rate": Gauge(
                    "llm_spec_accept_rate",
                    "running acceptance rate of speculative proposals",
                    tag_keys=("engine",),
                ).set_default_tags(tag),
            }
        self._thread = self._kv_thread = None
        if decode_loop:  # prefill-only servers skip the stepper thread
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()
            if self._prefix_cache is not None:
                # only a stepper issues inserts for the worker to finish
                self._kv_thread = threading.Thread(
                    target=self._kv_insert_loop, daemon=True)
                self._kv_thread.start()

    def _build_draft(self, spec_config: dict, unbox):
        """spec_config -> DraftProvider. method="ngram" builds the zero-FLOP
        retrieval draft; otherwise a draft MODEL: `draft_layers=j` shares the
        target's first j layers + embeddings (EAGLE-style early exit),
        `draft_cfg`/`draft_params` plug an external tiny model, and the
        default (no keys) is the self-draft used as an all-accept test rig."""
        from ray_tpu._private.config import CONFIG
        from ray_tpu.llm.scheduler.spec import (
            ModelDraft, NGramDraft, early_exit_draft,
        )

        k = max(1, int(spec_config.get("num_spec_tokens", 6)))
        if spec_config.get("method") == "ngram":
            return NGramDraft(
                k=k,
                n=int(spec_config.get("ngram", CONFIG.llm_spec_ngram)),
                store_entries=int(spec_config.get(
                    "store_entries", CONFIG.llm_spec_store_entries)),
            )
        if spec_config.get("draft_layers"):
            d_cfg, d_params = early_exit_draft(
                self.cfg, self.params, int(spec_config["draft_layers"])
            )
        else:
            d_cfg = spec_config.get("draft_cfg") or self.cfg
            d_params = unbox(spec_config.get("draft_params", self.params))
            assert not d_cfg.scan_layers
        return ModelDraft(
            d_cfg, d_params, k=k, num_slots=self.B, max_seq=self.T,
            program=self._program, bucket=self._bucket,
        )

    @property
    def _slots(self):
        """Back-compat view: slot state lives in the scheduler now."""
        return self._sched.slots

    # -- warm start --------------------------------------------------------
    @classmethod
    def from_sharded_checkpoint(cls, cfg: ModelConfig, path: str, *,
                                tp: Any = 1, **kwargs) -> "DecodeEngine":
        """Build an engine whose weights come from a committed sharded
        checkpoint (ray_tpu.checkpoint) — the fast DP replica warm-start:
        slice files are memory-mapped straight off the shared filesystem, so
        a scale-up replica never pulls a whole pickled tree through the
        object store. Accepts either a bare params save or a train-state
        save holding a "params" subtree. Refuses uncommitted (manifest-less)
        directories.

        The restore always hands LAYOUTS to `checkpoint._restore`: with
        tp > 1 every leaf streams straight to its TP mesh sharding (each
        device reads only the file regions its shard overlaps — no host
        gather of a tree that may not fit one host); at tp=1 leaves stream
        onto the default device, never materializing an intermediate host
        pytree that the engine would immediately re-upload."""
        from ray_tpu.checkpoint import restore

        mesh = build_tp_mesh(tp)

        def restored():
            if mesh is not None:
                tree = restore(path, shardings=checkpoint_shardings(path, mesh))
            else:
                tree = restore(path, shardings=single_device_shardings())
            return tree.get("params", tree) if isinstance(tree, dict) else tree

        return cls(cfg, restored, tp=tp, **kwargs)

    # -- lora registry -----------------------------------------------------
    def add_lora(self, name: str, layer_weights: Dict[int, Dict[str, np.ndarray]],
                 alpha: float = 1.0) -> int:
        """Register an adapter host-side. layer_weights: layer index ->
        {"q_A": [M,r], "q_B": [r,H*D], "v_A": [M,r], "v_B": [r,Hkv*D]}
        (missing projections stay zero). Rank/shape consistency is validated
        against the bucketed table HERE (ValueError) instead of failing
        inside jit. Returns the adapter's stable uid; the device slot is
        paged in on first use (docs/multitenancy.md)."""
        if self._adapters is None:
            raise ValueError("engine built without lora_config")
        return self._adapters.register(name, layer_weights, alpha)

    # Explicit alias: the serve layers call this "register_adapter".
    register_adapter = add_lora

    def _adapter_index(self, lora: str) -> int:
        """Stable adapter uid for a request ("" = base). Raises the typed,
        client-visible UnknownAdapterError (a KeyError subclass) instead of
        a bare KeyError from deep inside the engine."""
        if not lora:
            return 0
        if self._adapters is None:
            raise UnknownAdapterError(
                f"unknown lora adapter {lora!r}: engine built without "
                f"lora_config"
            )
        return self._adapters.uid_of(lora)

    def _lora_tables(self):
        """The AdapterCache's current stacked device tables (or None): read
        per dispatch, because a page-in swaps the table reference."""
        return None if self._adapters is None else self._adapters.tables()

    def adapter_stats(self) -> Optional[dict]:
        """AdapterCache residency/paging counters (None when the engine has
        no lora_config). See docs/multitenancy.md."""
        return None if self._adapters is None else self._adapters.stats()

    def _resident(self, x):
        """A small array that stays on the device between dispatches (the sampler's
        key, the slots' temperatures): under a TP mesh placed where the decode program
        returns and reads it, so that no dispatch moves it and the program's second
        call finds the first's."""
        if self._mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, replicated(self._mesh))

    # -- jitted programs ---------------------------------------------------
    def _prefill_at(self, params, lora, tokens, caches, slot, offset,
                    total_len, adapter_id):
        """One chunk of one slot (the block's `prefill`). One program per
        bucket: offset and total_len are traced scalars — a chunked prefill
        of any length mix reuses exactly these bucket programs. Slot lengths
        are host-side state (the dispatcher records total_len itself — no
        device lens write)."""
        last, caches, stats = self._block.prefill(
            params, self.cfg, tokens, caches, slot, offset, total_len, lora, adapter_id)
        return (last, caches, *stats)

    def _decode_step(self, params, lora, adapter_ids, last_token, caches, lens,
                     gate):
        """One token for every slot (the block's `decode`). last_token: [B];
        lens: [B] current lengths; gate: [B] bool — only slots in the decode
        phase land their KV row. A slot mid-chunked-prefill rides through the
        batched forward with a stale lens, and an ungated write there would
        permanently corrupt rows its covering chunk already wrote (same hazard
        the spec-verify gate exists for)."""
        with _traced_on(self._mesh):
            logits, new_caches, stats = self._block.decode(
                params, self.cfg, last_token, caches, lens, gate, lora, adapter_ids)
        return (logits, new_caches, lens + 1, *stats)

    def _decode_sample(self, params, lora, adapter_ids, last_token, caches, lens,
                       gate, temps, key):
        """The single-step program (`rt_decode`): `_decode_step`, then one token
        a slot from its logits and the slots' temperatures (`_sample_device`),
        under the scope `sample`. Returns (tokens [B], logits [B, V], caches,
        lens + 1, the sampler's next key, *stats): a round pulls the tokens, and
        the logits stay on the device unless a slot's row is the host's to draw."""
        logits, new_caches, lens, *stats = self._decode_step(
            params, lora, adapter_ids, last_token, caches, lens, gate)
        with jax.named_scope("sample"):
            tokens, key = _sample_device(logits, temps, gate, key)
        return (tokens, logits, new_caches, lens, key, *stats)

    def _decode_multi(self, params, lora, adapter_ids, last_token, caches, lens,
                      gate, temps, key, *, n):
        """n tokens for every slot in ONE program (`rt_decode_multi_n<n>`): a
        `lax.scan` over decode steps, each drawing its token as the single-step
        program does (the argmax at temperature 0, a draw otherwise; under the
        scope `sample`, in the form a loop's body wants: `_sample_device_flat`)
        and feeding it to the next. The key rides the scan's carry, so n steps
        here consume it as n single-step rounds do. Returns ([n, B] tokens, final
        caches/lens, the sampler's next key, the steps' stats summed)."""

        def step(carry, _):
            last, c, l, k = carry
            logits, c, l, *stats = self._decode_step(
                params, lora, adapter_ids, last, c, l, gate
            )
            with jax.named_scope("sample"):
                nxt, k = _sample_device_flat(logits, temps, gate, k)
            return (nxt, c, l, k), (nxt, *stats)

        (last, caches, lens, key), (toks, *stats) = jax.lax.scan(
            step, (last_token, caches, lens, key), None, length=n
        )
        return (toks, caches, lens, key, *(jnp.sum(s, axis=0) for s in stats))

    def _spec_verify_batched(self, params, lora, adapter_ids, tokens, caches,
                             lens, gate, constraint_mask):
        """Target forward over [t0, d1..dk] for EVERY slot in one dispatch
        (the block's `verify`): tokens [B, k+1] at positions lens..lens+k.
        Non-participating slots (gate False) flow through the forward for
        batching but leave their KV rows untouched — the canonical row for a
        plainly-decoding slot is written by the decode dispatch that follows
        the verify phase.

        constraint_mask [B, k+1, V] is the guided-decoding composition point
        (docs/generation.md): an ALWAYS-PASSED additive logits mask — all
        zeros for unguided slots — folded in before the argmax, so the same
        ONE verify program per k serves guided and unguided traffic (no
        guided program variant, no recompile when a guided request lands).
        A disallowed draft token's mask row pins its logit to -inf, the
        masked argmax disagrees with the proposal, and the standard
        acceptance rule rejects at that position with the masked argmax as
        the correction — exactly what masked plain decode would emit, which
        is what keeps guided spec decode token-identical.

        Returns on-device argmax [B, k+1] (the host needs k+1 ints per slot,
        not logits)."""
        with _traced_on(self._mesh):
            logits, new_caches, stats = self._block.verify(
                params, self.cfg, tokens, caches, lens, gate, lora, adapter_ids)
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits + constraint_mask, axis=-1).astype(jnp.int32)
        return (greedy, new_caches, *stats)

    # -- speculative phase --------------------------------------------------
    def _spec_round(self, plan: Plan):
        """One scheduler-scheduled speculative phase: the draft provider's
        proposals (gathered at plan time) verify for every participating
        slot in ONE batched dispatch, and each slot emits its longest
        accepted prefix plus the target's correction token — exactly the
        greedy chain. Runs BEFORE the decode phase so plainly-decoding
        slots' canonical rows land last."""
        draft = self._draft
        k = draft.k
        S = k + 1
        tokens = np.zeros((self.B, S), np.int32)
        gate = np.zeros((self.B,), bool)
        base_lens: Dict[int, int] = {}
        # Guided composition (docs/generation.md): per-position constraint
        # masks for guided participants, zeros elsewhere — built host-side
        # by walking a CLONE of each slot's automaton through its KNOWN
        # proposal (the real state advances only through _emit). The array
        # is always passed, so the verify program's signature never forks.
        cmask = np.zeros((self.B, S, self.cfg.vocab_size), np.float32)
        for i in plan.spec_slots:
            s = self._sched.slots[i]
            p = plan.proposals[i]
            tokens[i, 0] = s.tokens[-1]
            tokens[i, 1:1 + len(p)] = p
            gate[i] = True
            base_lens[i] = s.host_len
            if s.constraint is not None:
                rows = s.constraint.proposal_masks(
                    [int(x) for x in p], s.params.stop_token_id, length=S,
                    budget=s.params.max_tokens - s.generated,
                )
                cmask[i, :len(rows)] = rows
        with xprof.span("rt.engine.dispatch", steps=1, slots=len(plan.spec_slots),
                        rows=int(self._lens[plan.spec_slots].sum())) as dispatch:
            with xprof.span("rt.engine.dispatch.args"):
                lora, adapter_ids = self._lora_tables(), jnp.asarray(self._adapter_ids)
                tokens_dev, lens = jnp.asarray(tokens), jnp.asarray(self._lens)
                gate_dev, cmask_dev = jnp.asarray(gate), jnp.asarray(cmask)
            with xprof.span("rt.engine.dispatch.call"):
                verify = self._program(
                    self._jit_spec_verify, ("verify", S),
                    lambda: jax.jit(named(f"rt_verify_s{S}", self._spec_verify_batched),
                                    donate_argnums=(4,)),
                )
                greedy_dev, self._caches, *stats = verify(
                    self.params, lora, adapter_ids, tokens_dev, self._caches,
                    lens, gate_dev, cmask_dev)
                self._note_stats(stats)
        # The round's ONE acceptance sync: k+1 tokens per participating slot
        # arrive in a single batched pull — no per-token host round trip.
        greedy = self._readback(greedy_dev)
        c = self._spec_counters
        c["rounds"] += 1
        round_proposed = round_accepted = 0
        with xprof.span("rt.engine.sample", slots=len(plan.spec_slots)), \
                xprof.span("rt.engine.sample.emit"):
            for i in plan.spec_slots:
                s = self._sched.slots[i]
                p = plan.proposals[i]
                l = base_lens[i]
                m = 0
                while m < len(p) and int(greedy[i, m]) == int(p[m]):
                    m += 1
                emitted = [int(x) for x in p[:m]] + [int(greedy[i, m])]
                # Bookkeeping: rows [l, l+m] now hold [t0, accepted...]; rows
                # beyond hold rejected proposals' kv, invisible behind lens and
                # overwritten write-before-read by the next dispatch.
                s.host_len = l + m + 1
                draft.on_accept(i, s, l, p, m)
                round_proposed += len(p)
                round_accepted += m
                if s.rec is not None:
                    s.rec.span("spec-verify", dispatch.t0, time.time(),
                               proposed=len(p), accepted=m)
                for token in emitted:
                    if not s.active:
                        break
                    s.generated += 1
                    s.tokens.append(token)
                    s.history.append(token)
                    self._emit(i, token)
                self._lens[i] = s.host_len
                if s.tokens:
                    self._last_token[i] = s.tokens[-1]
                c["emitted_tokens"] += len(emitted)
        c["proposed_tokens"] += round_proposed
        c["accepted_tokens"] += round_accepted
        # Plain counters only: the llm_spec_* metrics flush their deltas
        # from scheduler_stats() — a Metric.inc here rides every spec
        # round of the decode loop (RL901).

    def _insert_prompt_kv(self, slot: int, prompt: List[int], adapter: int,
                          cached_offset: int, rid: str = ""):
        """Start the copy of the slot's freshly prefilled whole blocks to the
        prefix cache, and do not wait for it. Skips when the prompt has no
        full block beyond what the cache already held (cached_offset tokens).

        One gather program (`rt_kv_gather_b<bucket>`, one per prefill bucket)
        puts the slot's rows [0, bucket) of every layer into one array in the
        pool's layout, and one asynchronous copy takes it to the host. The
        rows beyond the prompt's whole blocks are padding that the pool's
        insert ignores; the already-cached prefix rides along (the radix walk
        dedups it without copying). The gather reads the caches and returns a
        new array; the very next program consumes the buffers it read
        (`donate_argnums`), and the runtime holds that donation back until the
        reads already enqueued are done, so the copy holds the prompt's bytes
        (tests/test_llm_engine_hotpath.py holds this). The worker
        thread hands them to the pool when the copy has landed; a lookup that
        comes sooner finishes the insert itself (`_finish_kv_inserts`), and a
        third pending insert first waits for the oldest."""
        bs = self._prefix_cache.block_size
        n = (len(prompt) // bs) * bs
        if n == 0 or n <= cached_offset:
            return
        self._finish_kv_inserts(keep=_MAX_PENDING_KV_INSERTS - 1)
        with xprof.span("rt.engine.kv_insert", rid=rid, rows=n):
            bucket = self._bucket(n)
            gather = self._program(
                self._jit_kv_gather, ("kv_gather", bucket),
                lambda: jax.jit(
                    named(f"rt_kv_gather_b{bucket}", self._block.gather_rows,
                          rows=bucket),
                    out_shardings=self.kv_transfer_sharding,
                ),
            )
            kv = gather(self._caches, jnp.int32(slot))
            kv.copy_to_host_async()
            self._kv_pending.append(_PendingKVInsert(prompt[:n], adapter, kv, rid))
            self._kv_counters["inserts_issued"] += 1
        self._kv_wake.set()

    def _finish_kv_inserts(self, keep: int = 0, worker: bool = False):
        """Hand pending inserts to the prefix pool, oldest first, until `keep`
        are left, blocking until each one's copy has landed. The worker thread
        does this for every insert (span `rt.engine.kv_copy`). Anyone else is
        a caller that cannot go on before they are done: a lookup, which must
        see every insert issued before it, or a third insert. It waits for
        the worker to let go of the lock, finishes what is left itself (span
        `rt.engine.kv_insert`), and the wait is counted."""
        if len(self._kv_pending) <= keep:
            return
        t0 = time.perf_counter()
        with self._kv_lock:
            while len(self._kv_pending) > keep:
                if worker and self._stop:
                    return  # shutdown drops the rest
                tokens, namespace, kv, rid = self._kv_pending[0]
                try:
                    with xprof.span("rt.engine.kv_copy" if worker else "rt.engine.kv_insert",
                                    rid=rid, rows=len(tokens)):
                        self._prefix_cache.insert(tokens, np.asarray(kv), namespace=namespace)
                    self._kv_counters["inserts_completed"] += 1
                finally:
                    self._kv_pending.popleft()  # a failed insert is not tried again
            if not worker:
                self._kv_counters["insert_waits"] += 1
                self._kv_counters["insert_wait_s"] += time.perf_counter() - t0

    def _kv_insert_loop(self):
        """The worker thread: sleeps until the stepper has issued an insert,
        then waits for its copy and gives it to the pool, so that the stepper
        never does. It ends with the engine (`shutdown`)."""
        while True:
            self._kv_wake.wait()
            self._kv_wake.clear()
            if self._stop:
                return
            try:
                self._finish_kv_inserts(worker=True)
            except Exception:  # noqa: BLE001 - a lost cache entry must not end the worker
                logging.getLogger(__name__).exception(
                    "prefix-cache insert failed; the prompt stays uncached")

    def _drop_kv_inserts(self):
        """Forget pending inserts and free their device buffers (shutdown,
        stepper death): nobody will look the prompts up."""
        with self._kv_lock:
            while self._kv_pending:
                self._kv_pending.popleft().kv.delete()

    def _lookup_prefix(self, prompt: List[int], adapter: int):
        """The scheduler's admission lookup: every insert issued before it is
        in the cache first, so the same prompt sent twice back to back hits."""
        self._finish_kv_inserts()
        return self._prefix_cache.lookup(prompt, namespace=adapter)

    def _kv_block_to_device(self, host_kv):
        """Hot-tier promotion copy: one [L, 2, bs, Hkv, D] block onto this
        engine's device layout — mesh-sharded on kv heads for TP engines, so
        a hot-tier attach is mesh-resident (docs/serving_tp.md), plain
        device_put otherwise."""
        if self._mesh is not None:
            return jax.device_put(
                host_kv, kv_prefix_sharding(self._mesh, self.cfg.n_kv_heads)
            )
        return jax.device_put(host_kv)

    def prefix_cache_stats(self) -> Optional[dict]:
        """Hit/eviction/residency counters of the paged KV prefix cache,
        incl. the per-tier breakdown for a tiered cache (None when the cache
        is disabled). This is a REPORT path: the tiered cache's
        llm_kv_tier_* metric deltas flush here. See docs/kvcache.md."""
        if self._prefix_cache is None:
            return None
        out = self._prefix_cache.stats()
        # The insert path's own counts (plain reads; docs/kvcache.md): inserts
        # whose copy was started, those the pool has, those in flight now, and
        # how often and how long a lookup or a third insert waited for one.
        out.update(self._kv_counters, inserts_pending=len(self._kv_pending))
        return out

    # -- cluster prefix plane (docs/kvcache.md) -----------------------------
    def lease_prefix(self, token_ids: List[int], lora: str = ""):
        """Full-coverage lease of this engine's longest cached prefix of
        token_ids (no len-1 cap: the peer wants every cached row) — the
        EXPORT side of the cross-replica prefix fetch. None when the cache
        is disabled or cold. Caller must release() the lease once the
        transfer's send leg is done."""
        if self._prefix_cache is None:
            return None
        self._finish_kv_inserts()
        return self._prefix_cache.lease_prefix(
            token_ids, namespace=self._adapter_index(lora)
        )

    def insert_prefix(self, token_ids: List[int], kv: np.ndarray,
                      lora: str = "") -> int:
        """Feed a prefix fetched from a PEER replica into this engine's
        cache (the IMPORT side of the cross-replica fetch): the next lookup
        for these tokens hits locally and prefills suffix-only."""
        if self._prefix_cache is None:
            return 0
        adapter = self._adapter_index(lora)
        insert = getattr(self._prefix_cache, "insert_remote", None)
        if insert is None:
            insert = self._prefix_cache.insert
        return insert(token_ids, kv, namespace=adapter)

    def scheduler_stats(self) -> dict:
        """Iteration-level scheduler occupancy (per-phase token counters,
        interleaving, queue depths) plus speculative-decoding acceptance.
        See docs/scheduler.md. This is a REPORT path: the flight recorder's
        pending completions flush to the SLO metrics plane and trace export
        here (never from the dispatch loop)."""
        from ray_tpu.devtools import distsan

        with distsan.report_path("scheduler_stats"):
            return self._scheduler_stats_inner()

    def _scheduler_stats_inner(self) -> dict:
        out = self._sched.stats()
        if self._adapters is not None:
            out["adapters"] = self._adapters.stats()
        if self._prefix_cache is not None:
            # Report-path flush of the cache counters incl. the tiered
            # llm_kv_tier_* metric deltas (never from the decode loop).
            out["prefix_cache"] = self.prefix_cache_stats()
        if self._draft is not None:
            spec = dict(self._spec_counters)
            spec["accept_rate"] = (
                spec["accepted_tokens"] / max(1, spec["proposed_tokens"])
            )
            spec["draft"] = self._draft.stats()
            out["spec"] = spec
            if self._spec_metrics is not None:
                # Report-path delta flush of the llm_spec_* metrics (the
                # decode loop only bumps the plain _spec_counters ints).
                try:
                    dp = spec["proposed_tokens"] - self._flushed_spec[0]
                    da = spec["accepted_tokens"] - self._flushed_spec[1]
                    self._flushed_spec = [
                        spec["proposed_tokens"], spec["accepted_tokens"]]
                    if dp:
                        self._spec_metrics["proposed"].inc(dp)
                    if da:
                        self._spec_metrics["accepted"].inc(da)
                    self._spec_metrics["accept_rate"].set(spec["accept_rate"])
                except Exception:
                    pass  # metrics must never break the serving path
        # Rows the decode rounds drew, by where: in a program (single-step or multi-step), or
        # on the host from pulled logits (guided slots; top-k at a temperature).
        out["rows_sampled_device"] = self._rows_sampled["device"]
        out["rows_sampled_host"] = self._rows_sampled["host"]
        out["recorder"] = self._flush_observability()
        # Compute-plane report (same report-path contract): this engine's
        # compiled-program rows + the process-wide device-memory ledger.
        out["programs"] = self._xprof.report(owner=self._xprof_owner)
        # Where the stepper thread's time went, by `rt.engine.*` span: count
        # and seconds since the process started (plain reads of a host table).
        # `rt.engine.kv_copy` alone is another thread's, the insert worker's.
        out["loop"] = xprof.span_totals()
        out["memory"] = xprof.device_memory_report()
        # What is being served, by its widths: a replica that came up on another
        # model than the one asked for shows here, not in a model_id string.
        cfg = self.cfg
        out["model"] = {
            "hidden": cfg.hidden, "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "vocab_size": cfg.vocab_size,
            "num_slots": self.B, "max_seq": self.T, "tp": self.tp, "block": cfg.block,
            # every array a slot keeps, rows and recurrent state alike (shape arithmetic, no pull)
            "cache_bytes": sum(a.nbytes for layer in self._caches or () for a in layer),
            # the tree the programs read, and the bytes of it that construction cast from a
            # wider tree (0 where it came in the served type)
            "weight_bytes": self._weight_bytes, "weight_bytes_cast": self._weight_bytes_cast,
        }
        out.update(self._block_report())
        return out

    def _note_stats(self, stats) -> None:
        """Add a dispatch's counts (none for a block that counts nothing) to the
        running sum on the device: one small add each, no readback. The sum is
        int32 and may wrap; a report takes differences, which a wrap leaves right."""
        self._stats_acc = tuple(acc + counts for acc, counts in zip(self._stats_acc, stats))

    def _block_report(self) -> dict:
        """What the block says of its counts (report path): one readback of the
        running sum, handed to the block as the counts since the engine started
        and over the window since the last report."""
        acc = self._stats_acc  # the stepper replaces it, never changes it
        with self._stats_lock:
            now = tuple(np.asarray(a).astype(np.uint32) for a in acc)
            # modulo 2**32: right across a wrap
            window = tuple((n - seen).astype(np.int64) for n, seen in zip(now, self._stats_seen))
            self._stats_seen = now
            total = self._stats_totals = tuple(t + w for t, w in zip(self._stats_totals, window))
        return self._block.report(self.cfg, total, window)

    def _flush_observability(self) -> dict:
        """Report-path export: queued completion summaries become
        Histogram/Counter observations and traced records become synthetic
        task events for timeline()/OTel (docs/observability.md)."""
        self._serve_metrics.flush()
        self._recorder.flush_task_events()
        if self._prefix_cache is not None:
            # The tiered cache's llm_kv_tier_* deltas ride the same
            # report-path contract (stats() is where they flush).
            self._prefix_cache.stats()
        return self._recorder.stats()

    def recorder_stats(self) -> dict:
        """Flight-recorder counters; calling this (or scheduler_stats) is
        what flushes pending metrics/spans — the report-path contract."""
        return self._flush_observability()

    def set_tenant_weight(self, tenant: str, weight: float) -> None:
        """Actuator for the serve autopilot's adaptive-WFQ loop (and for
        operators): reshare one tenant's weighted-fair queue weight."""
        self._sched.set_tenant_weight(tenant, weight)

    def autopilot_signals(self) -> dict:
        """Compact control-law signal vector for the serve autopilot
        (docs/autoscale.md): queue/occupancy from the scheduler, burn
        rates from the SLO metrics plane. REPORT path — probing it also
        drains the observability backlog, so the autopilot's tick cadence
        doubles as the metric flush cadence for an otherwise-idle engine."""
        from ray_tpu.devtools import distsan

        with distsan.report_path("autopilot_signals"):
            from ray_tpu._private.config import CONFIG

            st = self._sched.stats()
            self._flush_observability()
            burns = self._serve_metrics.burn_rates()
            # Batch is NON-SLO load (docs/generation.md): its queued depth
            # and burn are excluded from the control-law signals, so a deep
            # offline backlog never scales the fleet up or steals tenant
            # weight — online pressure alone drives the laws.
            batch = CONFIG.llm_batch_tenant
            tenants = st.get("tenants") or {}
            batch_queued = int((tenants.get(batch) or {}).get("queued", 0))
            online_burns = {t: b for t, b in burns.items() if t != batch}
            return {
                "role": "engine",
                "queued": max(0, st.get("queue_depth", 0) - batch_queued),
                "running": (st.get("running", 0) or 0)
                + (st.get("prefilling", 0) or 0),
                "burn_rate": max(online_burns.values(), default=0.0),
                "tenant_burn": {
                    t: b for t, b in online_burns.items() if t
                },
                "tenant_weights": {
                    t: info.get("weight", 1.0)
                    for t, info in tenants.items() if t != batch
                },
            }

    def request_timing(self, rid: str) -> Optional[dict]:
        """Per-request timing breakdown (the response-metadata payload):
        queue/prefill/decode phase durations, TTFT, mean TPOT, e2e, routing
        reason — from the flight recorder's ring."""
        summary = self._recorder.lookup(rid)
        if summary is None:
            return None
        return {
            "request_id": summary["rid"],
            "queue_s": summary["queue_s"],
            "prefill_wait_s": summary["prefill_wait_s"],
            "ttft_s": summary["ttft_s"],
            "tpot_s": summary["tpot_s"],
            "e2e_s": summary["e2e_s"],
            "tokens": summary["tokens"],
            "route": summary["route"],
            "phases": {
                name: {"count": p["count"],
                       "seconds": round(p["seconds"], 6)}
                for name, p in summary["phases"].items()
            },
            "trace_id": summary["trace_id"],
        }

    def _memory_owner_report(self) -> dict:
        """Memory-ledger owner callback (report paths only): this engine's
        device-resident bytes by component, attributed per device where the
        plane is mesh-sharded. Shape metadata only — never a device pull."""
        components: Dict[str, int] = {}
        per_device: Dict[str, int] = {}
        kv_bytes = 0
        # The stepper's next program consumes these arrays while this thread looks:
        # only what a deleted array still answers (`nbytes` is shape arithmetic) is
        # read here, and a mesh's per-device split comes from the pool's books.
        caches = self._caches
        if self._kv_pool is not None and caches:
            kv_bytes = self._kv_pool.total_bytes
            per_device = dict(self._kv_pool.per_device)
        elif caches:
            kv_bytes = sum(a.nbytes for layer in caches for a in layer)
        components["kv_slots"] = kv_bytes
        if self._adapters is not None:
            components["adapters"] = int(
                self._adapters.stats().get("bytes_resident") or 0
            )
        if self._prefix_cache is not None:
            tiers = self._prefix_cache.stats().get("tiers")
            if tiers:
                components["prefix_hot_tier"] = int(
                    tiers.get("device_bytes") or 0
                )
        row: dict = {"bytes": sum(components.values()),
                     "components": components}
        if per_device:
            row["per_device"] = per_device
        return row

    def _leased_kv(self, lease):
        """Materialize a lease's prefix rows from the best tier: the tiered
        cache's device hot tier when every block holds a device copy (a jax
        array — zero H2D on attach, mesh-sharded on TP engines), else the
        host blocks (numpy)."""
        dev_kv = getattr(self._prefix_cache, "device_kv", None)
        if dev_kv is not None:
            kv = dev_kv(lease)
            if kv is not None:
                return kv
        return lease.kv()

    # -- public API --------------------------------------------------------
    def submit(self, token_ids: List[int], sampling: SamplingParams, callback,
               lora: str = "", tenant: Optional[str] = None,
               request_id: Optional[str] = None, route: Optional[str] = None,
               constraint=None):
        """callback(token_id: int, finished: bool) per generated token.

        tenant keys the weighted-fair admission queue (docs/multitenancy.md);
        it defaults to the adapter name, the natural tenant identity of a
        LoRA fleet. request_id keys the flight-recorder record (the serve
        layers pass theirs so `request_timing()` can surface the breakdown
        in response metadata) AND is the `cancel()` handle; route is the DP
        router's routing reason, recorded for the trace. constraint is a
        compiled guided-decoding `TokenConstraint`
        (ray_tpu.llm.generate.compile_constraint — callers own the
        tokenizer, the engine owns the per-request state): its token masks
        fold into this request's host sampling rows and spec-verify gate,
        and its state releases on finish/cancel/drain/shutdown
        (docs/generation.md). Raises ValueError when the prompt cannot fit
        the engine's sequence budget (it is never silently truncated),
        UnknownAdapterError for an unregistered adapter,
        EngineOverloadedError when the tenant's quota or the global depth
        cap is hit, and RuntimeError when the stepper is dead (shut down or
        crashed) — a dead engine must reject work loudly, not enqueue it
        where no loop will ever run it (the caller's callback would
        otherwise wait forever)."""
        self._check_alive()
        token_ids = list(token_ids) or [0]  # empty prompt decodes from token 0
        if len(token_ids) > self.T - 1:
            raise ValueError(
                f"prompt of {len(token_ids)} tokens exceeds this engine's "
                f"max_seq={self.T} budget (prompt_len <= max_seq - 1 so at "
                f"least one token can be generated); truncate the prompt "
                f"client-side or raise max_seq"
            )
        adapter = self._adapter_index(lora)
        self._check_constraint(constraint)
        # The prompt is never truncated; a generation budget that would
        # overflow the KV rows shrinks max_tokens instead.
        headroom = self.T - 1 - len(token_ids)
        if sampling.max_tokens > headroom:
            sampling = dataclasses.replace(sampling, max_tokens=max(1, headroom))
        tenant = lora if tenant is None else tenant
        req = Request(
            "prompt", prompt=token_ids, sampling=sampling, callback=callback,
            adapter=adapter, tenant=tenant,
        )
        req.rid = request_id
        req.rec = self._start_record(request_id, tenant, route,
                                     prompt_len=len(token_ids))
        if constraint is not None:
            req.constraint = constraint.begin(
                request_id or f"req-{id(req):x}"
            )
        try:
            self._sched.submit(req)
        except EngineOverloadedError:
            if req.constraint is not None:
                req.constraint.release()
                req.constraint = None
            summary = self._recorder.finish(req.rec, status="rejected")
            if summary is not None:
                self._serve_metrics.record(summary)
            raise

    def _check_constraint(self, constraint):
        """A constraint compiled against a different logits width would
        mis-mask silently; fail the submit loudly instead."""
        if constraint is None:
            return
        vocab = getattr(constraint, "vocab", None)
        if vocab is not None and int(vocab) != int(self.cfg.vocab_size):
            raise ValueError(
                f"guided constraint compiled for vocab {vocab} but this "
                f"engine's model has vocab_size={self.cfg.vocab_size}; "
                f"compile_constraint(spec, tokenizer, vocab_size) must use "
                f"the MODEL's logits width"
            )

    def _start_record(self, request_id: Optional[str], tenant: str,
                      route: Optional[str] = None, **mark_attrs):
        """Open a flight-recorder record for one admission. The trace
        context is captured from the SUBMITTING thread (the serve task's
        activated span), because the stepper thread that executes the
        request has no ambient context of its own."""
        from ray_tpu.util import tracing

        rec = self._recorder.start(
            request_id, trace=tracing.current(), tenant=tenant, route=route,
        )
        if rec is not None:
            rec.mark("queued", tenant=tenant,
                     depth=self._sched.queue_depth(), **mark_attrs)
        return rec

    def submit_prefilled(self, kv, prompt_len: int,
                         first_logits: np.ndarray, sampling: SamplingParams,
                         callback, lora: str = "",
                         token_ids: Optional[List[int]] = None,
                         tenant: Optional[str] = None,
                         request_id: Optional[str] = None,
                         transfer_s: Optional[float] = None,
                         constraint=None):
        """Admit a request whose prefill ran elsewhere (PD disaggregation,
        reference prefill_decode_disagg.py): kv [L, 2, P, Hkv, D] is the
        transferred cache prefix — host numpy, or a jax Array when the
        DeviceChannel stream staged it on device (the attach then skips the
        host round-trip) — and first_logits the last-position logits.
        token_ids (optional, the prompt behind kv) lets the transferred
        prefix feed this engine's KV prefix cache AND keeps the slot
        spec-eligible (the draft catches up on the token history)."""
        models.require(self.cfg, "pd")
        self._check_alive()
        if prompt_len >= self.T:
            raise ValueError(
                f"transferred KV prefix of {prompt_len} tokens does not fit this "
                f"decode engine's max_seq={self.T}; align prefill and decode "
                f"max_seq (build_pd_openai_app shares one config)"
            )
        adapter = self._adapter_index(lora)
        self._check_constraint(constraint)
        # Same KV headroom contract as the prompt path: the cache must hold
        # prompt_len + max_tokens rows, so a long transferred prefix shrinks
        # the generation budget rather than silently wrapping the cache.
        headroom = self.T - 1 - prompt_len
        if sampling.max_tokens > headroom:
            sampling = dataclasses.replace(sampling, max_tokens=max(1, headroom))
        tenant = lora if tenant is None else tenant
        req = Request(
            "prefilled",
            prompt=None if token_ids is None else list(token_ids),
            prompt_len=int(prompt_len), sampling=sampling, callback=callback,
            adapter=adapter, kv=kv, first_logits=first_logits,
            tenant=tenant,
        )
        req.rid = request_id
        req.rec = self._start_record(request_id, tenant,
                                     prompt_len=int(prompt_len))
        if req.rec is not None and transfer_s is not None:
            # The PD KV pull the decode server timed around the stream read.
            t1 = time.time()
            req.rec.span("pd-transfer", t1 - transfer_s, t1,
                         prompt_len=int(prompt_len))
        if constraint is not None:
            req.constraint = constraint.begin(
                request_id or f"req-{id(req):x}"
            )
        try:
            self._sched.submit(req)
        except EngineOverloadedError:
            if req.constraint is not None:
                req.constraint.release()
                req.constraint = None
            summary = self._recorder.finish(req.rec, status="rejected")
            if summary is not None:
                self._serve_metrics.record(summary)
            raise

    def open_stream(self, token_ids: List[int], sampling: SamplingParams, *,
                    lora: str = "", tenant: Optional[str] = None,
                    request_id: Optional[str] = None,
                    route: Optional[str] = None, on_token=None,
                    constraint=None, buffer_cap: Optional[int] = None):
        """Submit a request and return its `TokenStream` subscription
        (docs/generation.md) instead of wiring a raw callback: per-token
        delivery via iteration/`get()` (buffered) or the `on_token` relay
        (the asyncio-bridge shape generate_stream uses). The stream's
        `close()`/`cancel()` is the mid-stream-disconnect path — it cancels
        the underlying request, and the engine frees the slot, prefix
        lease, adapter pin, and constraint state within one scheduler
        iteration. Lifecycle: every open_stream must resolve through
        close() (iterating to exhaustion closes for you); leaksan's
        token_stream books fail tests on a stranded subscription."""
        import uuid

        from ray_tpu.llm.generate import TokenStream

        rid = request_id or f"stream-{uuid.uuid4().hex}"
        stream = TokenStream(self, rid, on_token=on_token,
                             buffer_cap=buffer_cap)
        try:
            self.submit(
                token_ids, sampling, stream._push, lora=lora, tenant=tenant,
                request_id=rid, route=route, constraint=constraint,
            )
        except BaseException:
            # The submit never enqueued: close the subscription WITHOUT the
            # cancel round-trip (there is no request to cancel).
            stream._finished.set()
            stream.close()
            raise
        return stream

    def cancel(self, request_id: Optional[str]) -> bool:
        """Cancel one request by the id its submit carried (the mid-stream
        client-disconnect path; docs/generation.md). Still-QUEUED requests
        retire synchronously here: callback fires (-1, True), the flight
        record finishes as `cancelled`, the constraint state releases.
        Anything already prefilling or decoding is handed to the stepper
        through the pending-cancel set and retires at the top of its next
        iteration — slot, prefix lease, adapter pin, and constraint state
        all free within ONE scheduler iteration. Never raises: cancelling
        an unknown/finished id (or racing engine shutdown) is a no-op —
        the terminal paths already freed everything."""
        if not request_id:
            return False
        req = self._sched.cancel_queued(request_id)
        if req is not None:
            self._fail_cancelled_request(req)
            return True
        with self._cancel_lock:
            self._pending_cancels.add(request_id)
        return True

    def _fail_cancelled_request(self, req: Request):
        """Retire a cancelled not-yet-active request: books balance (lease,
        adapter pin, constraint, flight record) and the callback observes
        the terminal sentinel exactly once."""
        if req.constraint is not None:
            req.constraint.release()
            req.constraint = None
        rec, req.rec = req.rec, None
        summary = self._recorder.finish(rec, status="cancelled")
        if summary is not None:
            self._serve_metrics.record(summary)
        if req.callback is not None:
            try:
                req.callback(-1, True)
            except Exception:
                pass  # the cancel must complete past a broken callback

    def _process_cancels(self):
        """Stepper-side half of cancel(): runs at the top of every loop
        iteration, so an active/prefilling cancel completes within one
        scheduler iteration. Ids that match nothing (request already
        finished, or cancelled while queued) drop silently."""
        with self._cancel_lock:
            if not self._pending_cancels:
                return
            rids, self._pending_cancels = self._pending_cancels, set()
        for rid in rids:
            self._cancel_one(rid)

    def _cancel_one(self, rid: str):
        # Queued again-check first: a cancel() that raced admission may have
        # missed the queue scan while the request was still queued.
        req = self._sched.cancel_queued(rid)
        if req is None:
            req = self._sched.cancel_prefilling(rid)
        if req is not None:
            self._fail_cancelled_request(req)
            return
        for i, s in enumerate(self._sched.slots):
            if not s.active or s.rid != rid:
                continue
            s.active = False
            if s.constraint is not None:
                s.constraint.release()
                s.constraint = None
            self._finish_record(s, status="cancelled")
            self._release_slot_pin(s)
            if self._draft is not None:
                self._draft.on_finish(i, s)
            if s.callback is not None:
                try:
                    s.callback(-1, True)
                except Exception:
                    pass  # the cancel must complete past a broken callback
            return

    def prefill_detached(self, token_ids: List[int], lora: str = "",
                         request_id: Optional[str] = None,
                         trace_ctx: Optional[dict] = None):
        """Prefill WITHOUT occupying a decode slot: returns
        (first_logits [V], kv [L, 2, P, Hkv, D], prompt_len) for transfer to a
        decode engine. P is a padded length >= prompt_len. Prompts that do not
        fit raise ValueError (never silently truncated). A prefix-cache hit
        prefills only the suffix and splices the cached rows host-side.

        The adapter pin covers resolve-slot .. dispatch (released in a
        finally): the device slot the program gathers from must not be
        evicted-and-reused between resolution and the dispatch capturing the
        table reference — after that, jax buffer immutability makes the
        captured table safe regardless."""
        models.require(self.cfg, "pd")
        prompt = list(token_ids)
        if len(prompt) > self.T - 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds this prefill engine's "
                f"max_seq={self.T} budget (prompt_len <= max_seq - 1); "
                f"truncate the prompt client-side or raise max_seq"
            )
        adapter = self._adapter_index(lora)  # stable uid: the cache namespace
        # Prefill-side flight record: callers dispatching from an executor
        # thread (PrefillServer) pass trace_ctx explicitly — contextvars do
        # not cross run_in_executor, so tracing.current() would be None here.
        from ray_tpu.util import tracing

        rec = self._recorder.start(
            request_id, trace=trace_ctx or tracing.current(), tenant=lora,
        )
        t_pf0 = time.time()
        handle = None
        if self._adapters is not None and adapter:
            resident = self._adapters.is_resident(adapter)
            try:
                handle = self._adapters.acquire(adapter)
            except BaseException:
                self._recorder.drop(rec)  # fully-pinned cache: books balance
                raise
            if rec is not None and not resident:
                rec.mark("adapter-page-in", adapter=adapter)
        try:
            adapter_slot = 0 if handle is None else handle.slot
            lease = None
            tier = "host"
            if self._prefix_cache is not None:
                lease = self._lookup_prefix(prompt, adapter)
            if lease is not None:
                # finally, not straight-line: a raise out of kv() or the suffix
                # prefill would otherwise pin the leased blocks forever (the
                # detached path has no scheduler drain to back-stop it), wedging
                # eviction for the rest of the engine's life.
                try:
                    m = lease.matched_tokens
                    tier = getattr(lease, "tier", "host")
                    prefix_kv = lease.kv()  # [L, 2, m, Hkv, D] (copied: safe to release)
                finally:
                    lease.release()
                first_logits, kv = self._detached_suffix(
                    prompt, m, prefix_kv, adapter_slot
                )
            else:
                m = 0
                bucket = self._bucket(len(prompt))
                padded = np.zeros((1, bucket), np.int32)
                padded[0, : len(prompt)] = prompt

                def make_detached():
                    def detached(params, lora_p, tokens, adapter_id):
                        return self._block.prefill_detached(
                            params, self.cfg, tokens, lora_p, adapter_id)

                    return jax.jit(named(f"rt_prefill_detached_b{bucket}", detached))

                prog = self._program(
                    self._jit_prefill, ("detached", bucket), make_detached
                )
                logits, kv_dev = prog(
                    self.params, self._lora_tables(), jnp.asarray(padded),
                    jnp.int32(adapter_slot)
                )
                first_logits = np.asarray(logits[len(prompt) - 1])
                if self._mesh is None:
                    kv = np.asarray(kv_dev)
                else:
                    # TP prefill: the prefix STAYS mesh-resident (sharded on
                    # kv heads). The PD handoff streams it per shard over the
                    # DeviceChannel plane — a host np.asarray here would be
                    # exactly the gather-then-scatter the sharded plane
                    # exists to avoid (docs/serving_tp.md).
                    kv = kv_dev
        except BaseException as e:
            # Books balance on the poisoned-pool / failed-dispatch paths too:
            # the record retires as dropped instead of living forever. A
            # RESOURCE_EXHAUSTED escape first pins the ranked memory ledger
            # to the recorder so the OOM is attributable post-mortem.
            if xprof.is_resource_exhausted(e):
                self._recorder.note_oom(xprof.oom_snapshot())
            self._recorder.drop(rec)
            raise
        finally:
            if handle is not None:
                handle.release()
        self.last_prefill = {
            "offset": m, "prompt_len": len(prompt), "detached": True,
            "tier": tier,
        }
        if rec is not None:
            rec.span("prefill-detached", t_pf0, time.time(),
                     prompt_len=len(prompt), cached_tokens=m, tier=tier)
            # Prefill-only records carry no generated tokens, so they feed
            # the ring/trace export but NOT the TTFT/TPOT SLO metrics.
            self._recorder.finish(rec)
        if self._prefix_cache is not None:
            bs = self._prefix_cache.block_size
            n = (len(prompt) // bs) * bs
            if n > m:  # nothing new to insert when the hit covered every block
                # The host-side prefix pool wants host rows; a TP engine pays
                # one bounded gather per INSERT (off the decode loop, skipped
                # entirely when the cache is disabled), amortized by every
                # future hit.
                host_kv = kv if isinstance(kv, np.ndarray) else np.asarray(kv)  # raylint: disable=RL603 (one per-insert pull feeding the host prefix pool)
                self._prefix_cache.insert(prompt[:n], host_kv, namespace=adapter)
        return first_logits, kv, len(prompt)

    def _detached_suffix(self, prompt: List[int], m: int,
                         prefix_kv: np.ndarray, adapter_slot: int):
        """Detached prefill of prompt[m:] against a cached m-token KV prefix.
        Returns (first_logits [V], kv [L, 2, P, Hkv, D]) with P >= prompt_len,
        rows [0, prompt_len) valid — same contract as the cold detached path.
        The prefix rides in padded to its own bucket so programs are keyed by
        (prefix_bucket, suffix_bucket), not by raw lengths."""
        suffix = prompt[m:]
        mb = self._bucket(m)
        sb = self._bucket(len(suffix))
        if prefix_kv.shape[2] < mb:
            pad = np.zeros(
                (prefix_kv.shape[0], 2, mb - prefix_kv.shape[2])
                + prefix_kv.shape[3:], prefix_kv.dtype,
            )
            prefix_kv = np.concatenate([prefix_kv, pad], axis=2)
        padded = np.zeros((1, sb), np.int32)
        padded[0, : len(suffix)] = suffix

        def make_detached_suffix():
            def detached_suffix(params, lora_p, prefix, tokens, off, adapter_id):
                return self._block.prefill_detached_suffix(
                    params, self.cfg, prefix, tokens, off, lora_p, adapter_id)

            return jax.jit(named(f"rt_prefill_detached_suffix_b{mb}_{sb}",
                                 detached_suffix))

        prog = self._program(
            self._jit_prefill, ("detached_suffix", mb, sb), make_detached_suffix
        )
        logits, suffix_kv = prog(
            self.params, self._lora_tables(), jnp.asarray(prefix_kv),
            jnp.asarray(padded), jnp.int32(m), jnp.int32(adapter_slot),
        )
        first_logits = np.asarray(logits[len(suffix) - 1])
        kv = np.concatenate(
            [prefix_kv[:, :, :m], np.asarray(suffix_kv)], axis=2
        )  # [L, 2, m + sb, Hkv, D]; rows [0, prompt_len) valid
        return first_logits, kv

    def _check_alive(self):
        """Reject submissions to a dead engine instead of enqueueing work no
        stepper will ever run (the caller's callback would hang forever)."""
        if self.error is not None:
            raise RuntimeError(
                "engine stepper died; no further requests are accepted"
            ) from self.error
        if self._stop:
            raise RuntimeError("engine is shut down")

    def shutdown(self):
        """Idempotent. Stops the stepper, then fails every request that was
        admitted but never got a slot: their prefix-cache leases release and
        their callbacks fire (token=-1, finished=True) so submitters blocked
        on generation unwind instead of hanging."""
        self._stop = True
        self._kv_wake.set()
        for thread in (self._thread, self._kv_thread):
            if thread is not None:
                thread.join(timeout=5)
        for slot in self._sched.slots:
            self._release_slot_pin(slot)  # adapter pins die with the engine
            self._release_slot_constraint(slot)
            if slot.active and slot.callback is not None:
                slot.active = False
                try:
                    slot.callback(-1, True)
                except Exception:
                    pass  # shutdown must proceed past a broken callback
        for req in self._sched.drain():
            # drain() released each request's lease/pin/constraint already.
            if req.callback is not None:
                try:
                    req.callback(-1, True)
                except Exception:
                    pass  # shutdown must proceed past a broken callback
        # Every live flight record retires (status "dropped"): ring buffers
        # and span handles balance on engine shutdown by construction —
        # leaksan's flight_record books prove it.
        self._recorder.close()
        self._drop_kv_inserts()
        close_cache = getattr(self._prefix_cache, "close", None)
        if close_cache is not None:
            close_cache()  # tiered cache: flush + stop the kv-spill worker
        self._release_mesh_state()
        # Retire this engine from the compute-plane observatory: its ledger
        # owner and program rows must not outlive it (both idempotent).
        xprof.unregister_memory_owner(self._xprof_owner)
        self._xprof.forget_owner(self._xprof_owner)
        if self._adapters is not None:
            self._xprof.forget_owner(f"adapters:{self._adapters.name}")

    def _release_mesh_state(self):
        """Drop every mesh-resident buffer reference a TP engine holds (the
        drain-and-retire contract, docs/serving_tp.md): the sharded KV pool
        frees through its tracked handle and the param-shard token balances
        its books, so leaksan proves a retired TP replica strands no
        shards. Idempotent; a no-op for single-device engines."""
        if self._kv_pool is not None:
            self._kv_pool.free()
            self._caches = []
        if self._mesh is not None and getattr(self, "_param_shard_token", None):
            from ray_tpu.devtools import leaksan as _leaksan

            _leaksan.untrack("tp_param_shards", token=self._param_shard_token)
            self._param_shard_token = None
            self.params = None

    @property
    def kv_transfer_sharding(self):
        """Target mesh sharding for a transferred KV prefix [L, 2, P, Hkv, D]
        (the PD handoff payload); None on single-device engines."""
        if self._mesh is None:
            return None
        return kv_prefix_sharding(self._mesh, self.cfg.n_kv_heads)

    # -- stepper -----------------------------------------------------------
    def _bucket(self, n: int) -> int:
        """Smallest entry of the engine's fixed bucket table that fits n
        (power-of-two multiples of llm_prefill_bucket_min, capped at T)."""
        for b in self._prefill_buckets:
            if n <= b:
                return b
        return self.T

    def _program(self, cache: dict, key, make):
        """Get-or-build a jitted program under the engine-wide cap.

        Keys are drawn from the bucket table, so growth is log-shaped by
        construction; llm_max_jit_programs bounds the cross products
        ((prefix, suffix) pairs, spec-k variants) that remain. Past the cap
        the oldest-inserted program is dropped — re-requesting it later
        re-jits (XLA's own compilation cache may still serve the binary).

        The mesh signature is part of every key (docs/serving_tp.md): a
        sharding regime is a DIFFERENT program by construction — an engine's
        mesh is fixed at construction, so nothing can recompile mid-serve,
        and two engines over different meshes never alias cache entries."""
        if self._mesh_sig is not None:
            key = (self._mesh_sig, key)
        prog = cache.get(key)
        if prog is None:
            if self._max_jit_programs and len(cache) >= self._max_jit_programs:
                cache.pop(next(iter(cache)))
            # The registry wrapper times the first call (= the synchronous
            # trace+lower+compile) and counts the rest; re-instrumenting an
            # evicted key marks its rebuild as a recompile, not warmup.
            prog = cache[key] = self._xprof.instrument(
                self._xprof_owner, key, make()
            )
        return prog

    # -- plan execution ----------------------------------------------------
    def _exec_chunk(self, chunk: ScheduledChunk):
        """Dispatch one scheduled prefill chunk (or transferred-prefix
        attach). The FIRST chunk of a cache-hit request attaches the leased
        prefix rows; the LAST chunk samples the request's first token (the
        one per-admission host pull) and activates the slot."""
        req = chunk.request
        if req.kind == "prefilled":
            self._exec_attach(req)
            return
        rec = req.rec
        slot = req.slot
        offset = req.prefilled
        if chunk.is_first and req.lease is not None:
            # Attach the cached prefix through the padded-bucket attach
            # path, then prefill only the suffix (in chunks). The lease
            # pins the blocks until the host->device copy is staged; it
            # releases in a finally — on an attach failure the stepper dies
            # and the scheduler drain would release it too, but only after
            # req.lease was cleared here, so the release must not depend on
            # the happy path.
            tier = getattr(req.lease, "tier", "host")
            with xprof.span("rt.engine.attach", rid=_rid(req),
                            rows=req.cached_offset) as attach_span:
                try:
                    prefix_kv = self._leased_kv(req.lease)
                    if isinstance(prefix_kv, np.ndarray):
                        xp = np
                        if tier == "device":
                            tier = "host"  # device copies dropped mid-lease
                    else:
                        xp = jnp  # device hot tier: the attach is zero-H2D
                    mb = self._bucket(req.cached_offset)
                    if prefix_kv.shape[2] < mb:
                        pad = xp.zeros(
                            (prefix_kv.shape[0], 2, mb - prefix_kv.shape[2])
                            + tuple(prefix_kv.shape[3:]), prefix_kv.dtype,
                        )
                        prefix_kv = xp.concatenate([prefix_kv, pad], axis=2)
                    attach = self._program(
                        self._jit_prefill, ("attach", mb),
                        lambda: jax.jit(named(f"rt_attach_b{mb}", self._block.attach_rows),
                                        donate_argnums=(0,)),
                    )
                    self._caches = attach(
                        self._caches,
                        prefix_kv if xp is jnp else jnp.asarray(prefix_kv),
                        jnp.int32(slot),
                    )
                finally:
                    req.lease.release()
                    req.lease = None
            if rec is not None:
                # Host-stamped dispatch span (the copy is staged async; a
                # blocking wait here would be the RL603 sync jaxlint bans).
                # The tier field says which tier SERVED the rows
                # (device/host/disk); a prefix the router fetched from a
                # peer replica's cache reports as "remote" for this first
                # post-fetch request (docs/observability.md).
                if rec.route == "remote_fetch":
                    tier = "remote"
                rec.span("cache-attach", attach_span.t0, attach_span.t1,
                         cached_tokens=req.cached_offset, tier=tier)
            self.last_attach = {
                "tier": tier, "cached_tokens": req.cached_offset,
            }
        t_chunk = time.time()
        padded = np.zeros((1, chunk.bucket), np.int32)
        padded[0, : len(chunk.tokens)] = chunk.tokens
        prefill = self._program(
            self._jit_prefill, chunk.bucket,
            lambda: jax.jit(named(f"rt_prefill_b{chunk.bucket}", self._prefill_at),
                            donate_argnums=(3,)),
        )
        last_logits, self._caches, *stats = prefill(
            self.params, self._lora_tables(), jnp.asarray(padded), self._caches,
            jnp.int32(slot), jnp.int32(offset),
            jnp.int32(req.prompt_len), jnp.int32(req.adapter_slot),
        )
        self._note_stats(stats)
        self._sched.chunk_done(chunk)
        if rec is not None:
            rec.span("prefill-chunk", t_chunk, time.time(),
                     bucket=chunk.bucket, offset=offset,
                     tokens=len(chunk.tokens), chunk=req.chunks - 1)
        # The host lens mirror advances with EVERY chunk (not just the last):
        # the decode write gate is the primary guard against interleaved
        # dispatches touching a mid-prefill slot, and an accurate lens is the
        # backstop — anything that did write at lens would land at the next
        # chunk's start offset and be overwritten write-before-read.
        self._lens[slot] = req.prefilled
        if not chunk.is_last:
            return  # intermediate chunk: logits discarded, no host pull
        self.last_prefill = {
            "bucket": chunk.bucket, "offset": req.cached_offset,
            "prompt_len": req.prompt_len, "chunks": req.chunks,
        }
        # The admission sync: the request's FIRST token must be sampled
        # host-side before the slot can join the decode batch — one
        # [V]-row pull per admitted request, not per step or per chunk.
        first_row = self._readback(last_logits)
        with xprof.span("rt.engine.sample", slots=1):
            if req.constraint is not None:
                first_row = first_row + req.constraint.mask(
                    req.sampling.stop_token_id, budget=req.sampling.max_tokens
                )
            first = _sample_host(first_row, req.sampling, self._np_rng)
        if self._prefix_cache is not None:
            self._insert_prompt_kv(slot, req.prompt, req.adapter,
                                   req.cached_offset, rid=_rid(req))
        if self._draft is not None:
            # Draft catch-up: cache-hit admissions (offset > 0) stay
            # spec-eligible — the draft sees the full token history (the
            # model draft re-prefills its own cache; the ngram draft only
            # needs the ids).
            self._draft.on_admit(slot, list(req.prompt))
        self._start_slot(req, first)

    def _exec_attach(self, req: Request):
        """Transferred-prefix admission (PD disaggregation): attach the KV,
        sample the first token from the transferred logits, and feed the
        slot straight into the scheduler's running queue. kv may arrive as a
        jax Array (the DeviceChannel streamed path device_puts chunks as they
        land — docs/device_channels.md) — padding then stays on device and
        the attach program consumes it without a host round-trip."""
        slot = req.slot
        kv = req.kv
        prompt_len = req.prompt_len
        on_device = isinstance(kv, jax.Array)
        with xprof.span("rt.engine.attach", rid=_rid(req),
                        rows=prompt_len) as attach_span:
            if on_device and self._mesh is not None:
                # Normalize a transferred device prefix onto THIS engine's mesh
                # (no-op when it already is): a prefix committed to one device
                # (recv_device staging) or sharded on a peer engine's mesh must
                # not meet mesh-sharded caches inside one jit un-resharded.
                kv = jax.device_put(
                    kv, kv_prefix_sharding(self._mesh, self.cfg.n_kv_heads)
                )
            xp = jnp if on_device else np
            # Pad the transferred prefix to a bucket so attach programs are reused.
            P = kv.shape[2]
            bucket = self._bucket(max(P, prompt_len))
            if P < bucket:
                pad = xp.zeros(
                    (kv.shape[0], 2, bucket - P) + tuple(kv.shape[3:]), kv.dtype
                )
                kv = xp.concatenate([kv, pad], axis=2)
            elif P > bucket:
                kv = kv[:, :, :bucket]
            attach = self._program(
                self._jit_prefill, ("attach", bucket),
                lambda: jax.jit(named(f"rt_attach_b{bucket}", self._block.attach_rows),
                                donate_argnums=(0,)),
            )
            self._caches = attach(
                self._caches, kv if on_device else jnp.asarray(kv), jnp.int32(slot)
            )
        self._lens[slot] = prompt_len
        if req.rec is not None:
            req.rec.span("pd-attach", attach_span.t0, attach_span.t1,
                         prompt_len=prompt_len, bucket=bucket,
                         on_device=on_device)
        first_row = self._readback(req.first_logits)
        with xprof.span("rt.engine.sample", slots=1):
            if req.constraint is not None:
                # Guided PD decode: the transferred first-logits row gets the
                # same start-state mask a local prefill's first sample would.
                first_row = first_row + req.constraint.mask(
                    req.sampling.stop_token_id, budget=req.sampling.max_tokens
                )
            first = _sample_host(first_row, req.sampling, self._np_rng)
        prompt_tokens = req.prompt
        # PD-disagg transferred prefixes feed the prefix cache too: the
        # host-side kv is already in pool layout, so insertion is free of
        # device readbacks.
        if (self._prefix_cache is not None and prompt_tokens
                and len(prompt_tokens) >= prompt_len):
            bs = self._prefix_cache.block_size
            n = (prompt_len // bs) * bs
            if n:
                # The pool wants host rows; a device-attached prefix pulls
                # back once here, off the decode hot loop (host-path
                # transfers are already numpy and insert for free).
                self._prefix_cache.insert(
                    prompt_tokens[:n],
                    np.asarray(kv) if on_device else kv,  # raylint: disable=RL603 (one per-admission pull feeding the prefix cache)
                    namespace=req.adapter,
                )
        if self._draft is not None:
            if prompt_tokens and len(prompt_tokens) >= prompt_len:
                # The transferred prefix carries its token ids: the draft
                # catches up and the slot stays spec-eligible.
                self._draft.on_admit(slot, list(prompt_tokens[:prompt_len]))
            else:
                # No ids, no draft history: plain decode for this slot.
                self._draft.on_plain_decode(slot)
        self._start_slot(req, first)

    def _start_slot(self, req: Request, first: int):
        self._sched.start_decode(req, first)
        slot = req.slot
        # The DEVICE slot (AdapterCache row), not the stable uid: paging can
        # move an adapter between rows, but the slot's pin (held until the
        # request finishes) keeps this row valid for the whole generation.
        self._adapter_ids[slot] = req.adapter_slot
        self._last_token[slot] = first
        s = self._sched.slots[slot]
        temp = np.float32(0.0 if _host_drawn(s.params, s.constraint) else s.params.temperature)
        if temp != self._temps[slot]:
            self._temps[slot] = temp
            self._temps_dev = self._resident(self._temps)
        self._emit(slot, first)

    def _finish_record(self, s, status: str = "ok"):
        """Retire a slot's flight record exactly once: the decode phase
        aggregates into ONE span (first..last token — the per-token record
        is the timestamp list, not n events) and the completion summary
        queues for the report-path metrics flush (a GCS RPC must never ride
        this loop). status="cancelled" is the disconnect path — the record
        retires under that outcome and stays OUT of the SLO good/bad books
        (a client hanging up is not an availability breach)."""
        rec, s.rec = s.rec, None
        if rec is None:
            return
        tt = rec.token_times
        if tt:
            rec.span("decode", tt[0], tt[-1], tokens=len(tt))
        summary = self._recorder.finish(rec, status=status)
        if summary is not None:
            self._serve_metrics.record(summary)
            with xprof.span("rt.engine.finish", rid=rec.rid,
                            tokens=summary["tokens"], status=status):
                pass

    @staticmethod
    def _note_first_token(rec):
        """A request's first token as an instant of a profiler trace, its
        durations the record's own (the stamp just made, `admitted`, the first
        `prefill-chunk`), so that trace and recorder cannot disagree."""
        attrs = {"ttft_us": int((rec.token_times[0] - rec.t_submit) * 1e6),
                 "chunks": sum(1 for e in rec.events if e[0] == "prefill-chunk")}
        wait = rec.prefill_wait_s()
        if wait is not None:  # a transferred prefix ran no chunk
            attrs["prefill_wait_us"] = int(wait * 1e6)
        with xprof.span("rt.engine.first_token", rid=rec.rid, **attrs):
            pass

    def _emit(self, slot: int, token: int):
        s = self._sched.slots[slot]
        done = (
            s.generated >= s.params.max_tokens
            or (s.params.stop_token_id is not None and token == s.params.stop_token_id)
        )
        if s.constraint is not None:
            if (s.params.stop_token_id is not None
                    and token == s.params.stop_token_id):
                pass  # the stop token ends output; it never enters the DFA
            else:
                s.constraint.advance(token)
                if s.constraint.is_complete():
                    # Accepting dead-end: nothing can legally extend the
                    # output — finish NOW instead of burning max_tokens on
                    # tokens the mask would make degenerate.
                    done = True
        self._sched.note_emitted(slot)  # per-tenant decode-token metering
        if s.rec is not None:
            s.rec.token()  # host timestamp append; TTFT/TPOT derive from these
            if len(s.rec.token_times) == 1:
                self._note_first_token(s.rec)
            if done:
                # Retire the record BEFORE the callback observes
                # finished=True: a caller reading request_timing() the
                # moment its future resolves must see the finished summary,
                # not a mid-flight record missing the decode span.
                self._finish_record(s)
        try:
            s.callback(token, done)
        except Exception:
            done = True
            self._finish_record(s)  # callback-abort path: books still balance
        if done:
            s.active = False
            if s.constraint is not None:
                s.constraint.release()  # guided books balance on finish
                s.constraint = None
            self._release_slot_pin(s)
            if self._draft is not None:
                self._draft.on_finish(slot, s)
            # slot cache naturally reused on next admit (lens reset at prefill)

    @staticmethod
    def _release_slot_pin(s):
        """Unpin the slot's adapter exactly once (the finish, shutdown, and
        stepper-death paths all funnel here; a double release would free a
        pin a concurrent admission already re-acquired)."""
        handle, s.adapter_handle = s.adapter_handle, None
        if handle is not None:
            try:
                handle.release()
            except Exception:
                pass  # a poisoned cache must not break finish/teardown

    @staticmethod
    def _release_slot_constraint(s):
        """Release a slot's guided constraint state exactly once on the
        terminal paths that bypass _emit (shutdown, stepper death)."""
        state, s.constraint = s.constraint, None
        if state is not None:
            try:
                state.release()
            except Exception:
                pass  # leaksan books must balance even on a broken state

    def _loop(self):
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 - stepper death must be visible
            if xprof.is_resource_exhausted(e):
                # OOM forensics: attach the ranked ledger snapshot to the
                # flight recorder before the engine poisons itself, so the
                # operator sees WHO held the bytes at death, not just that
                # XLA ran out (docs/observability.md "compute plane").
                self._recorder.note_oom(xprof.oom_snapshot())
            self.error = e
            # Callers blocked on per-request callbacks would otherwise hang
            # forever: fail every active/queued request loudly. A program that
            # raised after consuming its caches leaves `self._caches` naming
            # deleted buffers: nothing dispatches on them again, because this
            # thread ends here and `_check_alive` refuses every later request.
            for slot in self._sched.slots:
                self._release_slot_pin(slot)
                self._release_slot_constraint(slot)
                if slot.active and slot.callback is not None:
                    slot.active = False
                    try:
                        slot.callback(-1, True)
                    except Exception:
                        pass
            for req in self._sched.drain():
                if req.callback is not None:
                    try:
                        req.callback(-1, True)
                    except Exception:
                        pass
            self._recorder.close()  # stepper death strands no live records
            self._drop_kv_inserts()

    def _loop_inner(self):
        """Execute one scheduler plan per iteration: prefill chunks, then
        the speculative verify phase, then the batched decode phase (the
        order is load-bearing — see Plan). The whole loop runs under a
        distsan hot-path tag: any metric mutation or GCS call reached from
        an iteration — even through a callback distlint can't see — is a
        recorded contract violation when the sanitizer is on."""
        from ray_tpu.devtools import distsan

        with distsan.hot_path("llm-decode-loop"):
            while not self._stop:
                # Disconnect cancels retire FIRST (before planning), so a
                # cancelled slot never joins another decode dispatch: the
                # cancel-to-free latency is bounded by one iteration.
                with xprof.span("rt.engine.plan"):
                    self._process_cancels()
                    plan = self._sched.next_plan(draft=self._draft)
                if plan.idle:
                    with xprof.span("rt.engine.idle"):
                        time.sleep(0.002)
                    continue
                # The `rt.engine.*` spans (docs/observability.md "compute
                # plane") put this loop's phases into any profiler trace, on
                # the device's clock, and into scheduler_stats()["loop"].
                # `limit` says what held `steps` under `steps_max`
                # (`Plan.limit`); `unix_us` is this instant on the clock of
                # the flight records, so that any trace holds the offset
                # between that clock and the profiler's.
                with xprof.span("rt.engine.iter", chunks=len(plan.chunks),
                                decode_slots=len(plan.decode_slots),
                                steps=plan.multi_step, steps_max=plan.steps_max,
                                limit=plan.limit,
                                waiting=self._sched.queue_depth(),
                                prefilling=self._sched.prefilling(),
                                unix_us=int(time.time() * 1e6)):
                    self._exec_plan(plan)

    def _exec_plan(self, plan: Plan):
        for chunk in plan.chunks:
            with xprof.span("rt.engine.prefill", rid=_rid(chunk.request),
                            tokens=len(chunk.tokens), bucket=chunk.bucket,
                            last=int(chunk.is_last)):
                self._exec_chunk(chunk)
        if plan.spec_slots:
            self._spec_round(plan)
        if plan.decode_slots:
            if plan.multi_step > 1:
                self._multi_round(plan.decode_slots, plan.multi_step)
            else:
                self._decode_round(plan.decode_slots)
            if self._draft is not None:
                for i in plan.decode_slots:
                    # A plain step advances the target but not a model
                    # draft's cache: its proposals would be garbage.
                    # (The ngram draft is stateless here: no-op.)
                    self._draft.on_plain_decode(i)

    def _step_args(self, decode_slots: List[int]):
        """What a decode or multi-step program takes besides the parameters and
        the caches: (lora, adapter_ids, last_token, lens, gate).
        lens/last_token/adapter_ids ride host->device per dispatch (an async
        copy of a few int32s); the returned device lens is discarded — the
        host mirrors are canonical. The write gate restricts KV writes to
        exactly the slots whose lens advances after the step: idle and
        mid-prefill slots pass through write-free."""
        gate = np.zeros((self.B,), bool)
        gate[decode_slots] = True
        return (self._lora_tables(), jnp.asarray(self._adapter_ids),
                jnp.asarray(self._last_token), jnp.asarray(self._lens),
                jnp.asarray(gate))

    def _hot(self, decode_slots: List[int]) -> int:
        """How many of a round's slots its program draws at a temperature
        (`rt.engine.dispatch`'s `hot`): 0 says the round's program takes the argmax."""
        return int(np.count_nonzero(self._temps[decode_slots]))

    def _readback(self, x) -> np.ndarray:
        """A program's result on the host: the dispatch's one device->host pull,
        in the two parts a trace has to tell apart. `.wait` ends when the host
        is back from waiting for the device (the step's rest, then the wake-up
        of this thread: the device's idle time inside it is the wake-up);
        `.copy` is the copy out of a finished buffer (the bytes). No second
        pull and no program: `block_until_ready` moves nothing, and the copy
        is asked for before the wait, so that it follows the step on the
        device as it does under `np.asarray` alone (waiting first and asking
        then costs a second round trip, 0.12 ms a pull: PERF.md §6, PR 36)."""
        with xprof.span("rt.engine.readback", bytes=x.nbytes):
            on_device = isinstance(x, jax.Array)  # a PD transfer's first logits may be numpy
            if on_device:
                x.copy_to_host_async()
            with xprof.span("rt.engine.readback.wait"):
                if on_device:
                    x.block_until_ready()  # raylint: disable=RL603 (the same pull's wait, named apart from its copy)
            with xprof.span("rt.engine.readback.copy"):
                return np.asarray(x)  # raylint: disable=RL603 (the per-dispatch batched readback)

    def _decode_round(self, decode_slots: List[int]):
        """One single-step round: the program steps every slot and draws its
        token (`_decode_sample`: the argmax at temperature 0, a draw from
        `softmax(logits / T)` otherwise), and the round pulls `[B]` token ids.
        The logits stay on the device unless a slot in the round is the host's
        to draw (`_host_drawn`: a guided slot, whose mask is a host automaton's
        row a step, or a top-k filter at a temperature): then they are pulled
        too, and those rows alone go through `_sample_host`."""
        slots = self._sched.slots
        host_rows = [i for i in decode_slots
                     if slots[i].active and _host_drawn(slots[i].params, slots[i].constraint)]
        with xprof.span("rt.engine.dispatch", steps=1, slots=len(decode_slots),
                        rows=int(self._lens[decode_slots].sum()),
                        hot=self._hot(decode_slots)):
            with xprof.span("rt.engine.dispatch.args"):
                lora, adapter_ids, last_token, lens, gate = self._step_args(decode_slots)
            with xprof.span("rt.engine.dispatch.call"):
                tokens_dev, logits, self._caches, _, self._sample_key, *stats = self._jit_decode(
                    self.params, lora, adapter_ids, last_token, self._caches, lens, gate,
                    self._temps_dev, self._sample_key)
                self._note_stats(stats)
            if host_rows:
                logits.copy_to_host_async()  # behind the step, as the tokens' copy is
        # The step's device->host pull: 4 bytes a slot.
        tokens = self._readback(tokens_dev).tolist()
        with xprof.span("rt.engine.sample", slots=len(decode_slots), host_rows=len(host_rows)):
            drawn = {}
            if host_rows:
                logits_np = self._readback(logits)
                with xprof.span("rt.engine.sample.draw"):
                    for i in host_rows:
                        s = slots[i]
                        row = logits_np[i]
                        if s.constraint is not None:
                            # Guided composition point (docs/generation.md): one cached
                            # [V] mask row + one numpy add on the pulled logits, on the
                            # host and with no program of its own. When the
                            # unconstrained argmax is already legal the mask cannot
                            # change it, so guided greedy output is token-identical to
                            # unconstrained greedy except where the constraint binds.
                            # budget= steers onto a completable path once remaining
                            # max_tokens gets tight (an unbounded quantifier must not
                            # eat the budget and truncate mid-pattern).
                            row = row + s.constraint.mask(
                                s.params.stop_token_id,
                                budget=s.params.max_tokens - s.generated,
                            )
                        drawn[i] = _sample_host(row, s.params, self._np_rng)
            with xprof.span("rt.engine.sample.emit"):
                self._lens[decode_slots] += 1  # the decode step wrote these slots' kv rows
                emitted = 0
                for i in decode_slots:
                    s = slots[i]
                    if not s.active:
                        continue
                    token = drawn.get(i, tokens[i])
                    s.generated += 1
                    s.host_len += 1
                    s.tokens.append(token)
                    s.history.append(token)
                    self._last_token[i] = token
                    self._emit(i, token)
                    emitted += 1
                self._rows_sampled["host"] += len(drawn)
                self._rows_sampled["device"] += emitted - len(drawn)

    def _multi_round(self, decode_slots: List[int], n: int):
        """One multi-token dispatch + host-side emission with rollback for
        slots that stop early (stop_token, drawn or greedy): their device
        lens/last_token are corrected back to what was actually consumed. The
        program draws every step's token itself (`_decode_multi`), at the slots'
        temperatures; the sampler's key has then moved on by n steps whatever
        was consumed."""
        with xprof.span("rt.engine.dispatch", steps=n, slots=len(decode_slots),
                        rows=int(self._lens[decode_slots].sum()),
                        hot=self._hot(decode_slots)):
            with xprof.span("rt.engine.dispatch.args"):
                lora, adapter_ids, last_token, lens, gate = self._step_args(decode_slots)
            with xprof.span("rt.engine.dispatch.call"):
                decode_multi = self._program(
                    self._jit_decode_multi, ("decode_multi", n),
                    lambda: jax.jit(named(f"rt_decode_multi_n{n}", self._decode_multi, n=n),
                                    donate_argnums=(4,)),
                )
                toks_dev, self._caches, _, self._sample_key, *stats = decode_multi(
                    self.params, lora, adapter_ids, last_token, self._caches, lens, gate,
                    self._temps_dev, self._sample_key)
                self._note_stats(stats)
        # The chunk's ONE device->host pull: n tokens x B slots per readback
        # (the whole point of multi-step decode).
        toks = self._readback(toks_dev)
        # Nothing is drawn on the host here (the program drew every step): the
        # round's `rt.engine.sample` is emission alone, and says so.
        with xprof.span("rt.engine.sample", slots=len(decode_slots)), \
                xprof.span("rt.engine.sample.emit"):
            emitted = 0
            for i in decode_slots:
                s = self._sched.slots[i]
                self._lens[i] += n  # device wrote n kv rows for this slot
                consumed = 0
                for j in range(n):
                    if not s.active:
                        break
                    token = int(toks[j, i])
                    consumed += 1
                    s.generated += 1
                    s.host_len += 1
                    s.tokens.append(token)
                    s.history.append(token)
                    self._last_token[i] = token
                    self._emit(i, token)
                emitted += consumed
                if consumed < n:
                    # Early stop: rows past the last consumed token are invisible
                    # once lens rolls back (kv_mask <= lens) and get overwritten
                    # by the slot's next occupant.
                    self._lens[i] = s.host_len
            self._rows_sampled["device"] += emitted
