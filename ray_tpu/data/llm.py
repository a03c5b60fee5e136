"""ray_tpu.data.llm: batch LLM inference inside Data pipelines.

Design parity: reference `python/ray/data/llm.py` + the processor/stage stack
under `python/ray/llm/_internal/batch/` (`processor/base.py` ProcessorBuilder,
`processor/vllm_engine_proc.py` build_vllm_engine_processor,
`stages/vllm_engine_stage.py`, `stages/tokenize_stage.py`,
`stages/chat_template_stage.py`, `stages/http_request_stage.py`) — a
`Processor` is a reusable pipeline fragment: preprocess → [chat template →
tokenize → engine → detokenize] → postprocess, each stage a `map_batches` over
a pool of warm actors.

Re-designed TPU-first: the engine stage holds ONE warm `DecodeEngine`
(`ray_tpu/llm/_engine.py`) per pool actor — compiled prefill/decode programs
persist across batches — and every batch is fed through the engine's
continuous-batching queue, so decode steps interleave all in-flight rows
instead of generating one prompt at a time (the reference gets this from
vLLM's AsyncLLMEngine; here it is the engine's slot scheduler). Backpressure
is structural: a stage call returns only when its batch completes, so Data's
streaming executor throttles upstream reads to engine throughput.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np


# --------------------------------------------------------------------------
# configs
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ProcessorConfig:
    """Base processor config (reference: processor/base.py ProcessorConfig).

    batch_size rows are handed to a stage actor per call; concurrency sizes
    the engine-stage actor pool (data parallelism across warm engines).
    """

    batch_size: int = 32
    concurrency: Union[int, Tuple[int, int]] = 1
    accelerator_resources: Optional[Dict[str, float]] = None

    def pool_size(self) -> int:
        c = self.concurrency
        return int(c[1] if isinstance(c, (tuple, list)) else c)


@dataclasses.dataclass
class EngineProcessorConfig(ProcessorConfig):
    """TPU engine processor config (the `vLLMEngineProcessorConfig` analog,
    reference processor/vllm_engine_proc.py). `engine_kwargs` feed the
    DecodeEngine (num_slots, max_seq, seed, lora_config, spec_config)."""

    model_id: str = "test-tiny"
    model_config: Optional[Any] = None
    checkpoint_path: Optional[str] = None
    tokenizer: Optional[Any] = None
    engine_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Default sampling for rows without a "sampling_params" column.
    sampling_params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    apply_chat_template: bool = False
    tokenize: bool = True
    detokenize: bool = True
    # Per-batch throughput line ("[data.llm] ... tok/s"), the visible analog of
    # the reference's batch telemetry.
    log_stats: bool = True
    # Record the per-batch token emission order (row indices) in an
    # "emit_order" column — proof that continuous batching interleaved rows.
    record_emit_order: bool = False
    # Shared-fleet batch mode (docs/generation.md): a serve DeploymentHandle
    # (picklable: app+deployment names) routes this stage's rows into LIVE
    # serve replicas as the zero-floor-weight batch WFQ tenant instead of
    # building a dedicated engine per pool actor. Online traffic always
    # preempts: the scheduler's batch tenant has a floor weight and the
    # autopilot ignores batch pressure (no scale-up on batch load).
    serve_handle: Optional[Any] = None


# Keep the reference's public spelling available for drop-in familiarity.
TPUEngineProcessorConfig = EngineProcessorConfig


@dataclasses.dataclass
class HttpRequestProcessorConfig(ProcessorConfig):
    """HTTP processor config (reference: processor/http_request_proc.py).
    Rows must carry a "payload" column; responses land in "http_response"."""

    url: str = ""
    headers: Dict[str, str] = dataclasses.field(default_factory=dict)
    qps: Optional[float] = None
    timeout_s: float = 60.0


# --------------------------------------------------------------------------
# stage callables (instantiated once per pool actor: warm state)
# --------------------------------------------------------------------------


def _column(batch: Dict[str, Any], name: str) -> List[Any]:
    values = batch[name]
    if isinstance(values, np.ndarray):
        return [v.tolist() if isinstance(v, np.ndarray) else v for v in values]
    return list(values)


def _rows(batch: Dict[str, Any]) -> List[Dict[str, Any]]:
    names = list(batch.keys())
    cols = {n: _column(batch, n) for n in names}
    n = len(cols[names[0]]) if names else 0
    return [{name: cols[name][i] for name in names} for i in range(n)]


def _rows_to_batch(rows: List[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    if not rows:
        return {}
    # Union of keys across rows: a stage may add a column to only some rows
    # (e.g. chat template skips rows without messages) — missing cells become
    # None instead of the column silently vanishing.
    names: Dict[str, None] = {}
    for r in rows:
        for name in r:
            names.setdefault(name)
    out: Dict[str, np.ndarray] = {}
    for name in names:
        vals = [r.get(name) for r in rows]
        arr = np.empty(len(vals), dtype=object)
        arr[:] = vals
        out[name] = arr
    return out


def _resolve_tokenizer_cached(spec):
    """Per-process tokenizer cache: pool workers are reused across blocks, so
    an HF tokenizer (seconds of load time) is built once per worker, not once
    per block. Non-hashable specs (tokenizer objects) pass straight through."""
    from ray_tpu.llm import resolve_tokenizer

    if spec is None or isinstance(spec, str):
        tok = _TOKENIZER_CACHE.get(spec)
        if tok is None:
            tok = _TOKENIZER_CACHE[spec] = resolve_tokenizer(spec)
        return tok
    return resolve_tokenizer(spec)


_TOKENIZER_CACHE: Dict[Any, Any] = {}


class ChatTemplateStage:
    """messages -> prompt string (reference: stages/chat_template_stage.py).
    Uses the tokenizer's chat template when it has one; otherwise a plain
    role-prefixed rendering (matching OpenAIRouter's fallback)."""

    def __init__(self, tokenizer_spec):
        self._tok = _resolve_tokenizer_cached(tokenizer_spec)

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        rows = _rows(batch)
        inner = getattr(self._tok, "_tok", None)
        for row in rows:
            messages = row.get("messages")
            if messages is None:
                continue
            if inner is not None and getattr(inner, "chat_template", None):
                row["prompt"] = inner.apply_chat_template(
                    messages, tokenize=False, add_generation_prompt=True
                )
            else:
                row["prompt"] = "\n".join(
                    f"{m.get('role', 'user')}: {m.get('content', '')}"
                    for m in messages
                ) + "\nassistant:"
        return _rows_to_batch(rows)


class TokenizeStage:
    """prompt -> token ids (reference: stages/tokenize_stage.py)."""

    def __init__(self, tokenizer_spec):
        self._tok = _resolve_tokenizer_cached(tokenizer_spec)

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        rows = _rows(batch)
        for row in rows:
            if "tokenized_prompt" not in row or row["tokenized_prompt"] is None:
                row["tokenized_prompt"] = self._tok.encode(str(row.get("prompt", "")))
        return _rows_to_batch(rows)


class DetokenizeStage:
    """generated token ids -> text (reference: stages/tokenize_stage.py
    DetokenizeStage)."""

    def __init__(self, tokenizer_spec):
        self._tok = _resolve_tokenizer_cached(tokenizer_spec)

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        rows = _rows(batch)
        for row in rows:
            ids = row.get("generated_tokens") or []
            row["generated_text"] = self._tok.decode([int(t) for t in ids])
        return _rows_to_batch(rows)


class EngineStage:
    """The LLM engine stage (reference: stages/vllm_engine_stage.py
    vLLMEngineStage).

    One warm DecodeEngine per pool actor. A batch call submits EVERY row into
    the engine's continuous-batching queue up front and waits for all to
    finish: the engine's slot scheduler admits rows as slots free, decode
    steps advance all active slots together, and per-row callbacks collect
    tokens — requests interleave exactly as they do behind Serve.
    """

    def __init__(self, config: EngineProcessorConfig):
        import os

        from ray_tpu.llm import LLMConfig, engine_config, load_model
        from ray_tpu.llm._engine import DecodeEngine

        self._config = config
        self._handle = config.serve_handle
        if self._handle is not None:
            # Shared-fleet mode: rows ride live serve replicas as the batch
            # tenant; no local engine (and no extra compiled programs).
            self._engine = None
            self._pid = os.getpid()
            return
        kwargs = dict(config.engine_kwargs)
        llm_cfg = LLMConfig(
            model_id=config.model_id,
            model_config=config.model_config,
            checkpoint_path=config.checkpoint_path,
            tokenizer=config.tokenizer,
            seed=int(kwargs.pop("seed", 0)),
        )
        cfg = engine_config(llm_cfg)
        self._engine = DecodeEngine(
            cfg,
            lambda: load_model(llm_cfg)[1],
            num_slots=int(kwargs.pop("num_slots", 4)),
            max_seq=kwargs.pop("max_seq", None) or min(cfg.max_seq, 2048),
            seed=llm_cfg.seed,
            lora_config=kwargs.pop("lora_config", None),
            spec_config=kwargs.pop("spec_config", None),
        )
        self._pid = os.getpid()

    @staticmethod
    def _row_sampling(defaults: Dict[str, Any], row: Dict[str, Any]) -> dict:
        # Arrow struct columns null-pad keys missing in some rows; a None
        # must not shadow a configured default.
        row_sp = {
            k: v for k, v in (row.get("sampling_params") or {}).items()
            if v is not None
        }
        return {**defaults, **row_sp}

    @staticmethod
    def _row_token_ids(row: Dict[str, Any]) -> List[int]:
        token_ids = row.get("tokenized_prompt")
        if token_ids is None:
            raise ValueError(
                "engine stage needs a 'tokenized_prompt' column; enable "
                "tokenize=True or provide token ids in preprocess"
            )
        return [int(t) for t in token_ids]

    def _call_serve(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Shared-fleet mode: each row becomes one generate() on a live
        serve replica, tagged as the batch WFQ tenant, with a bounded
        in-flight window so batch load can never swamp a replica's admission
        queue ahead of online traffic (docs/generation.md)."""
        from ray_tpu._private.config import CONFIG

        rows = _rows(batch)
        if not rows:
            return batch
        defaults = self._config.sampling_params
        tenant = CONFIG.llm_batch_tenant
        window = max(1, int(CONFIG.llm_batch_max_inflight))
        t0 = time.monotonic()
        results: List[Optional[dict]] = [None] * len(rows)
        inflight: List[Tuple[int, Any]] = []  # (row index, response) FIFO
        prompt_lens: List[int] = []

        def drain_one():
            i, resp = inflight.pop(0)
            results[i] = resp.result(timeout_s=300)

        for i, row in enumerate(rows):
            sp = self._row_sampling(defaults, row)
            token_ids = self._row_token_ids(row)
            prompt_lens.append(len(token_ids))
            while len(inflight) >= window:
                drain_one()
            inflight.append((i, self._handle.generate.remote(
                token_ids,
                max_tokens=int(sp.get("max_tokens", 32)),
                temperature=float(sp.get("temperature", 0.0)),
                top_k=int(sp.get("top_k", 0)),
                stop_token_id=sp.get("stop_token_id"),
                lora=str(sp.get("lora", "")),
                tenant=tenant,
            )))
        while inflight:
            drain_one()
        dt = max(time.monotonic() - t0, 1e-9)
        gen_tokens = sum(len(r["token_ids"]) for r in results)
        if self._config.log_stats:
            print(
                f"[data.llm] serve batch of {len(rows)} prompts: {gen_tokens} "
                f"tokens in {dt:.2f}s = {gen_tokens / dt:.1f} tok/s "
                f"(tenant {tenant!r})"
            )
        for i, row in enumerate(rows):
            row["generated_tokens"] = list(results[i]["token_ids"])
            row["num_input_tokens"] = prompt_lens[i]
            row["num_generated_tokens"] = len(results[i]["token_ids"])
            row["batch_tokens_per_s"] = gen_tokens / dt
            row["engine_pid"] = self._pid
        return _rows_to_batch(rows)

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        from ray_tpu.llm._engine import EngineOverloadedError, SamplingParams

        if self._handle is not None:
            return self._call_serve(batch)
        from ray_tpu._private.config import CONFIG

        rows = _rows(batch)
        if not rows:
            return batch
        defaults = self._config.sampling_params
        done_events = [threading.Event() for _ in rows]
        outputs: List[List[int]] = [[] for _ in rows]
        emit_lock = threading.Lock()
        emit_order: List[int] = []
        t0 = time.monotonic()
        # Bounded in-flight window (docs/generation.md): at most
        # llm_batch_max_inflight rows live in the engine at once, so a colocated
        # online tenant's admissions always find queue room — batch preempts
        # nothing. Released by each row's finish callback.
        window = threading.Semaphore(max(1, int(CONFIG.llm_batch_max_inflight)))
        rids = [f"batch-{id(done_events):x}-{i}" for i in range(len(rows))]

        def make_cb(i: int):
            def cb(token: int, finished: bool):
                with emit_lock:
                    if token >= 0:
                        outputs[i].append(int(token))
                        emit_order.append(i)
                if finished:
                    done_events[i].set()
                    window.release()

            return cb

        prompt_lens = []
        dead = False
        for i, row in enumerate(rows):
            sp = self._row_sampling(defaults, row)
            token_ids = self._row_token_ids(row)
            prompt_lens.append(len(token_ids))
            while not window.acquire(timeout=2.0):
                if self._engine.error is not None:
                    dead = True
                    break
            if dead:
                break
            while True:
                try:
                    self._engine.submit(
                        token_ids,
                        SamplingParams(
                            max_tokens=int(sp.get("max_tokens", 32)),
                            temperature=float(sp.get("temperature", 0.0)),
                            top_k=int(sp.get("top_k", 0)),
                            stop_token_id=sp.get("stop_token_id"),
                        ),
                        make_cb(i),
                        lora=str(sp.get("lora", "")),
                        tenant=CONFIG.llm_batch_tenant,
                        request_id=rids[i],
                    )
                    break
                except EngineOverloadedError:
                    if self._engine.error is not None:
                        dead = True
                        break
                    time.sleep(0.05)  # queue full of online traffic: yield
            if dead:
                break
        if not dead:
            for ev in done_events:
                # Poll-wait so a dead stepper thread fails the batch instead
                # of hanging the whole Data job on callbacks that never fire.
                while not ev.wait(2.0):
                    if self._engine.error is not None:
                        break
                if self._engine.error is not None:
                    break
        if self._engine.error is not None:
            # Cancel/drain every still-unfinished submission BEFORE raising:
            # a failed batch must leave zero live slots or leases behind
            # (leaksan flight_record / lease books balance). cancel() is
            # queue-side-safe even with the stepper dead and never raises.
            for i, ev in enumerate(done_events):
                if not ev.is_set():
                    self._engine.cancel(rids[i])
            raise RuntimeError(
                "LLM engine stepper died"
            ) from self._engine.error
        dt = max(time.monotonic() - t0, 1e-9)
        gen_tokens = sum(len(o) for o in outputs)
        if self._config.log_stats:
            print(
                f"[data.llm] batch of {len(rows)} prompts: {gen_tokens} tokens "
                f"in {dt:.2f}s = {gen_tokens / dt:.1f} tok/s (engine pid {self._pid})"
            )
        for i, row in enumerate(rows):
            row["generated_tokens"] = outputs[i]
            row["num_input_tokens"] = prompt_lens[i]
            row["num_generated_tokens"] = len(outputs[i])
            row["batch_tokens_per_s"] = gen_tokens / dt
            row["engine_pid"] = self._pid
            if self._config.record_emit_order:
                row["emit_order"] = list(emit_order)
        return _rows_to_batch(rows)


class HttpRequestStage:
    """POST each row's payload to the configured URL (reference:
    stages/http_request_stage.py). The pool actor keeps a session-scoped
    opener; `qps` rate-limits across the batch."""

    def __init__(self, config: HttpRequestProcessorConfig):
        self._config = config
        self._last_request = 0.0

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        import json
        import urllib.request

        cfg = self._config
        rows = _rows(batch)
        for row in rows:
            if cfg.qps:
                wait = self._last_request + 1.0 / cfg.qps - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            self._last_request = time.monotonic()
            req = urllib.request.Request(
                cfg.url,
                data=json.dumps(row.get("payload", {})).encode(),
                headers={"Content-Type": "application/json", **cfg.headers},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=cfg.timeout_s) as resp:
                row["http_response"] = json.loads(resp.read().decode())
        return _rows_to_batch(rows)


# --------------------------------------------------------------------------
# processor
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _Stage:
    fn: type
    fn_args: tuple
    pooled: bool = False  # engine stages run on the ActorPool with resources


class Processor:
    """A reusable Dataset -> Dataset pipeline fragment (reference:
    processor/base.py Processor). Call it on a Dataset to append its stages."""

    def __init__(
        self,
        config: ProcessorConfig,
        stages: List[_Stage],
        preprocess: Optional[Callable[[Dict], Dict]] = None,
        postprocess: Optional[Callable[[Dict], Dict]] = None,
    ):
        self._config = config
        self._stages = stages
        self._preprocess = preprocess
        self._postprocess = postprocess

    def __call__(self, ds):
        from ray_tpu.data._executor import ActorPoolStrategy

        cfg = self._config
        if self._preprocess is not None:
            pre = self._preprocess
            # Reference wrap_preprocess: user output merges over the row, the
            # untouched columns carry through to postprocess.
            ds = ds.map(lambda row: {**row, **pre(row)})
        for stage in self._stages:
            compute = None
            if stage.pooled:
                # Resources must ride the pool strategy: ActorMapOperator
                # creates its actors from strategy.num_cpus/num_tpus, not from
                # map_batches' task-level remote args.
                res = cfg.accelerator_resources or {}
                compute = ActorPoolStrategy(
                    size=cfg.pool_size(),
                    num_cpus=float(res.get("CPU", 0)),
                    num_tpus=float(res.get("TPU", 0)),
                )
            ds = ds.map_batches(
                stage.fn,
                fn_args=stage.fn_args,
                batch_size=cfg.batch_size,
                compute=compute,
            )
        if self._postprocess is not None:
            post = self._postprocess
            ds = ds.map(lambda row: {**row, **post(row)})
        return ds

    @property
    def config(self) -> ProcessorConfig:
        return self._config


def build_llm_processor(
    config: ProcessorConfig,
    preprocess: Optional[Callable[[Dict], Dict]] = None,
    postprocess: Optional[Callable[[Dict], Dict]] = None,
) -> Processor:
    """Build a Processor for a config (reference: python/ray/data/llm.py
    build_llm_processor -> ProcessorBuilder.build dispatch)."""
    stages: List[_Stage] = []
    if isinstance(config, EngineProcessorConfig):
        if config.apply_chat_template:
            stages.append(_Stage(ChatTemplateStage, (config.tokenizer,)))
        if config.tokenize:
            stages.append(_Stage(TokenizeStage, (config.tokenizer,)))
        stages.append(_Stage(EngineStage, (config,), pooled=True))
        if config.detokenize:
            stages.append(_Stage(DetokenizeStage, (config.tokenizer,)))
    elif isinstance(config, HttpRequestProcessorConfig):
        stages.append(_Stage(HttpRequestStage, (config,), pooled=True))
    else:
        raise TypeError(f"unsupported processor config {type(config).__name__}")
    return Processor(config, stages, preprocess, postprocess)


__all__ = [
    "ProcessorConfig",
    "EngineProcessorConfig",
    "TPUEngineProcessorConfig",
    "HttpRequestProcessorConfig",
    "Processor",
    "build_llm_processor",
    "ChatTemplateStage",
    "TokenizeStage",
    "DetokenizeStage",
    "EngineStage",
    "HttpRequestStage",
]
