"""ray_tpu.llm: LLM serving on TPU replicas.

Parity: reference `python/ray/llm/` + `python/ray/serve/llm/__init__.py` — LLMConfig,
build_llm_deployment, build_openai_app (OpenAI-compatible /v1/completions +
/v1/chat/completions router). The engine is TPU-native continuous batching
(`_engine.py`) instead of a wrapped CUDA vLLM; replicas hold compiled prefill/decode
programs warm, so scaling replicas scales both throughput and compiled-state reuse.
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import pickle
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Union

from ray_tpu import serve
from ray_tpu.llm._engine import DecodeEngine, EngineOverloadedError, SamplingParams
from ray_tpu.llm.adapters import (
    AdapterCacheFullError,
    UnknownAdapterError,
)


class ByteTokenizer:
    """Default zero-dependency tokenizer: UTF-8 bytes as token ids (vocab >= 256).

    Real deployments plug a sentencepiece/BPE tokenizer via LLMConfig.tokenizer;
    the byte fallback keeps the stack runnable with zero downloads."""

    vocab_size = 256

    def encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: List[int]) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode("utf-8", errors="replace")

    def token_bytes(self, token_id: int) -> Optional[bytes]:
        """Exact byte rendering of one token (the guided-decoding byte-DFA
        keys its token masks on this; docs/generation.md). None marks an
        unrenderable id, which the mask then permanently disallows."""
        if 0 <= token_id < 256:
            return bytes([token_id])
        return None


class HFTokenizer:
    """Adapter over a HuggingFace tokenizer (encode/decode protocol)."""

    def __init__(self, name_or_path: str):
        from transformers import AutoTokenizer  # baked in; local paths work offline

        self._tok = AutoTokenizer.from_pretrained(name_or_path)
        self.vocab_size = self._tok.vocab_size

    def encode(self, text: str) -> List[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def decode(self, ids: List[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)


def resolve_tokenizer(tokenizer) -> Any:
    """None -> ByteTokenizer; str -> HF AutoTokenizer (model id or local path);
    anything with encode/decode passes through (reference: tokenizer plumbed via
    server_models.py LLMConfig.model_loading_config)."""
    if tokenizer is None:
        return ByteTokenizer()
    if isinstance(tokenizer, str):
        return HFTokenizer(tokenizer)
    return tokenizer


@dataclasses.dataclass
class LLMConfig:
    """Parity: reference `ray.serve.llm.LLMConfig` (server_models.py)."""

    model_id: str = "test-tiny"
    model_config: Optional[Any] = None  # ModelConfig; defaults to get_config(model_id)
    # Weight source: a dir with params.pkl, OR a committed sharded checkpoint
    # (ray_tpu.checkpoint manifest) — the warm-start path for DP replica
    # scale-up: every new replica reads only slice files, no pickle of the
    # whole tree through the object store. None -> random init.
    checkpoint_path: Optional[str] = None
    num_replicas: int = 1
    num_slots: int = 4            # continuous-batching slots per replica
    max_seq: Optional[int] = None
    tokenizer: Optional[Any] = None
    seed: int = 0
    accelerator_resources: Optional[dict] = None  # e.g. {"TPU": 4}
    # Multi-LoRA serving (reference: LoraConfig in server_models.py + vLLM
    # multi-LoRA): {"max_loras": N, "rank": r}. Adapters register at runtime via
    # LLMServer.load_lora and are selected per request with model="<id>:<adapter>".
    lora_config: Optional[dict] = None
    # Speculative decoding (docs/scheduler.md): e.g. {"method": "ngram",
    # "num_spec_tokens": 8} for the zero-FLOP retrieval draft, or
    # {"draft_layers": j} / {"draft_cfg": ..., "draft_params": ...} for a
    # cheap draft model sharing the target's embeddings. None disables.
    spec_config: Optional[dict] = None
    # Multi-tenant admission (docs/multitenancy.md): tenant -> WFQ weight
    # (priority classes; unlisted tenants weigh 1.0). wfq=False restores the
    # single arrival-order FIFO (the A/B control); tenant_quota overrides
    # llm_tenant_max_queue_depth per engine.
    tenant_weights: Optional[dict] = None
    wfq: bool = True
    tenant_quota: Optional[int] = None
    # Tensor parallelism (docs/serving_tp.md): each replica's engine shards
    # params + KV pool + adapter tables over a jax.sharding.Mesh of this
    # many devices (or a mesh-axes dict, e.g. {"tp": 4}); GSPMD partitions
    # every compiled program. Composes with num_replicas / dp_size into
    # DP x TP fleets; accelerator_resources are scaled per replica by the
    # builders so each replica's device gang is reserved atomically.
    tp: Any = 1


def model_config(config: "LLMConfig"):
    """The ModelConfig a config serves. An unknown model_id with no model_config
    raises (get_config): serving test-tiny under another model's name would look
    like a working replica."""
    from ray_tpu.models.transformer import get_config

    return config.model_config or get_config(config.model_id)


def engine_config(config: "LLMConfig"):
    """The ModelConfig a replica of `config` runs: the named one in the layout the engine's
    programs take (no scan over the layers, no remat)."""
    return dataclasses.replace(model_config(config), scan_layers=False, remat=False)


def load_model(config: "LLMConfig"):
    """Build (cfg, params) for a config — shared by monolithic and PD-disagg
    deployments. Without a checkpoint the tree is the block's own, at seeded
    random weights in `param_dtype`. A replica serves it in `dtype` (the engine casts
    what its programs multiply in `dtype`), so it hands the engine this call as a
    function (`lambda: load_model(config)[1]`) and keeps no tree of its own."""
    import jax

    from ray_tpu import models

    cfg = engine_config(config)
    if config.checkpoint_path:
        models.require(cfg, "checkpoint")
        from ray_tpu import checkpoint as ckpt_lib

        if ckpt_lib.is_sharded(config.checkpoint_path):
            # Sharded warm start (docs/checkpoint.md): slice files are read
            # directly (mmap) and only a committed manifest is accepted. A
            # train-plane save of {"params": ...} and a bare params save both
            # restore. TP configs stream every leaf straight to its mesh
            # layout through the resharding restore (docs/serving_tp.md) —
            # no host materialization of a tree that may not fit one chip.
            from ray_tpu.llm.tp import build_tp_mesh, checkpoint_shardings

            mesh = build_tp_mesh(config.tp)
            if mesh is not None:
                tree = ckpt_lib.restore(
                    config.checkpoint_path,
                    shardings=checkpoint_shardings(config.checkpoint_path, mesh),
                )
            else:
                tree = ckpt_lib.restore(config.checkpoint_path)
            params = tree.get("params", tree) if isinstance(tree, dict) else tree
        else:
            with open(os.path.join(config.checkpoint_path, "params.pkl"), "rb") as f:
                params = pickle.load(f)
    else:
        params = models.block_module(cfg).init_params(cfg, jax.random.PRNGKey(config.seed))
    return cfg, params


def replica_resources(config: "LLMConfig") -> dict:
    """Per-replica actor resource demand: each accelerator unit in
    `accelerator_resources` scales by the TP device count, so one replica's
    whole device gang is reserved atomically by the scheduler (DP x TP
    composition, docs/serving_tp.md). Cross-host gangs go through
    `cluster_utils.reserve_tp_slice` placement groups instead."""
    from ray_tpu.llm.tp import tp_device_count

    resources = dict(config.accelerator_resources or {})
    n_dev = tp_device_count(config.tp)
    if n_dev > 1 and resources:
        resources = {k: float(v) * n_dev for k, v in resources.items()}
    return resources


def replica_actor_options(config: "LLMConfig") -> dict:
    """`ray_actor_options` for one replica of any LLM deployment: the replica's
    accelerator demand as `resources=`, which is what the scheduler reads (a bare
    `TPU=` key among the options reserves nothing and is refused)."""
    resources = replica_resources(config)
    return {"num_cpus": resources.pop("CPU", 0), "resources": resources}


class LLMServer:
    """One TPU replica: engine + tokenizer. Parity: llm_server.py LLMServer."""

    def __init__(self, config: LLMConfig):
        cfg = engine_config(config)
        self._cfg = cfg
        self._config = config
        self._tokenizer = resolve_tokenizer(config.tokenizer)
        # Guided decoding (docs/generation.md): specs compile ONCE per
        # distinct schema/regex against this replica's tokenizer and model
        # vocab, then every request with the same spec reuses the DFA.
        from ray_tpu.llm.generate import ConstraintCompiler

        self._constraints = ConstraintCompiler(
            self._tokenizer, cfg.vocab_size
        )
        self._engine = DecodeEngine(
            cfg, lambda: load_model(config)[1], num_slots=config.num_slots,
            max_seq=config.max_seq or min(cfg.max_seq, 2048), seed=config.seed,
            lora_config=config.lora_config,
            spec_config=config.spec_config,
            wfq=config.wfq, tenant_weights=config.tenant_weights,
            tenant_quota=config.tenant_quota,
            tp=config.tp,
        )

    def weights(self):
        """(ModelConfig, parameter tree) this replica serves: the device arrays themselves,
        not copies, in the types the engine holds them (`cfg.dtype` wherever its programs
        multiply in it, whatever `load_model` gave). For scoring what the replica generated
        against a reference forward pass over the very weights it ran (the benchmark's
        `correct` for a block whose tree fills most of a chip, where a second copy would not
        fit beside it)."""
        return self._engine.cfg, self._engine.params

    async def load_lora(self, name: str, layer_weights: dict, alpha: float = 1.0) -> int:
        """Register a LoRA adapter on this replica (reference: LoRA checkpoints
        loaded per model id under Serve multiplexing)."""
        return self._engine.add_lora(name, layer_weights, alpha)

    async def generate(self, prompt: Union[str, List[int]], *,
                       max_tokens: int = 64, temperature: float = 0.0,
                       top_k: int = 0, stop_token_id: Optional[int] = None,
                       lora: str = "", tenant: Optional[str] = None,
                       route: Optional[str] = None,
                       guided=None) -> dict:
        t0 = time.monotonic()
        rid = uuid.uuid4().hex  # keys the engine's flight-recorder record
        constraint = self._constraints.get(guided) if guided is not None else None
        token_ids = (
            self._tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        loop = asyncio.get_running_loop()
        done: asyncio.Future = loop.create_future()
        out: List[int] = []
        ttft = [None]

        def cb(token: int, finished: bool):
            if ttft[0] is None:
                ttft[0] = time.monotonic() - t0
            out.append(token)
            if finished:
                loop.call_soon_threadsafe(
                    lambda: done.set_result(None) if not done.done() else None
                )

        self._engine.submit(
            token_ids,
            SamplingParams(max_tokens=max_tokens, temperature=temperature,
                           top_k=top_k, stop_token_id=stop_token_id),
            cb,
            lora=lora, tenant=tenant, request_id=rid, route=route,
            constraint=constraint,
        )
        await done
        gen = list(out)
        if stop_token_id is not None and gen and gen[-1] == stop_token_id:
            gen = gen[:-1]
        return {
            "text": self._tokenizer.decode(gen),
            "token_ids": gen,
            "usage": {
                "prompt_tokens": len(token_ids),
                "completion_tokens": len(gen),
                "total_tokens": len(token_ids) + len(gen),
            },
            "ttft_s": ttft[0],
            "latency_s": time.monotonic() - t0,
            # Flight-recorder phase breakdown (docs/observability.md):
            # queue/prefill/decode seconds, TTFT/TPOT, routing reason.
            "timing": self._engine.request_timing(rid),
        }

    async def generate_stream(self, prompt: Union[str, List[int]], *,
                              max_tokens: int = 64, temperature: float = 0.0,
                              top_k: int = 0, stop_token_id: Optional[int] = None,
                              lora: str = "", tenant: Optional[str] = None,
                              route: Optional[str] = None,
                              request_id: Optional[str] = None,
                              guided=None):
        """Async generator: yields text increments as tokens are decoded.

        SSE-ready: the OpenAI router maps each item to one `data:` event
        (reference: vllm_engine.py generate -> StreamingResponse path).
        Closing the generator mid-stream (client disconnect) cancels the
        engine request: GeneratorExit lands on the `await`, the finally
        closes the TokenStream, and close() retires the slot / releases
        leases within one scheduler iteration (docs/generation.md).
        """
        token_ids = (
            self._tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        constraint = self._constraints.get(guided) if guided is not None else None
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()

        def cb(token: int, finished: bool):
            loop.call_soon_threadsafe(queue.put_nowait, (token, finished))

        stream = self._engine.open_stream(
            token_ids,
            SamplingParams(max_tokens=max_tokens, temperature=temperature,
                           top_k=top_k, stop_token_id=stop_token_id),
            lora=lora, tenant=tenant, route=route, request_id=request_id,
            on_token=cb, constraint=constraint,
        )
        # Incremental detokenization with a short prefix window: deltas come
        # from decode(prefix + pending) minus decode(prefix), so tokenizers
        # whose rendering depends on context (sentencepiece leading-space
        # markers) stay correct across yield boundaries, without the O(N^2)
        # full-prefix decode. Held back while ending mid-codepoint so
        # multi-byte chars emit whole.
        PREFIX = 8
        emitted: List[int] = []
        sent = 0  # tokens already covered by yielded text
        try:
            while True:
                token, finished = await queue.get()
                if token >= 0 and not (
                    finished and stop_token_id is not None and token == stop_token_id
                ):
                    emitted.append(token)
                prefix = emitted[max(0, sent - PREFIX):sent]
                cur = self._tokenizer.decode(prefix + emitted[sent:])
                base = self._tokenizer.decode(prefix) if prefix else ""
                delta = cur[len(base):]
                if delta.endswith("�") and not finished:
                    pass  # mid-codepoint: hold until the remaining bytes arrive
                elif delta:
                    yield delta
                    sent = len(emitted)
                if finished:
                    return
        finally:
            # No-op after a clean finish; on disconnect/error this is the
            # cancel path that frees the slot and the constraint state.
            stream.close()

    async def model_id(self) -> str:
        return self._config.model_id

    async def cache_stats(self) -> Optional[dict]:
        """Paged KV prefix-cache counters for this replica's engine (None when
        the cache is disabled). See docs/kvcache.md."""
        return self._engine.prefix_cache_stats()

    # -- cluster-wide prefix plane (docs/kvcache.md) -----------------------
    async def export_prefix(self, token_ids: List[int],
                            lora: str = "") -> Optional[dict]:
        """EXPORT side of the cross-replica prefix fetch: lease this
        engine's longest cached whole-block prefix of token_ids, stream its
        KV rows through a DeviceChannel on a background thread (raw chunk
        frames, never a cloudpickled blob), and return the picklable reader
        end. The lease pins the chain until the send leg finishes (released
        in the pump's finally; leaksan-proved), so eviction can never free
        rows mid-transfer. None when nothing is cached."""
        loop = asyncio.get_running_loop()
        lease = await loop.run_in_executor(
            None, lambda: self._engine.lease_prefix(list(token_ids), lora)
        )
        if lease is None:
            return None
        from ray_tpu._private.worker import global_worker
        from ray_tpu.experimental.device_channel import DeviceChannel

        w = global_worker()
        owner = (
            ("actor", w.actor_id) if w.actor_id is not None
            else ("addr", (getattr(w, "node_ip", "127.0.0.1"),
                           w._direct_server.port))
        )
        ch = DeviceChannel.create(same_node=False, owner=owner)
        matched = lease.matched_tokens

        def pump():
            try:
                ch.send(lease.kv(), timeout=60.0)
                ch.drain(timeout=60.0)
            except Exception:
                pass  # reader died/skipped: the fetch degrades to a recompute
            finally:
                lease.release()
                ch.destroy()

        threading.Thread(
            target=pump, daemon=True, name="kv-prefix-export",
        ).start()
        return {"channel": ch, "matched_tokens": matched}

    async def import_prefix(self, desc: dict, token_ids: List[int],
                            lora: str = "") -> int:
        """IMPORT side of the cross-replica prefix fetch: drain the peer's
        stream and feed the rows into this engine's cache, so the request
        the router is about to send here prefills suffix-only. Returns
        blocks inserted (0 on any transfer failure — a failed fetch is a
        recompute, never an error)."""
        loop = asyncio.get_running_loop()

        def pull() -> int:
            try:
                kv = desc["channel"].recv(timeout=60.0)
            except Exception:
                return 0
            m = int(desc["matched_tokens"])
            return self._engine.insert_prefix(
                list(token_ids)[:m], kv, lora
            )

        return await loop.run_in_executor(None, pull)

    async def scheduler_stats(self) -> dict:
        """Iteration-level scheduler occupancy + spec-decode acceptance +
        per-tenant metering for this replica's engine. See docs/scheduler.md
        and docs/multitenancy.md."""
        return self._engine.scheduler_stats()

    async def adapter_stats(self) -> Optional[dict]:
        """AdapterCache residency/paging counters for this replica's engine
        (None without lora_config) — includes resident_adapters, the list
        the DP router's residency-affinity path keys on. See
        docs/multitenancy.md."""
        return self._engine.adapter_stats()

    async def recorder_stats(self) -> dict:
        """Flight-recorder counters for this replica's engine; the call is
        the report path that flushes pending SLO metrics and trace spans
        (docs/observability.md)."""
        return self._engine.recorder_stats()

    async def set_tenant_weight(self, tenant: str, weight: float) -> float:
        """Adaptive-WFQ actuator (docs/autoscale.md): the serve autopilot
        broadcasts adapted per-tenant weights here; the engine forwards to
        its scheduler's weighted-fair queues."""
        self._engine.set_tenant_weight(tenant, weight)
        return float(weight)

    async def autopilot_signals(self) -> dict:
        """The serve autopilot's per-replica signal probe (queue depth,
        occupancy, per-tenant SLO burn rates). Deployments whose replicas
        answer this become autopilot-managed; see docs/autoscale.md."""
        return self._engine.autopilot_signals()

    async def capture_profile(self, duration_s: float = 3.0,
                              log_dir: Optional[str] = None) -> dict:
        """On-demand profiler capture on this replica (the fleet surface
        `util.state.capture_profile` fans out to): runs jax.profiler trace
        capture for duration_s on an executor thread — the engine keeps
        serving — and returns the trace artifacts inline."""
        import asyncio

        from ray_tpu.util import xprof

        return await asyncio.get_running_loop().run_in_executor(
            None, lambda: xprof.capture(duration_s, log_dir)
        )

    async def shutdown(self):
        """Explicit retirement hook (the serve controller calls it, bounded,
        before the hard kill): stop the stepper and fail queued requests so
        blocked submitters unwind NOW instead of when GC notices."""
        self._engine.shutdown()

    def __del__(self):
        try:
            self._engine.shutdown()
        except Exception:
            pass


class OpenAIRouter:
    """OpenAI-compatible HTTP front: /v1/completions, /v1/chat/completions,
    /v1/models. Parity: reference serve/deployments/routers/router.py."""

    def __init__(self, servers: Dict[str, Any]):
        self._servers = servers  # model_id -> DeploymentHandle

    async def __call__(self, request):
        """Async generator ingress: one JSON item for regular calls, a stream of
        SSE `data:` events when the request sets "stream": true (reference:
        router.py -> StreamingResponse with text/event-stream)."""
        import json as _json

        path = request.path
        if path.endswith("/v1/models"):
            yield {"__serve_content_type__": "application/json"}
            yield {
                "object": "list",
                "data": [{"id": mid, "object": "model"} for mid in self._servers],
            }
            return
        body = request.json()
        model = body.get("model") or next(iter(self._servers))
        # "base-id:adapter" selects a LoRA adapter on the base model (the vLLM
        # multi-LoRA model-name convention the reference passes through).
        lora = ""
        base = model
        if model not in self._servers and ":" in model:
            base, lora = model.split(":", 1)
        handle = self._servers.get(base)
        if handle is None:
            yield {"__serve_content_type__": "application/json"}
            yield {"error": {"message": f"unknown model {model!r}",
                             "type": "invalid_request_error"}}
            return
        is_chat = path.endswith("/v1/chat/completions")
        if is_chat:
            prompt = "\n".join(
                f"{m.get('role', 'user')}: {m.get('content', '')}"
                for m in body.get("messages", [])
            ) + "\nassistant:"
        else:
            prompt = body.get("prompt", "")
        gen_kwargs = dict(
            max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            lora=lora,
        )
        # Guided decoding (docs/generation.md): OpenAI `response_format`
        # json_schema envelope, plus the vLLM-style guided_* extensions.
        guided = None
        rf = body.get("response_format")
        if isinstance(rf, dict) and rf.get("type") == "json_schema":
            guided = {"json_schema": rf.get("json_schema", {})}
        if body.get("guided_regex"):
            guided = {"regex": body["guided_regex"]}
        elif body.get("guided_json"):
            guided = {"json_schema": body["guided_json"]}
        elif body.get("guided_grammar") is not None:
            guided = {"grammar": body["guided_grammar"]}
        if guided is not None:
            gen_kwargs["guided"] = guided
        created = int(time.time())
        if body.get("stream"):
            yield {"__serve_content_type__": "text/event-stream"}
            rid = f"{'chatcmpl' if is_chat else 'cmpl'}-{uuid.uuid4().hex[:16]}"
            obj = "chat.completion.chunk" if is_chat else "text_completion"

            def sse(delta_text, finish_reason=None, first=False):
                if is_chat:
                    delta = {}
                    if first:
                        delta["role"] = "assistant"
                    if delta_text:
                        delta["content"] = delta_text
                    choice = {"index": 0, "delta": delta,
                              "finish_reason": finish_reason}
                else:
                    choice = {"index": 0, "text": delta_text or "",
                              "finish_reason": finish_reason}
                chunk = {"id": rid, "object": obj, "created": created,
                         "model": model, "choices": [choice]}
                return f"data: {_json.dumps(chunk)}\n\n"

            stream = handle.options(stream=True).generate_stream.remote(
                prompt, **gen_kwargs
            )
            try:
                first = True
                async for delta_text in stream:
                    yield sse(delta_text, first=first)
                    first = False
            except (KeyError, ValueError):
                yield sse("", finish_reason="error")
                yield "data: [DONE]\n\n"
                return
            finally:
                # Client disconnect raises GeneratorExit at the yield above;
                # closing the deployment stream propagates the cancel to the
                # replica so the decode slot frees (docs/generation.md).
                close = getattr(stream, "close", None)
                if close is not None:
                    close()
            yield sse("", finish_reason="length")
            yield "data: [DONE]\n\n"
            return
        response = handle.generate.remote(prompt, **gen_kwargs)
        try:
            result = await response
        except UnknownAdapterError as e:
            # Typed, client-visible rejection (docs/multitenancy.md): the
            # engine raised UnknownAdapterError and it rode the remote hop
            # intact — surface the registry's own message, not a guess.
            yield {"__serve_content_type__": "application/json"}
            yield {"error": {"message": str(e),
                             "type": "invalid_request_error",
                             "code": "unknown_adapter"}}
            return
        except KeyError:
            yield {"__serve_content_type__": "application/json"}
            yield {"error": {"message": f"unknown lora adapter in model {model!r}",
                             "type": "invalid_request_error"}}
            return
        except ValueError as e:
            # Guided-decoding compile rejections (SchemaError/PatternError/
            # GrammarError are ValueError subclasses) and other bad params.
            yield {"__serve_content_type__": "application/json"}
            yield {"error": {"message": str(e),
                             "type": "invalid_request_error",
                             "code": "guided_decoding"}}
            return
        yield {"__serve_content_type__": "application/json"}
        if is_chat:
            yield {
                "id": f"chatcmpl-{uuid.uuid4().hex[:16]}",
                "object": "chat.completion",
                "created": created,
                "model": model,
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant", "content": result["text"]},
                    "finish_reason": "length",
                }],
                "usage": result["usage"],
            }
            return
        yield {
            "id": f"cmpl-{uuid.uuid4().hex[:16]}",
            "object": "text_completion",
            "created": created,
            "model": model,
            "choices": [{"index": 0, "text": result["text"],
                         "finish_reason": "length"}],
            "usage": result["usage"],
        }


def build_llm_deployment(config: LLMConfig) -> "serve.Application":
    """One LLM server deployment. Parity: serve.llm.build_llm_deployment."""
    deployment = serve.deployment(
        name=f"LLMServer-{config.model_id}",
        num_replicas=config.num_replicas,
        ray_actor_options=replica_actor_options(config),
        max_ongoing_requests=config.num_slots * 4,
    )(LLMServer)
    return deployment.bind(config)


def build_openai_app(llm_configs: List[LLMConfig]) -> "serve.Application":
    """OpenAI-compatible app over one or more models. Parity:
    serve.llm.build_openai_app."""
    servers = {cfg.model_id: build_llm_deployment(cfg) for cfg in llm_configs}
    router = serve.deployment(name="OpenAIRouter")(OpenAIRouter)
    return router.bind(servers)


__all__ = [
    "AdapterCacheFullError",
    "ByteTokenizer",
    "DecodeEngine",
    "EngineOverloadedError",
    "HFTokenizer",
    "LLMConfig",
    "LLMServer",
    "OpenAIRouter",
    "SamplingParams",
    "UnknownAdapterError",
    "build_llm_deployment",
    "build_openai_app",
    "replica_actor_options",
    "replica_resources",
]
