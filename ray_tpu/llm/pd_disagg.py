"""Prefill/decode disaggregation: separate deployments for the two LLM phases.

Design parity: reference `python/ray/llm/_internal/serve/deployments/
prefill_decode_disagg/prefill_decode_disagg.py` — prefill replicas (compute-bound,
batch-friendly) and decode replicas (latency-bound, slot-limited) scale
independently; the prefill output KV cache transfers to a decode replica which
continues generation. The reference moves KV over NIXL/RDMA; here the prefill
replica pins the prefix as a device object and the decode replica pulls it
over a chunked DeviceChannel stream (round 11, docs/device_channels.md): a
shm ring intra-node, chunked RPC frames across nodes — raw buffers behind a
tiny pickled header, never a monolithic cloudpickled blob — with per-chunk
device staging on real accelerators so the attach overlaps the tail of the
transfer.
"""

from __future__ import annotations

import asyncio
import threading
import time
import uuid
from typing import Any, List, Optional, Union

from ray_tpu import models
from ray_tpu.llm import (
    ByteTokenizer, LLMConfig, SamplingParams, engine_config, load_model, model_config, resolve_tokenizer,
)
from ray_tpu.llm._engine import DecodeEngine


class PrefillServer:
    """Prefill-only replica: turns a prompt into (first_logits, KV prefix)."""

    def __init__(self, config: LLMConfig):
        models.require(model_config(config), "pd")  # before any weight is built
        cfg = engine_config(config)
        self._engine = DecodeEngine(
            cfg, lambda: load_model(config)[1], num_slots=1,
            max_seq=config.max_seq or min(cfg.max_seq, 2048), seed=config.seed,
            lora_config=config.lora_config, decode_loop=False,
            tp=config.tp,
        )

    async def prefill(self, token_ids: List[int], lora: str = "",
                      request_id: Optional[str] = None) -> dict:
        # The trace context is captured HERE (the activated task span) and
        # passed explicitly: prefill_detached runs on an executor thread,
        # where contextvars from this coroutine do not follow.
        from ray_tpu.util import tracing

        trace_ctx = tracing.current()
        loop = asyncio.get_running_loop()
        first_logits, kv, prompt_len = await loop.run_in_executor(
            None, lambda: self._engine.prefill_detached(
                token_ids, lora, request_id=request_id, trace_ctx=trace_ctx)
        )
        # The KV prefix stays pinned HERE as a refcounted device object; only
        # its tiny descriptor rides through the router. The decode replica
        # pulls the payload straight from this actor (no router data hop —
        # reference moves this over NIXL; the descriptor + direct pull is the
        # TPU-object-plane analog), and the pin evicts when the last
        # descriptor holder drops it.
        from ray_tpu.experimental import device_objects

        kv_ref = device_objects.put(kv)
        return {"first_logits": first_logits, "kv": kv_ref, "prompt_len": prompt_len}

    async def prefill_multicast(self, token_ids: List[int],
                                num_subscribers: int, lora: str = "",
                                request_id: Optional[str] = None) -> dict:
        """One prefill feeding a whole DECODE GROUP (docs/device_channels.md
        multicast): run the prefill once, then pump the KV prefix through a
        MulticastDeviceChannel on a background thread — ONE D2H pass fanned
        out to `num_subscribers` readers over the ring's per-subscriber
        acks, instead of N point-to-point streams re-staging the same bytes
        N times. A subscriber dead long enough to stall the ring is detached
        (stall unwind) so it can never wedge the writer or its siblings.
        Returns the picklable group descriptor; decode replica i passes
        {"group": ..., "subscriber": i} as generate_prefilled's kv."""
        from ray_tpu.util import tracing

        trace_ctx = tracing.current()
        loop = asyncio.get_running_loop()
        first_logits, kv, prompt_len = await loop.run_in_executor(
            None, lambda: self._engine.prefill_detached(
                token_ids, lora, request_id=request_id, trace_ctx=trace_ctx)
        )
        from ray_tpu.experimental.device_channel import MulticastDeviceChannel

        owner = None
        try:
            from ray_tpu._private.worker import global_worker

            w = global_worker()
            if w.actor_id is not None:
                owner = ("actor", w.actor_id)
        except RuntimeError:
            pass  # no cluster (engine-level use): shm ring, same node
        group = MulticastDeviceChannel.create(
            num_subscribers, same_node=owner is None, owner=owner,
        )

        def pump():
            try:
                group.send(kv, stall_timeout=30.0)
                group.drain(timeout=60.0)
            except Exception:
                pass  # every subscriber died: their generate calls surface it
            finally:
                group.destroy()

        threading.Thread(target=pump, daemon=True,
                         name="kv-multicast-pump").start()
        return {"first_logits": first_logits, "prompt_len": prompt_len,
                "group": group}

    async def load_lora(self, name: str, layer_weights: dict, alpha: float = 1.0):
        return self._engine.add_lora(name, layer_weights, alpha)

    async def cache_stats(self) -> Optional[dict]:
        return self._engine.prefix_cache_stats()

    async def scheduler_stats(self) -> dict:
        """Prefill-side admission/occupancy counters: the llm-stats surface
        must be whole on every deployed replica class (raylint RL1003) so
        fleet snapshots never AttributeError on one phase."""
        return self._engine.scheduler_stats()

    async def recorder_stats(self) -> dict:
        """Prefill-side flight-recorder report path: flushes this engine's
        pending trace spans (docs/observability.md)."""
        return self._engine.recorder_stats()

    async def set_tenant_weight(self, tenant: str, weight: float) -> float:
        """Adaptive-WFQ actuator: prefill admission shares the tenant
        weights. Required because this class answers autopilot_signals —
        the autopilot broadcasts weight updates to every replica of a
        managed deployment (docs/autoscale.md)."""
        self._engine.set_tenant_weight(tenant, weight)
        return float(weight)

    async def capture_profile(self, duration_s: float = 3.0,
                              log_dir: Optional[str] = None) -> dict:
        """On-demand profiler capture on this prefill replica (the fleet
        capture fan-out reaches both PD phases)."""
        loop = asyncio.get_running_loop()
        from ray_tpu.util import xprof

        return await loop.run_in_executor(
            None, lambda: xprof.capture(duration_s, log_dir)
        )

    async def autopilot_signals(self) -> dict:
        """Autopilot probe; the prefill role marks this pool as the TTFT
        side of the P:D rebalance law (docs/autoscale.md)."""
        sig = self._engine.autopilot_signals()
        sig["role"] = "prefill"
        return sig

    async def shutdown(self):
        """Explicit retirement hook for the serve controller's retire path."""
        self._engine.shutdown()

    def __del__(self):
        try:
            self._engine.shutdown()
        except Exception:
            pass


class DecodeServer:
    """Decode-only replica: continues generation from a transferred KV prefix."""

    def __init__(self, config: LLMConfig):
        models.require(model_config(config), "pd")  # before any weight is built
        cfg = engine_config(config)
        self._tokenizer = resolve_tokenizer(config.tokenizer)
        self._engine = DecodeEngine(
            cfg, lambda: load_model(config)[1], num_slots=config.num_slots,
            max_seq=config.max_seq or min(cfg.max_seq, 2048), seed=config.seed,
            lora_config=config.lora_config,
            # Transferred prefixes arrive with token_ids, so decode-side spec
            # decoding stays live: the draft catches up on the token history
            # instead of downgrading to plain decode (docs/scheduler.md).
            spec_config=config.spec_config,
            tp=config.tp,
        )

    def _guided_constraint(self, guided):
        """Compile (or cache-hit) a guided-decoding spec against this decode
        engine's tokenizer/vocab — the constraint masks decode-side sampling
        and spec-verify exactly as in the colocated engine
        (docs/generation.md)."""
        if guided is None:
            return None
        compiler = getattr(self, "_constraints", None)
        if compiler is None:
            from ray_tpu.llm.generate import ConstraintCompiler

            compiler = self._constraints = ConstraintCompiler(
                self._tokenizer, self._engine.cfg.vocab_size
            )
        return compiler.get(guided)

    async def _pull_kv(self, kv):
        """Resolve the transferred KV prefix (multicast subscription or
        point-to-point DeviceObjectRef pull) to device/host rows.
        Returns (kv, transfer_s)."""
        loop = asyncio.get_running_loop()
        from ray_tpu.experimental.device_objects import DeviceObjectRef, get as dev_get

        transfer_s = None
        if isinstance(kv, dict) and "group" in kv:
            # Multicast PD handoff (docs/device_channels.md): this replica is
            # subscriber i of the prefill's one-writer fanout group — it
            # reads the SAME staged chunk frames as its siblings (the writer
            # paid one D2H pass for the whole group). The subscription is
            # released in a finally: an unsubscribed-on-error reader detaches
            # from ring back-pressure, so a crashing decode replica can't
            # wedge the writer or the other subscribers.
            import jax

            to_device = jax.default_backend() != "cpu"
            kv_sharding = self._engine.kv_transfer_sharding if to_device else None
            group, index = kv["group"], int(kv["subscriber"])
            sub = group.subscribe(index)
            t_pull = time.monotonic()
            try:
                kv = await loop.run_in_executor(
                    None,
                    lambda: (
                        sub.recv_device(timeout=120.0, sharding=kv_sharding)
                        if to_device else sub.recv(timeout=120.0)
                    ),
                )
            finally:
                sub.unsubscribe()
            transfer_s = time.monotonic() - t_pull
        elif isinstance(kv, DeviceObjectRef):
            # Pull the KV prefix peer-to-peer from the prefill replica over
            # the chunked DeviceChannel stream. On real accelerators each
            # chunk is device_put as it arrives, so the H2D leg of the attach
            # overlaps the tail of the wire transfer and submit_prefilled
            # receives a device-resident prefix; on the CPU backend the host
            # assembly IS the attach staging, and the engine's one
            # jnp.asarray aliases it. The pin on the prefill replica releases
            # when the ROUTER drops its reply reference (the descriptor in
            # `pre`) after generate() returns — this call's borrowed arg
            # holds it only transiently.
            import jax

            to_device = jax.default_backend() != "cpu"
            kv_ref = kv
            # TP decode engines hand the stream their kv-head sharding: each
            # arriving shard stages straight onto ITS device (per-shard H2D),
            # so a mesh-sharded prefix is never gathered whole anywhere —
            # the no-gather-then-scatter half of the sharded PD handoff
            # (docs/serving_tp.md; the prefill side streams per shard).
            kv_sharding = self._engine.kv_transfer_sharding if to_device else None
            t_pull = time.monotonic()
            kv = await loop.run_in_executor(
                None, lambda: dev_get(kv_ref, to_device=to_device,
                                      sharding=kv_sharding)
            )
            transfer_s = time.monotonic() - t_pull  # the PD KV handoff leg
        return kv, transfer_s

    async def generate_prefilled(self, kv, prompt_len: int, first_logits, *,
                                 max_tokens: int = 64, temperature: float = 0.0,
                                 top_k: int = 0, stop_token_id: Optional[int] = None,
                                 lora: str = "",
                                 token_ids: Optional[List[int]] = None,
                                 request_id: Optional[str] = None,
                                 guided=None) -> dict:
        loop = asyncio.get_running_loop()
        kv, transfer_s = await self._pull_kv(kv)
        done: asyncio.Future = loop.create_future()
        out: List[int] = []

        def cb(token: int, finished: bool):
            out.append(token)
            if finished:
                loop.call_soon_threadsafe(
                    lambda: done.set_result(None) if not done.done() else None
                )

        rid = request_id or uuid.uuid4().hex
        self._engine.submit_prefilled(
            kv, prompt_len, first_logits,
            SamplingParams(max_tokens=max_tokens, temperature=temperature,
                           top_k=top_k, stop_token_id=stop_token_id),
            cb, lora=lora, token_ids=token_ids,
            request_id=rid, transfer_s=transfer_s,
            constraint=self._guided_constraint(guided),
        )
        await done
        gen = list(out)
        if stop_token_id is not None and gen and gen[-1] == stop_token_id:
            gen = gen[:-1]
        return {"token_ids": gen, "text": self._tokenizer.decode(gen),
                "timing": self._engine.request_timing(rid)}

    async def generate_prefilled_stream(self, kv, prompt_len: int,
                                        first_logits, *,
                                        max_tokens: int = 64,
                                        temperature: float = 0.0,
                                        top_k: int = 0,
                                        stop_token_id: Optional[int] = None,
                                        lora: str = "",
                                        token_ids: Optional[List[int]] = None,
                                        request_id: Optional[str] = None,
                                        guided=None):
        """Streaming twin of generate_prefilled: pulls the transferred KV
        prefix, then yields text deltas per decoded token
        (docs/generation.md). Closing the generator mid-stream cancels the
        decode slot via the engine's cancel plane — the finally closes the
        TokenStream, and the multicast/point-to-point pull already completed
        (its subscription released) before the first yield."""
        loop = asyncio.get_running_loop()
        kv, transfer_s = await self._pull_kv(kv)
        queue: asyncio.Queue = asyncio.Queue()

        def cb(token: int, finished: bool):
            loop.call_soon_threadsafe(queue.put_nowait, (token, finished))

        rid = request_id or uuid.uuid4().hex
        from ray_tpu.llm.generate import TokenStream

        stream = TokenStream(self._engine, rid, on_token=cb)
        try:
            self._engine.submit_prefilled(
                kv, prompt_len, first_logits,
                SamplingParams(max_tokens=max_tokens, temperature=temperature,
                               top_k=top_k, stop_token_id=stop_token_id),
                stream._push, lora=lora, token_ids=token_ids,
                request_id=rid, transfer_s=transfer_s,
                constraint=self._guided_constraint(guided),
            )
        except Exception:
            # Rejected at admission: nothing to cancel engine-side.
            stream._finished.set()
            stream.close()
            raise
        # Same incremental-detokenization window as LLMServer.generate_stream.
        PREFIX = 8
        emitted: List[int] = []
        sent = 0
        try:
            while True:
                token, finished = await queue.get()
                if token >= 0 and not (
                    finished and stop_token_id is not None
                    and token == stop_token_id
                ):
                    emitted.append(token)
                prefix = emitted[max(0, sent - PREFIX):sent]
                cur = self._tokenizer.decode(prefix + emitted[sent:])
                base = self._tokenizer.decode(prefix) if prefix else ""
                delta = cur[len(base):]
                if delta.endswith("�") and not finished:
                    pass
                elif delta:
                    yield delta
                    sent = len(emitted)
                if finished:
                    return
        finally:
            stream.close()

    async def load_lora(self, name: str, layer_weights: dict, alpha: float = 1.0):
        return self._engine.add_lora(name, layer_weights, alpha)

    async def cache_stats(self) -> Optional[dict]:
        return self._engine.prefix_cache_stats()

    async def scheduler_stats(self) -> dict:
        return self._engine.scheduler_stats()

    async def recorder_stats(self) -> dict:
        """Decode-side flight-recorder report path: flushes pending SLO
        metrics and trace spans (docs/observability.md)."""
        return self._engine.recorder_stats()

    async def set_tenant_weight(self, tenant: str, weight: float) -> float:
        """Adaptive-WFQ actuator on the decode pool (the phase that owns
        the weighted-fair queues)."""
        self._engine.set_tenant_weight(tenant, weight)
        return float(weight)

    async def autopilot_signals(self) -> dict:
        """Autopilot probe; the decode role marks this pool as the TPOT
        side of the P:D rebalance law (docs/autoscale.md)."""
        sig = self._engine.autopilot_signals()
        sig["role"] = "decode"
        return sig

    async def capture_profile(self, duration_s: float = 3.0,
                              log_dir: Optional[str] = None) -> dict:
        """On-demand profiler capture on this decode replica — completes the
        llm-stats surface so the fleet capture fan-out covers the TPOT
        phase too."""
        loop = asyncio.get_running_loop()
        from ray_tpu.util import xprof

        return await loop.run_in_executor(
            None, lambda: xprof.capture(duration_s, log_dir)
        )

    async def shutdown(self):
        """Explicit retirement hook: stops the stepper and fails queued
        requests, so a decode replica retired mid-stream unblocks its
        in-flight generate_prefilled() callers instead of stranding them."""
        self._engine.shutdown()

    def __del__(self):
        try:
            self._engine.shutdown()
        except Exception:
            pass


class PDRouter:
    """Request path: tokenize -> prefill replica -> KV transfer -> decode replica."""

    def __init__(self, prefill_handle, decode_handle, config: LLMConfig):
        from collections import deque

        from ray_tpu._private.config import CONFIG

        self._prefill = prefill_handle
        self._decode = decode_handle
        self._tokenizer = resolve_tokenizer(config.tokenizer)
        self._model_id = config.model_id
        # Phase-pressure samples for the autopilot's P:D rebalance law
        # (docs/autoscale.md): bounded deques of (prefill_s / TTFT SLO) and
        # (decode TPOT / TPOT SLO) — plain appends on the request path, read
        # only from the autopilot_signals report probe.
        self._slo_ttft_s = max(1e-9, CONFIG.llm_slo_ttft_s)
        self._slo_tpot_s = max(1e-9, CONFIG.llm_slo_tpot_s)
        self._ttft_samples: deque = deque(maxlen=128)
        self._tpot_samples: deque = deque(maxlen=128)

    async def generate(self, prompt: Union[str, List[int]], *,
                       max_tokens: int = 64, temperature: float = 0.0,
                       top_k: int = 0, stop_token_id: Optional[int] = None,
                       lora: str = "", guided=None) -> dict:
        t0 = time.monotonic()
        # One request id spans both phases: the prefill-side and decode-side
        # flight records share it (and the caller's trace), so a PD request
        # renders as one span tree across the two replica processes.
        rid = uuid.uuid4().hex
        token_ids = (
            self._tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        pre = await self._prefill.prefill.remote(token_ids, lora,
                                                 request_id=rid)
        t_prefill = time.monotonic() - t0
        result = await self._decode.generate_prefilled.remote(
            pre["kv"], pre["prompt_len"], pre["first_logits"],
            max_tokens=max_tokens, temperature=temperature, top_k=top_k,
            stop_token_id=stop_token_id, lora=lora, guided=guided,
            # The prompt rides along so the decode engine can feed its prefix
            # cache with the transferred rows (docs/kvcache.md).
            token_ids=token_ids, request_id=rid,
        )
        latency_s = time.monotonic() - t0
        self._note_pd_sample(t_prefill, latency_s, len(result["token_ids"]))
        return {
            **result,
            "usage": {
                "prompt_tokens": len(token_ids),
                "completion_tokens": len(result["token_ids"]),
                "total_tokens": len(token_ids) + len(result["token_ids"]),
            },
            "prefill_s": t_prefill,
            "latency_s": latency_s,
        }

    async def generate_stream(self, prompt: Union[str, List[int]], *,
                              max_tokens: int = 64, temperature: float = 0.0,
                              top_k: int = 0,
                              stop_token_id: Optional[int] = None,
                              lora: str = "", guided=None):
        """Streaming PD path: prefill as usual, then per-token text deltas
        stream from the decode pool (docs/generation.md). The prefill/KV
        handoff completes before the first delta (TTFT covers it); closing
        this generator mid-stream rides the serve cancel plane down to the
        decode replica, which frees the slot within one scheduler iteration.
        Phase-pressure samples land like generate()'s, with the delta count
        standing in for the completion token count."""
        t0 = time.monotonic()
        rid = uuid.uuid4().hex
        token_ids = (
            self._tokenizer.encode(prompt) if isinstance(prompt, str)
            else list(prompt)
        )
        pre = await self._prefill.prefill.remote(token_ids, lora,
                                                 request_id=rid)
        t_prefill = time.monotonic() - t0
        stream = self._decode.options(
            stream=True
        ).generate_prefilled_stream.remote(
            pre["kv"], pre["prompt_len"], pre["first_logits"],
            max_tokens=max_tokens, temperature=temperature, top_k=top_k,
            stop_token_id=stop_token_id, lora=lora, guided=guided,
            token_ids=token_ids, request_id=rid,
        )
        chunks = 0
        try:
            async for delta in stream:
                chunks += 1
                yield delta
        finally:
            stream.close()
            self._note_pd_sample(t_prefill, time.monotonic() - t0,
                                 max(1, chunks))

    def _note_pd_sample(self, prefill_s: float, latency_s: float,
                        completion_tokens: int):
        """Record one request's phase pressures (plain deque appends)."""
        self._ttft_samples.append(prefill_s / self._slo_ttft_s)
        tpot = (latency_s - prefill_s) / max(1, completion_tokens)
        self._tpot_samples.append(tpot / self._slo_tpot_s)

    async def autopilot_signals(self) -> dict:
        """Autopilot probe: TTFT-vs-TPOT pressure for the P:D rebalance law
        (pressure 1.0 = that phase is exactly at its SLO component)."""
        ttft = list(self._ttft_samples)
        tpot = list(self._tpot_samples)
        return {
            "role": "pd_router",
            "queued": 0,
            "running": 0,
            "ttft_pressure": sum(ttft) / len(ttft) if ttft else 0.0,
            "tpot_pressure": sum(tpot) / len(tpot) if tpot else 0.0,
            "samples": len(ttft),
        }

    async def generate_multicast(self, prompt: Union[str, List[int]], *,
                                 max_tokens: int = 64,
                                 temperature: float = 0.0, top_k: int = 0,
                                 stop_token_id: Optional[int] = None,
                                 lora: str = "") -> dict:
        """One prefill feeding EVERY decode replica (speculative group
        decode / fanout evaluation): the prefill replica streams the KV
        prefix through a multicast group — one D2H pass total — and each
        decode replica continues generation from its own subscription.
        Returns the per-replica results (token-identical under greedy
        sampling: every replica attaches bit-identical rows)."""
        import ray_tpu

        t0 = time.monotonic()
        rid = uuid.uuid4().hex
        token_ids = (
            self._tokenizer.encode(prompt) if isinstance(prompt, str)
            else list(prompt)
        )
        router = self._decode.generate_prefilled._get_router()
        replicas = router.replicas()
        if not replicas:
            raise RuntimeError("no decode replicas to multicast to")
        pre = await self._prefill.prefill_multicast.remote(
            token_ids, len(replicas), lora, request_id=rid,
        )
        loop = asyncio.get_running_loop()
        kwargs = dict(
            max_tokens=max_tokens, temperature=temperature, top_k=top_k,
            stop_token_id=stop_token_id, lora=lora, token_ids=token_ids,
        )
        refs = [
            r.handle_request.remote(
                "generate_prefilled",
                ({"group": pre["group"], "subscriber": i},
                 pre["prompt_len"], pre["first_logits"]),
                {**kwargs, "request_id": f"{rid}-{i}"},
            )
            for i, r in enumerate(replicas)
        ]
        results = await loop.run_in_executor(
            None, lambda: [ray_tpu.get(ref, 300) for ref in refs]
        )
        return {
            "results": results,
            "replicas": len(replicas),
            "prompt_tokens": len(token_ids),
            "latency_s": time.monotonic() - t0,
        }

    async def __call__(self, request) -> dict:
        body = request.json() if hasattr(request, "json") else dict(request)
        model = body.get("model", "")
        lora = model.split(":", 1)[1] if ":" in model else ""
        stop = body.get("stop_token_id")
        try:
            return await self.generate(
                body.get("prompt", ""),
                max_tokens=int(body.get("max_tokens", 64)),
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                stop_token_id=None if stop is None else int(stop),
                lora=lora,
            )
        except KeyError as e:
            return {"error": {"message": f"unknown lora adapter {e}",
                              "type": "invalid_request_error"}}

    async def recorder_stats(self) -> dict:
        """Flight-recorder stats from BOTH phases' replica pools; the
        broadcast is the report path that flushes each engine's pending
        trace spans and SLO metrics (docs/observability.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None,
            lambda: {
                "prefill": self._prefill.recorder_stats.broadcast(),
                "decode": self._decode.recorder_stats.broadcast(),
            },
        )

    async def scheduler_stats(self) -> dict:
        """Decode-pool scheduler stats (the phase that owns slots/queues)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: {"decode": self._decode.scheduler_stats.broadcast()}
        )

    async def cache_stats(self) -> dict:
        """Prefix-cache counters from BOTH phases' replica pools (the PD
        view of where prefixes live: computed on prefill, fed forward into
        decode)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None,
            lambda: {
                "prefill": self._prefill.cache_stats.broadcast(),
                "decode": self._decode.cache_stats.broadcast(),
            },
        )

    async def set_tenant_weight(self, tenant: str, weight: float) -> float:
        """Fan one tenant's adapted WFQ weight out to both phases. Required
        because this router answers autopilot_signals (the P:D pressure
        probe): managed deployments receive the autopilot's weight
        broadcasts (docs/autoscale.md)."""
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None,
            lambda: (
                self._prefill.set_tenant_weight.broadcast(tenant, weight),
                self._decode.set_tenant_weight.broadcast(tenant, weight),
            ),
        )
        return float(weight)

    async def capture_profile(self, duration_s: float = 3.0) -> dict:
        """Fan a profiler capture out to both phases' replicas and gather
        the trace artifacts per pool (docs/observability.md)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None,
            lambda: {
                "prefill": self._prefill.capture_profile.broadcast(duration_s),
                "decode": self._decode.capture_profile.broadcast(duration_s),
            },
        )

    async def load_lora(self, name: str, layer_weights: dict, alpha: float = 1.0):
        """Install an adapter on EVERY replica of both phases (they must agree on
        factors). Replicas created after this call need a re-broadcast."""
        import asyncio as _asyncio

        loop = _asyncio.get_running_loop()
        await loop.run_in_executor(
            None,
            lambda: (
                self._prefill.load_lora.broadcast(name, layer_weights, alpha),
                self._decode.load_lora.broadcast(name, layer_weights, alpha),
            ),
        )
        return True


def build_pd_openai_app(config: LLMConfig, *, num_prefill: int = 1,
                        num_decode: int = 1) -> "Any":
    """Disaggregated serving app (reference: build_pd_openai_app in
    prefill_decode_disagg.py): independent prefill and decode replica pools
    behind one router. With `config.tp > 1` both pools run mesh-sharded
    engines and each replica's accelerator demand scales by the TP device
    count (docs/serving_tp.md)."""
    from ray_tpu import serve
    from ray_tpu.llm import replica_actor_options

    prefill = serve.deployment(
        name=f"Prefill-{config.model_id}",
        num_replicas=num_prefill,
        ray_actor_options=replica_actor_options(config),
    )(PrefillServer)
    decode = serve.deployment(
        name=f"Decode-{config.model_id}",
        num_replicas=num_decode,
        ray_actor_options=replica_actor_options(config),
        max_ongoing_requests=config.num_slots * 4,
    )(DecodeServer)
    router = serve.deployment(name=f"PDRouter-{config.model_id}")(PDRouter)
    return router.bind(prefill.bind(config), decode.bind(config), config)
