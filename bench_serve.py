"""Serving benchmark: TTFT + decode throughput of the TPU decode engine.

Measures the BASELINE.md "Serve LLM tokens/s + TTFT" north star directly on the
continuous-batching engine (`ray_tpu/llm/_engine.py`) — no cluster in the
measurement path, so the numbers are the engine's own ceiling:

- TTFT: submit -> first token on a warm engine (compiled prefill bucket),
  single request, empty batch (the latency-bound regime).
- decode tokens/s at concurrency 1/2/4/8: all requests in flight together
  through the slot scheduler; total generated tokens / wall time.
- mixed traffic (docs/scheduler.md): long prompts injected into 4 live
  decode streams, with the iteration-level scheduler's chunked prefill ON
  (token budget) vs OFF (legacy whole-prompt admission) — measures injected
  TTFT p50/p99 and the decode streams' inter-token stall (TPOT p99 / max)
  during the injection window. Chunked prefill must bound the stall.
- speculative decoding at concurrency 1 on a repeated-traffic workload
  (ngram/REST retrieval draft, docs/scheduler.md): reports tokens/s,
  speedup vs the plain engine on the SAME workload, and the measured
  acceptance rate (realistic: the first pass misses, repeats hit).
- prefix-cache warm vs cold TTFT on a repeated-prefix workload (shared
  system prompt + unique tails): a warm hit attaches cached KV blocks and
  prefills suffix-only (docs/kvcache.md), so warm TTFT must sit strictly
  below cold; hit-rate and prefill-bucket columns verify the mechanism.

Writes BENCH_SERVE.json: a list of measurement dicts + environment metadata.
"""

from __future__ import annotations

import json
import threading
import time


def build_engine(spec: bool = False, slots: int = 8, **kw):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import LLMConfig, load_model
    from ray_tpu.llm._engine import DecodeEngine

    on_tpu = jax.default_backend() == "tpu"
    model_id = "gpt2-125m" if on_tpu else "test-tiny"
    cfg, params = load_model(LLMConfig(model_id=model_id))
    max_seq = kw.pop("max_seq", 1024 if on_tpu else 128)
    spec_config = kw.pop("spec_config", None)
    if spec and spec_config is None:
        spec_config = {"draft_cfg": cfg, "draft_params": params,
                       "num_spec_tokens": 6}
    engine = DecodeEngine(
        cfg, params, num_slots=slots, max_seq=max_seq, seed=0,
        spec_config=spec_config, **kw,
    )
    return engine, cfg, model_id, on_tpu


def run_requests(engine, vocab: int, n: int, prompt_len: int, max_tokens: int):
    """Submit n concurrent requests; returns (ttft_first_req_s, tokens/s, total)."""
    from ray_tpu.llm._engine import SamplingParams

    import numpy as np

    rng = np.random.default_rng(0)
    done = [threading.Event() for _ in range(n)]
    first_token_t = [None] * n
    counts = [0] * n
    t0 = time.perf_counter()

    def cb_for(i):
        def cb(token, finished):
            if first_token_t[i] is None:
                first_token_t[i] = time.perf_counter() - t0
            counts[i] += 1
            if finished:
                done[i].set()

        return cb

    for i in range(n):
        prompt = rng.integers(0, vocab, prompt_len).tolist()
        engine.submit(prompt, SamplingParams(max_tokens=max_tokens), cb_for(i))
    for ev in done:
        if not ev.wait(timeout=600):
            raise TimeoutError("generation did not finish")
    elapsed = time.perf_counter() - t0
    total = sum(counts)
    return first_token_t[0], total / elapsed, total


def _pctl(values, q):
    xs = sorted(values)
    if not xs:
        return 0.0
    idx = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[idx]


def bench_mixed_traffic(token_budget: int, on_tpu: bool):
    """Inject long prefills into live decode streams and measure the damage.

    4 background streams decode steadily; once they are flowing, 4 long
    prompts are submitted together (concurrency 4 prefill + 4 decode).
    Reported: injected-request TTFT p50/p99, and the background streams'
    inter-token gap (TPOT) p99/max during the injection window. With
    token_budget=0 every prefill runs whole-prompt before decode resumes
    (the request-at-a-time cliff); with a budget the scheduler interleaves
    bucketed chunks with decode, bounding the stall (docs/scheduler.md).
    """
    import numpy as np

    from ray_tpu.llm import SamplingParams

    max_seq = 1024 if on_tpu else 512
    long_len = 768 if on_tpu else 384
    engine, cfg, model_id, _ = build_engine(
        slots=8, max_seq=max_seq, token_budget=token_budget,
        prefix_cache=False,
    )
    rng = np.random.default_rng(0)
    try:
        # Warm every program off-clock: the long-prompt chunk/whole buckets
        # and the decode/multi-step programs.
        warm_done = threading.Event()
        engine.submit(
            rng.integers(0, cfg.vocab_size, long_len).tolist(),
            SamplingParams(max_tokens=16),
            lambda t, fin: warm_done.set() if fin else None,
        )
        assert warm_done.wait(600)

        n_streams, n_inject = 4, 4
        stream_times = [[] for _ in range(n_streams)]
        stream_done = [threading.Event() for _ in range(n_streams)]

        def stream_cb(i):
            def cb(tok, fin):
                stream_times[i].append(time.perf_counter())
                if fin:
                    stream_done[i].set()
            return cb

        for i in range(n_streams):
            engine.submit(
                rng.integers(0, cfg.vocab_size, 16).tolist(),
                SamplingParams(max_tokens=160), stream_cb(i),
            )
        while min(len(t) for t in stream_times) < 8:  # streams flowing
            time.sleep(0.001)

        inject_t0 = time.perf_counter()
        ttfts = [None] * n_inject
        inject_done = [threading.Event() for _ in range(n_inject)]

        def inject_cb(i):
            def cb(tok, fin):
                if ttfts[i] is None:
                    ttfts[i] = time.perf_counter() - inject_t0
                if fin:
                    inject_done[i].set()
            return cb

        for i in range(n_inject):
            engine.submit(
                rng.integers(0, cfg.vocab_size, long_len).tolist(),
                SamplingParams(max_tokens=2), inject_cb(i),
            )
        for ev in inject_done:
            assert ev.wait(600)
        window_end = time.perf_counter()
        for ev in stream_done:
            assert ev.wait(600)

        gaps = []
        for times in stream_times:
            in_window = [t for t in times if inject_t0 <= t <= window_end]
            gaps.extend(b - a for a, b in zip(in_window, in_window[1:]))
        stats = engine.scheduler_stats()
        return {
            "metric": "mixed_traffic",
            "token_budget": token_budget,
            "prefill_concurrency": n_inject,
            "decode_concurrency": n_streams,
            "long_prompt_len": long_len,
            "ttft_p50_s": round(_pctl(ttfts, 0.5), 4),
            "ttft_p99_s": round(_pctl(ttfts, 0.99), 4),
            "decode_tpot_p99_s": round(_pctl(gaps, 0.99), 4),
            "decode_stall_max_s": round(max(gaps), 4) if gaps else 0.0,
            "prefill_chunks": stats["prefill_chunks"],
            "interleaved_iterations": stats["interleaved_iterations"],
            "model": model_id,
        }
    finally:
        engine.shutdown()


def bench_spec_decode(on_tpu: bool):
    """Speculative decoding on a repeated-traffic workload (concurrency 1).

    The ngram/REST retrieval draft proposes continuations remembered from
    earlier requests; greedy decode is deterministic, so repeats verify at
    high (but NOT all-accept — the first pass misses) acceptance with ZERO
    draft FLOPs, and one batched verify emits up to k+1 tokens per
    dispatch. The plain engine runs the SAME two-pass workload with its
    multi-step decode fully engaged — this is the honest baseline the old
    self-draft bench lost to (speedup 0.85)."""
    import numpy as np

    from ray_tpu.llm import SamplingParams

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, 32).tolist() for _ in range(4)]
    max_tokens = 64

    def run_pass(engine):
        total, t0 = 0, time.perf_counter()
        for p in prompts:
            done = threading.Event()
            count = [0]

            def cb(tok, fin):
                count[0] += 1
                if fin:
                    done.set()

            engine.submit(p, SamplingParams(max_tokens=max_tokens), cb)
            assert done.wait(600)
            total += count[0]
        return total, time.perf_counter() - t0

    results = {}
    model_id = None
    for mode in ("plain", "spec"):
        kw = {"prefix_cache": False}
        if mode == "spec":
            kw["spec_config"] = {"method": "ngram", "num_spec_tokens": 32}
        engine, _cfg, model_id, _ = build_engine(slots=4, **kw)
        try:
            run_pass(engine)                  # warm + build the draft store
            total, elapsed = run_pass(engine)  # measured: repeated traffic
            results[mode] = total / elapsed
            if mode == "spec":
                spec_stats = engine.scheduler_stats()["spec"]
        finally:
            engine.shutdown()
    return {
        "metric": "decode_tokens_per_s_specdecode",
        "concurrency": 1,
        "value": round(results["spec"], 1),
        "plain_tokens_per_s": round(results["plain"], 1),
        "speedup_vs_plain": round(results["spec"] / results["plain"], 2),
        "acceptance_rate": round(spec_stats["accept_rate"], 3),
        "spec_rounds": spec_stats["rounds"],
        "model": model_id,
        "note": "ngram/REST retrieval draft k=32, repeated-traffic workload "
                "(2 passes x 4 prompts; acceptance includes the cold pass); "
                "plain baseline runs multi-step decode on the same workload",
    }


def bench_prefix_cache(prompt_len: int):
    """Warm vs cold TTFT for a shared-prefix workload (docs/kvcache.md).

    Requests share a 5-block system-prompt prefix and differ in an 8-token
    tail. The first request prefills everything (cold); later ones attach the
    cached prefix and prefill only the tail's bucket (warm). Programs are
    warmed on a DIFFERENT prefix first so both measurements exclude compile
    time; `last_prefill` proves the warm request really prefilled
    suffix-only.
    """
    import time as _time

    import numpy as np

    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.kvcache import PrefixCacheManager

    engine, cfg, model_id, _on_tpu = build_engine(spec=False, slots=4)
    bs = CONFIG.llm_kv_block_size
    shared_len, tail_len = 5 * bs, 8
    rng = np.random.default_rng(1)

    def request(prefix, seed):
        tail = np.random.default_rng(seed).integers(0, cfg.vocab_size, tail_len)
        prompt = prefix + tail.tolist()
        done = threading.Event()
        ttft = [None]
        t0 = _time.perf_counter()

        def cb(token, finished):
            if ttft[0] is None:
                ttft[0] = _time.perf_counter() - t0
            if finished:
                done.set()

        engine.submit(prompt, SamplingParams(max_tokens=2), cb)
        assert done.wait(timeout=600)
        return ttft[0]

    try:
        # Compile warm-up on a throwaway prefix: first call compiles the cold
        # bucket, second the attach + suffix-bucket programs.
        warm_prefix = rng.integers(0, cfg.vocab_size, shared_len).tolist()
        request(warm_prefix, 100)
        request(warm_prefix, 101)

        prefix = rng.integers(0, cfg.vocab_size, shared_len).tolist()
        ttft_cold = request(prefix, 0)
        cold = dict(engine.last_prefill)
        warm_ttfts = []
        for i in range(1, 4):
            warm_ttfts.append(request(prefix, i))
        warm = dict(engine.last_prefill)
        stats = engine.prefix_cache_stats()
        assert warm["offset"] == shared_len and cold["offset"] == 0, (cold, warm)
        assert warm["bucket"] < cold["bucket"], (cold, warm)
        return [
            {
                "metric": "ttft_prefix_cold_s", "value": round(ttft_cold, 4),
                "prompt_len": shared_len + tail_len,
                "prefill_bucket": cold["bucket"], "model": model_id,
            },
            {
                "metric": "ttft_prefix_warm_s",
                "value": round(min(warm_ttfts), 4),
                "prompt_len": shared_len + tail_len,
                "prefill_bucket": warm["bucket"],
                "prefill_offset": warm["offset"],
                "cache_hit_rate": round(stats["hit_rate"], 3),
                "cache_hit_tokens": stats["hit_tokens"],
                "model": model_id,
                "note": "shared 5-block prefix attached from cache; "
                        "suffix-only prefill",
            },
        ]
    finally:
        engine.shutdown()


def bench_tier_sweep():
    """TTFT by serving tier of the hierarchical KV store (docs/kvcache.md):
    cold (full prefill) vs host-warm (attach from the host pool) vs
    device-warm (attach a device-resident hot-tier prefix, zero H2D) vs
    disk-warm (promote a spilled chain back through the host pool first).
    The engine's `last_attach` proves which tier actually served each row."""
    import tempfile
    import time as _time

    import numpy as np

    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.kvcache import TieredPrefixCacheManager

    import jax

    from ray_tpu.llm import LLMConfig, load_model
    from ray_tpu.llm._engine import DecodeEngine

    bs = CONFIG.llm_kv_block_size
    shared_len, tail_len = 5 * bs, 8
    on_tpu = jax.default_backend() == "tpu"
    model_id = "gpt2-125m" if on_tpu else "test-tiny"
    cfg, params = load_model(LLMConfig(model_id=model_id))
    block_bytes = (cfg.n_layers * 2 * bs * cfg.n_kv_heads * cfg.head_dim
                   * np.dtype(cfg.dtype).itemsize)
    # Capacity of exactly one 5-block chain: inserting a second chain
    # evicts (spills) the first, which is how we stage the disk-warm case.
    spill_dir = tempfile.mkdtemp(prefix="bench_kv_spill_")
    mgr = TieredPrefixCacheManager(
        bs, 5 * block_bytes, name="bench-tier",
        device_bytes=8 * block_bytes, spill_dir=spill_dir,
    )
    engine = DecodeEngine(cfg, params, num_slots=4,
                          max_seq=1024 if on_tpu else 256, seed=0,
                          prefix_cache=mgr)
    rng = np.random.default_rng(1)

    def request(prefix, seed):
        tail = np.random.default_rng(seed).integers(0, cfg.vocab_size, tail_len)
        prompt = prefix + tail.tolist()
        done = threading.Event()
        ttft = [None]
        t0 = _time.perf_counter()

        def cb(token, finished):
            if ttft[0] is None:
                ttft[0] = _time.perf_counter() - t0
            if finished:
                done.set()

        engine.submit(prompt, SamplingParams(max_tokens=2), cb)
        assert done.wait(timeout=600)
        return ttft[0]

    def wait_spills(n):
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            if mgr.stats()["tiers"]["spills"] >= n:
                return
            _time.sleep(0.05)
        raise TimeoutError("spill worker never drained")

    try:
        warm_prefix = rng.integers(0, cfg.vocab_size, shared_len).tolist()
        request(warm_prefix, 100)  # compile cold bucket
        request(warm_prefix, 101)  # compile attach + suffix bucket
        other = rng.integers(0, cfg.vocab_size, shared_len).tolist()
        request(other, 102)  # evicts warm_prefix; its chain spills

        prefix = rng.integers(0, cfg.vocab_size, shared_len).tolist()
        ttft_cold = request(prefix, 0)
        ttft_host = request(prefix, 1)
        assert engine.last_attach["tier"] == "host", engine.last_attach
        ttft_device = request(prefix, 2)
        assert engine.last_attach["tier"] == "device", engine.last_attach
        request(other, 3)          # evict prefix's chain -> disk
        wait_spills(5)
        ttft_disk = request(prefix, 4)
        assert engine.last_attach["tier"] == "disk", engine.last_attach
        tiers = mgr.stats()["tiers"]
        rows = []
        for tier, value in (("cold", ttft_cold), ("host", ttft_host),
                            ("device", ttft_device), ("disk", ttft_disk)):
            rows.append({
                "metric": f"ttft_tier_{tier}_s", "value": round(value, 4),
                "prompt_len": shared_len + tail_len, "model": model_id,
                "cached_blocks": 0 if tier == "cold" else 5,
            })
        rows[-1]["note"] = (
            f"tiered cache (docs/kvcache.md): device attach is zero-H2D, "
            f"disk promotes through the host pool; "
            f"spills={tiers['spills']} promotions_host="
            f"{tiers['promotions_host']} promotions_device="
            f"{tiers['promotions_device']}"
        )
        return rows
    finally:
        engine.shutdown()


def bench_multicast_fanout():
    """One prefill feeding N decode readers (docs/device_channels.md):
    multicast 1->4 over ONE ring (each payload chunk staged once) vs 4
    point-to-point streams (staged 4x). Reports writer wall time and the
    staged-chunk counters that prove the single D2H pass."""
    import time as _time

    import numpy as np

    from ray_tpu.experimental import tensor_transport as _tt
    from ray_tpu.experimental.device_channel import (
        DeviceChannel, MulticastDeviceChannel,
    )

    payload = np.random.default_rng(0).standard_normal(
        (2, 1 << 20)).astype(np.float32)  # 8 MiB, a PD-prefix-sized tensor
    fanout = 4

    def run_multicast():
        mc = MulticastDeviceChannel.create(fanout, num_slots=8)
        threads = []
        for i in range(fanout):
            def reader(i=i):
                with mc.subscribe(i) as sub:
                    sub.recv(timeout=120)
            threads.append(threading.Thread(target=reader))
            threads[-1].start()
        t0 = _time.perf_counter()
        mc.send(payload, timeout=120)
        mc.drain(timeout=120)
        wall = _time.perf_counter() - t0
        for t in threads:
            t.join(120)
        mc.close()
        mc.destroy()
        return wall

    def run_p2p():
        t_total = 0.0
        for _ in range(fanout):
            ch = DeviceChannel.create(same_node=True, num_slots=8)
            t = threading.Thread(target=lambda: ch.recv(timeout=120))
            t.start()
            t0 = _time.perf_counter()
            ch.send(payload, timeout=120)
            ch.drain(timeout=120)
            t_total += _time.perf_counter() - t0
            t.join(120)
            ch.close()
            ch.destroy()
        return t_total

    before = _tt.transport_stats()["stream_chunks_staged"]
    mc_wall = min(run_multicast() for _ in range(3))
    mc_staged = (_tt.transport_stats()["stream_chunks_staged"] - before) // 3
    before = _tt.transport_stats()["stream_chunks_staged"]
    p2p_wall = min(run_p2p() for _ in range(3))
    p2p_staged = (_tt.transport_stats()["stream_chunks_staged"] - before) // 3
    return {
        "metric": "multicast_fanout_1_to_4",
        "payload_mb": round(payload.nbytes / 2**20, 1),
        "multicast_writer_s": round(mc_wall, 4),
        "p2p_x4_writer_s": round(p2p_wall, 4),
        "multicast_chunks_staged": mc_staged,
        "p2p_chunks_staged": p2p_staged,
        "speedup_vs_p2p": round(p2p_wall / max(mc_wall, 1e-9), 2),
        "note": "one staged (D2H) pass fanned to 4 subscribers over one "
                "ring vs 4 point-to-point streams re-staging the payload",
    }


def bench_remote_fetch_crossover():
    """Cluster prefix plane (docs/kvcache.md): fetching a peer replica's
    cached prefix over the DeviceChannel stream vs recomputing it locally.
    Reports both legs for the standard 5-block prefix; the crossover moves
    toward fetch as model size grows (prefill FLOPs scale with params, the
    fetch only with KV bytes)."""
    import asyncio
    import time as _time

    import numpy as np

    import ray_tpu
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import LLMConfig, LLMServer

    bs = CONFIG.llm_kv_block_size
    ray_tpu.init(
        num_cpus=4, num_tpus=0,
        worker_env={"JAX_PLATFORMS": "cpu"},
    )
    try:
        cfg_obj = LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128)
        s1, s2 = LLMServer(cfg_obj), LLMServer(cfg_obj)
        rng = np.random.default_rng(5)
        toks = list(map(int, rng.integers(0, 64, 5 * bs + 4)))
        warmup = list(map(int, rng.integers(0, 64, 5 * bs + 4)))

        async def run():
            # Warm every compiled program off-clock on BOTH replicas (cold
            # bucket, then attach + suffix bucket via the repeat).
            for srv in (s1, s2):
                await srv.generate(warmup, max_tokens=1)
                await srv.generate(warmup, max_tokens=1)
            await s1.generate(toks, max_tokens=2)   # S1 computes + caches
            # recompute leg: S2 cold TTFT
            r = await s2.generate(list(reversed(toks)), max_tokens=1)
            recompute_s = r["ttft_s"]
            # fetch leg: export S1 -> stream -> import S2 -> warm TTFT
            t0 = _time.perf_counter()
            desc = await s1.export_prefix(toks)
            inserted = await s2.import_prefix(desc, toks)
            fetch_s = _time.perf_counter() - t0
            warm = await s2.generate(toks, max_tokens=1)
            return recompute_s, fetch_s, warm["ttft_s"], inserted

        recompute_s, fetch_s, warm_ttft, inserted = asyncio.run(run())
        out = {
            "metric": "remote_fetch_vs_recompute",
            "prefix_blocks": 5, "blocks_fetched": inserted,
            "recompute_ttft_s": round(recompute_s, 4),
            "fetch_s": round(fetch_s, 4),
            "post_fetch_warm_ttft_s": round(warm_ttft, 4),
            "model": "test-tiny",
            "note": "fetch = export lease + DeviceChannel stream + import; "
                    "crossover favors fetch as prefill FLOPs grow with "
                    "model size while fetch cost scales only with KV bytes",
        }
        asyncio.run(s1.shutdown())
        asyncio.run(s2.shutdown())
        return out
    finally:
        ray_tpu.shutdown()


def bench_adapter_churn(on_tpu: bool):
    """Multi-tenant LoRA paging (docs/multitenancy.md): 32 registered
    adapters served through an 8-slot HBM budget, with a zipf-ish mix (a hot
    working set inside the budget + a cold tail beyond it), vs the
    always-resident upper bound (table holds all 32).

    Reported: cache hit rate, TTFT p50/p99 under churn, TTFT p50 of the
    WARM subset (adapter resident at submit) — the acceptance bar is
    warm-adapter TTFT ~= resident-engine TTFT (paging costs the cold tail
    its page-in, never the warm path)."""
    import numpy as np

    from ray_tpu.llm import SamplingParams

    n_adapters, n_slots = 32, 8
    rng = np.random.default_rng(2)

    def build(paged: bool):
        cfg_extra = {"cache_slots": n_slots} if paged else {}
        engine, cfg, model_id, _ = build_engine(
            slots=4, prefix_cache=False,
            lora_config={"max_loras": n_adapters, "rank": 4, **cfg_extra},
        )
        for i in range(n_adapters):
            r = np.random.default_rng(1000 + i)
            engine.add_lora(f"a{i}", {0: {
                "q_A": r.normal(size=(cfg.hidden, 4)).astype(np.float32),
                "q_B": r.normal(size=(4, cfg.n_heads * cfg.head_dim)).astype(np.float32),
            }}, alpha=8.0)
        return engine, cfg, model_id

    # Traffic: 70% on a hot set of 6 adapters (fits the 8-slot budget),
    # 30% uniform over the cold tail — the shape a real tenant fleet has.
    hot = [f"a{i}" for i in range(6)]
    cold = [f"a{i}" for i in range(6, n_adapters)]
    names = [
        (hot[rng.integers(len(hot))] if rng.random() < 0.7
         else cold[rng.integers(len(cold))])
        for _ in range(120)
    ]

    def run(engine, cfg, classify=None):
        prompt = rng.integers(0, cfg.vocab_size, 12).tolist()
        # warm the compiled programs + the hot set off-clock
        for name in hot:
            done = threading.Event()
            engine.submit(prompt, SamplingParams(max_tokens=2),
                          lambda t, f: done.set() if f else None, lora=name)
            assert done.wait(600)
        ttfts, warm_ttfts = [], []
        for name in names:
            resident = classify(name) if classify else True
            done = threading.Event()
            ttft = [None]
            t0 = time.perf_counter()

            def cb(tok, fin):
                if ttft[0] is None:
                    ttft[0] = time.perf_counter() - t0
                if fin:
                    done.set()

            engine.submit(prompt, SamplingParams(max_tokens=2), cb, lora=name)
            assert done.wait(600)
            ttfts.append(ttft[0])
            if resident:
                warm_ttfts.append(ttft[0])
        return ttfts, warm_ttfts

    resident_engine, cfg, model_id = build(paged=False)
    try:
        res_ttfts, _ = run(resident_engine, cfg)
    finally:
        resident_engine.shutdown()
    paged_engine, cfg, model_id = build(paged=True)
    try:
        adapters = paged_engine._adapters
        ttfts, warm_ttfts = run(
            paged_engine, cfg,
            classify=lambda n: adapters.is_resident(adapters.uid_of(n)),
        )
        stats = paged_engine.adapter_stats()
    finally:
        paged_engine.shutdown()
    return {
        "metric": "adapter_churn_ttft",
        "adapters": n_adapters, "cache_slots": n_slots,
        "requests": len(names),
        "cache_hit_rate": round(stats["hit_rate"], 3),
        "evictions": stats["evictions"],
        "page_ins": stats["page_ins"],
        "ttft_p50_s": round(_pctl(ttfts, 0.5), 4),
        "ttft_p99_s": round(_pctl(ttfts, 0.99), 4),
        "ttft_warm_p50_s": round(_pctl(warm_ttfts, 0.5), 4),
        "ttft_resident_p50_s": round(_pctl(res_ttfts, 0.5), 4),
        "ttft_resident_p99_s": round(_pctl(res_ttfts, 0.99), 4),
        "model": model_id,
        "note": "32 adapters on an 8-slot HBM budget, 70% traffic on a "
                "6-adapter hot set; warm-adapter TTFT vs the always-resident "
                "upper bound is the paging-overhead bar",
    }


def bench_wfq_fairness(on_tpu: bool):
    """Weighted-fair admission under saturation vs the FIFO control
    (docs/multitenancy.md): three tenants (weights 2:1:1) keep the queue
    full; the light tenant's flood arrives LAST, so FIFO serves it nothing
    inside the measurement window while WFQ holds every tenant's
    decode-token share within 10% of its weight."""
    import numpy as np

    from ray_tpu.llm import SamplingParams

    weights = {"gold": 2.0, "silver": 1.0, "bronze": 1.0}
    target = {"gold": 0.5, "silver": 0.25, "bronze": 0.25}

    def run(wfq: bool):
        engine, cfg, model_id, _ = build_engine(
            slots=2, prefix_cache=False, wfq=wfq,
            tenant_weights=weights if wfq else None, tenant_quota=0,
        )
        rng = np.random.default_rng(3)
        counts = {t: 0 for t in weights}
        finished = []
        lock = threading.Lock()
        try:
            # warm off-clock
            done = threading.Event()
            engine.submit([1, 2, 3], SamplingParams(max_tokens=2),
                          lambda t, f: done.set() if f else None)
            assert done.wait(600)
            # gold+silver flood first; bronze arrives behind them (the FIFO
            # killer ordering)
            for tenant in ("gold", "silver", "bronze"):
                for _ in range(25):
                    def cb(tok, fin, _t=tenant):
                        with lock:
                            counts[_t] += 1
                        if fin:
                            finished.append(_t)

                    engine.submit(
                        rng.integers(0, cfg.vocab_size, 8).tolist(),
                        SamplingParams(max_tokens=4), cb, tenant=tenant,
                    )
            deadline = time.perf_counter() + 600
            while len(finished) < 40 and time.perf_counter() < deadline:
                time.sleep(0.01)
            with lock:
                total = sum(counts.values()) or 1
                shares = {t: round(c / total, 3) for t, c in counts.items()}
            return shares, model_id
        finally:
            engine.shutdown()

    wfq_shares, model_id = run(True)
    fifo_shares, _ = run(False)
    return {
        "metric": "wfq_fairness",
        "weights": {t: w for t, w in weights.items()},
        "target_share": target,
        "wfq_share": wfq_shares,
        "fifo_share": fifo_shares,
        "max_weight_error_wfq": round(
            max(abs(wfq_shares[t] - target[t]) for t in weights), 3),
        "light_tenant_share_fifo": fifo_shares["bronze"],
        "model": model_id,
        "note": "3 saturated tenants, 2 slots; shares measured over the "
                "first ~40 completions (queues still full). FIFO serves "
                "arrival order, starving the late light tenant; WFQ tracks "
                "the configured weights",
    }


def bench_tp_sweep(on_tpu: bool):
    """Tensor-parallel decode sweep (docs/serving_tp.md): decode tokens/s and
    per-chip HBM high-water vs TP degree on the forced multi-device mesh,
    plus a model-larger-than-one-chip configuration — a parameter+KV
    footprint exceeding a single device's budget that only the sharded
    plane can serve, with throughput scaling reported vs TP=1."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.llm.tp import per_device_bytes
    from ray_tpu.models.transformer import Transformer, get_config

    if on_tpu:
        cfg = get_config("gpt2-125m", scan_layers=False, remat=False)
        max_seq, prompt_len, max_tokens = 1024, 128, 64
    else:
        # kv_heads=4 so every sweep degree divides the KV axis; a deeper KV
        # budget (max_seq) makes the pool a real fraction of the footprint.
        cfg = get_config("test-tiny", scan_layers=False, remat=False,
                         n_kv_heads=4)
        max_seq, prompt_len, max_tokens = 512, 16, 16
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    degrees = [d for d in (1, 2, 4) if d <= len(jax.devices())]
    rows = []
    per_chip = {}
    tps_by_degree = {}
    for tp in degrees:
        engine = DecodeEngine(cfg, params, num_slots=8, max_seq=max_seq,
                              seed=0, tp=tp)
        try:
            run_requests(engine, cfg.vocab_size, 4, prompt_len, max_tokens)  # warm
            _, tps, total = run_requests(
                engine, cfg.vocab_size, 4, prompt_len, max_tokens
            )
            chip = per_device_bytes(engine.params) + per_device_bytes(
                engine._caches
            )
        finally:
            engine.shutdown()
        per_chip[tp] = chip
        tps_by_degree[tp] = tps
        row = {
            "metric": "tp_decode_sweep", "tp": tp,
            "decode_tokens_per_s": round(tps, 1), "tokens": total,
            "per_chip_bytes": int(chip),
            "speedup_vs_tp1": round(tps / tps_by_degree[degrees[0]], 2),
            "model": "gpt2-125m" if on_tpu else "test-tiny-kv4",
            "max_seq": max_seq,
        }
        if not on_tpu and tp > 1:
            row["note"] = (
                "CPU artifact: the 'mesh' is 8 virtual host devices on one "
                "CPU, so GSPMD collectives cost wall-clock they repay only "
                "on real ICI; the load-bearing columns here are per_chip_"
                "bytes (the 1/tp footprint) and token-identity (tests)"
            )
        rows.append(row)
    # Model-larger-than-one-chip: a synthetic per-chip budget strictly
    # between the TP=max per-chip footprint and the TP=1 footprint — the
    # unsharded engine cannot exist under it, the sharded one serves.
    tp_hi = degrees[-1]
    budget = int((per_chip[1] + per_chip[tp_hi]) // 2)
    rows.append({
        "metric": "tp_model_exceeds_one_chip",
        "chip_budget_bytes": budget,
        "per_chip_bytes_tp1": int(per_chip[1]),
        f"per_chip_bytes_tp{tp_hi}": int(per_chip[tp_hi]),
        "fits_one_chip": per_chip[1] <= budget,
        f"fits_tp{tp_hi}": per_chip[tp_hi] <= budget,
        f"decode_tokens_per_s_tp{tp_hi}": round(tps_by_degree[tp_hi], 1),
        "throughput_vs_tp1": round(
            tps_by_degree[tp_hi] / tps_by_degree[degrees[0]], 2
        ),
        "note": "footprint = params + per-slot KV pool per device; the "
                "budget sits between the sharded and unsharded footprints, "
                "so only the TP mesh serves this configuration",
    })
    return rows


def bench_pd_ttft():
    """PD-disaggregated TTFT through the real serve app: prefill replica ->
    KV handoff (descriptor + pull over the round-11 device-channel plane,
    docs/device_channels.md) -> decode replica's first token. max_tokens=1,
    so latency_s IS the disaggregated TTFT."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.pd_disagg import build_pd_openai_app

    ray_tpu.init(
        num_cpus=4, num_tpus=0,
        worker_env={"JAX_PLATFORMS": "cpu"},
    )
    try:
        app = build_pd_openai_app(
            LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128),
            num_prefill=1, num_decode=1,
        )
        handle = serve.run(app, name="bench_pd_app", route_prefix=None)
        handle.generate.remote("warm up the compiled buckets",
                               max_tokens=2).result(timeout_s=600)
        ttfts, prefills = [], []
        for _ in range(5):
            r = handle.generate.remote(
                "hello world benchmark prompt", max_tokens=1
            ).result(timeout_s=600)
            ttfts.append(r["latency_s"])
            prefills.append(r["prefill_s"])
        serve.delete("bench_pd_app")
        return {
            "metric": "pd_ttft_s", "value": round(min(ttfts), 4),
            "prefill_s": round(min(prefills), 4), "max_tokens": 1,
            "model": "test-tiny",
            "note": "prefill replica -> KV descriptor + pull "
                    "(blob/stream gated by devobj_stream_min_bytes) -> "
                    "decode first token, across real replica actors",
        }
    finally:
        ray_tpu.shutdown()


def bench_stream_ttft_vs_blocking(on_tpu: bool):
    """Round 22 (docs/generation.md): the TokenStream subscription vs the
    raw-callback blocking path on the SAME engine and prompt — streaming is
    a host-side relay, so its TTFT must sit on top of blocking TTFT."""
    import numpy as np

    from ray_tpu.llm._engine import SamplingParams

    engine, cfg, model_id, _ = build_engine(spec=False, slots=4)
    prompt_len, max_tokens = (128, 32) if on_tpu else (16, 16)
    rng = np.random.default_rng(7)
    try:
        run_requests(engine, cfg.vocab_size, 2, prompt_len, 4)  # warm
        blocking, streaming = [], []
        for _ in range(5):
            prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
            first = [None]
            done = threading.Event()
            t0 = time.perf_counter()

            def cb(tok, fin, first=first, done=done, t0=t0):
                if first[0] is None:
                    first[0] = time.perf_counter() - t0
                if fin:
                    done.set()

            engine.submit(prompt, SamplingParams(max_tokens=max_tokens), cb)
            done.wait(600)
            blocking.append(first[0])

            t0 = time.perf_counter()
            stream = engine.open_stream(
                prompt, SamplingParams(max_tokens=max_tokens))
            ttft = None
            for _tok in stream:
                if ttft is None:
                    ttft = time.perf_counter() - t0
            streaming.append(ttft)
        return {
            "metric": "stream_ttft_vs_blocking",
            "value": round(min(streaming), 4),
            "blocking_ttft_s": round(min(blocking), 4),
            "stream_over_blocking": round(min(streaming) / max(min(blocking), 1e-9), 3),
            "model": model_id,
        }
    finally:
        engine.shutdown()


def bench_guided_decode_overhead(on_tpu: bool):
    """Round 22 (docs/generation.md): decode throughput with an
    allow-everything constraint vs unconstrained — isolates the per-step
    host cost of the mask add + DFA advance (the mask changes no tokens)."""
    import numpy as np

    from ray_tpu.llm import ByteTokenizer
    from ray_tpu.llm._engine import SamplingParams
    from ray_tpu.llm.generate import compile_constraint

    engine, cfg, model_id, _ = build_engine(spec=False, slots=4)
    prompt_len, max_tokens = (128, 64) if on_tpu else (16, 32)
    n = 4
    rng = np.random.default_rng(11)
    constraint = compile_constraint("(.|\n)*", ByteTokenizer(), cfg.vocab_size)
    try:
        run_requests(engine, cfg.vocab_size, 2, prompt_len, max_tokens)  # warm
        results = {}
        for mode in ("plain", "guided"):
            done = [threading.Event() for _ in range(n)]
            counts = [0] * n
            t0 = time.perf_counter()

            def cb_for(i):
                def cb(token, finished):
                    counts[i] += 1
                    if finished:
                        done[i].set()

                return cb

            for i in range(n):
                prompt = rng.integers(0, 256, prompt_len).tolist()
                engine.submit(
                    prompt, SamplingParams(max_tokens=max_tokens), cb_for(i),
                    constraint=constraint if mode == "guided" else None,
                )
            for ev in done:
                ev.wait(600)
            results[mode] = sum(counts) / (time.perf_counter() - t0)
        return {
            "metric": "guided_decode_overhead",
            "value": round(results["guided"], 1),
            "plain_tokens_per_s": round(results["plain"], 1),
            "guided_over_plain": round(results["guided"] / results["plain"], 3),
            "model": model_id,
        }
    finally:
        engine.shutdown()


def bench_batch_coexistence(on_tpu: bool):
    """Round 22 (docs/generation.md): online TTFT p50/p99 with a deep
    floor-weight batch-tenant backlog queued vs a no-batch baseline — the
    number the batch-admission policy exists to protect."""
    import numpy as np

    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm._engine import SamplingParams

    engine, cfg, model_id, _ = build_engine(spec=False, slots=4)
    prompt_len = 128 if on_tpu else 16
    rng = np.random.default_rng(13)

    def timed_online(n):
        ttfts, dones = [], []
        for _ in range(n):
            first = [None]
            done = threading.Event()
            t0 = time.perf_counter()

            def cb(tok, fin, first=first, done=done, t0=t0):
                if first[0] is None and tok >= 0:
                    first[0] = time.perf_counter() - t0
                if fin:
                    done.set()

            engine.submit(
                rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                SamplingParams(max_tokens=8), cb, tenant="online")
            dones.append((done, first))
            time.sleep(0.02)
        for done, first in dones:
            done.wait(600)
            ttfts.append(first[0])
        return ttfts

    try:
        run_requests(engine, cfg.vocab_size, 2, prompt_len, 8)  # warm
        base = timed_online(8)
        batch_done = [threading.Event() for _ in range(16)]
        for i in range(16):
            engine.submit(
                rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                SamplingParams(max_tokens=24),
                lambda t, f, ev=batch_done[i]: ev.set() if f else None,
                tenant=CONFIG.llm_batch_tenant)
        loaded = timed_online(8)
        for ev in batch_done:
            ev.wait(600)
        return {
            "metric": "batch_coexistence",
            "value": round(_pctl(loaded, 0.99), 4),
            "online_ttft_p50_s": round(_pctl(loaded, 0.5), 4),
            "baseline_ttft_p99_s": round(_pctl(base, 0.99), 4),
            "loaded_over_baseline_p99": round(
                _pctl(loaded, 0.99) / max(_pctl(base, 0.99), 1e-9), 2),
            "batch_backlog_rows": 16,
            "model": model_id,
        }
    finally:
        engine.shutdown()


def main():
    import jax

    results = []
    engine, cfg, model_id, on_tpu = build_engine(spec=False, slots=8)
    prompt_len, max_tokens = (128, 64) if on_tpu else (16, 16)

    # Warm every compiled program off-clock: prefill bucket, batched decode,
    # and every multi-step chunk bucket the measured budget will use
    # (8/4/2/1 for max_tokens=64).
    run_requests(engine, cfg.vocab_size, 2, prompt_len, max_tokens)

    # TTFT: warm single request into an empty engine.
    ttfts = []
    for _ in range(3):
        ttft, _, _ = run_requests(engine, cfg.vocab_size, 1, prompt_len, 2)
        ttfts.append(ttft)
    results.append({
        "metric": "ttft_warm_s", "value": round(min(ttfts), 4),
        "prompt_len": prompt_len, "model": model_id,
    })

    # Decode throughput vs concurrency (continuous batching).
    for conc in (1, 2, 4, 8):
        _, tps, total = run_requests(
            engine, cfg.vocab_size, conc, prompt_len, max_tokens
        )
        results.append({
            "metric": "decode_tokens_per_s", "concurrency": conc,
            "value": round(tps, 1), "tokens": total, "model": model_id,
        })
    engine.shutdown()

    # Mixed traffic: chunked prefill (scheduler token budget) vs legacy
    # whole-prompt admission — the TTFT/TPOT interference A/B.
    from ray_tpu._private.config import CONFIG

    results.append(bench_mixed_traffic(0, on_tpu))
    results.append(bench_mixed_traffic(CONFIG.llm_sched_token_budget, on_tpu))

    # Speculative decoding on repeated traffic (ngram/REST draft).
    results.append(bench_spec_decode(on_tpu))

    results.extend(bench_prefix_cache(prompt_len))

    # Hierarchical KV store (round 17, docs/kvcache.md): per-tier TTFT,
    # multicast fanout vs point-to-point, and the cross-replica
    # fetch-vs-recompute crossover.
    results.extend(bench_tier_sweep())
    results.append(bench_multicast_fanout())

    # Multi-tenant serving plane (round 13, docs/multitenancy.md):
    # adapter-churn paging overhead + WFQ-vs-FIFO fairness under saturation.
    results.append(bench_adapter_churn(on_tpu))
    results.append(bench_wfq_fairness(on_tpu))

    # Tensor-parallel decode sweep + model-larger-than-one-chip (round 15,
    # docs/serving_tp.md).
    results.extend(bench_tp_sweep(on_tpu))

    # Generation modes (round 22, docs/generation.md): streaming TTFT tax,
    # guided-mask host overhead, and online TTFT under a batch backlog.
    results.append(bench_stream_ttft_vs_blocking(on_tpu))
    results.append(bench_guided_decode_overhead(on_tpu))
    results.append(bench_batch_coexistence(on_tpu))

    # PD disaggregation TTFT across real replica actors (round 11).
    results.append(bench_pd_ttft())

    # Cluster prefix plane: fetch a peer's cached prefix vs recompute
    # (round 17; needs its own cluster, so it runs after bench_pd_ttft's).
    results.append(bench_remote_fetch_crossover())

    out = {
        "bench": "serve_engine",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0].device_kind),
        "results": results,
    }
    with open("BENCH_SERVE.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
