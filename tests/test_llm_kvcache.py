"""Paged KV prefix cache tests: pool/radix mechanics + engine integration.

Covers the docs/kvcache.md contracts: shared-prefix dedup, LRU eviction that
refuses ref-held blocks, concurrent insert/lookup, token-exact equivalence of
cached vs uncached greedy generation (with suffix-only prefill verified via
the prefill bucket), bounded admission, prompt-overflow errors, and the DP
router's full sampling-surface forwarding.
"""

import threading

import numpy as np
import pytest


def _manager(capacity_blocks: int, block_size: int = 4, layers: int = 2,
             heads: int = 2, dim: int = 3):
    """A manager sized in BLOCKS (capacity = exactly N blocks of this shape)."""
    from ray_tpu.llm.kvcache import PrefixCacheManager

    block_bytes = layers * 2 * block_size * heads * dim * 4  # float32
    mgr = PrefixCacheManager(block_size, capacity_blocks * block_bytes,
                             name=f"test-{capacity_blocks}")
    shape = (layers, 2, heads, dim)
    return mgr, shape


def _kv_for(tokens, shape):
    """Deterministic per-token KV rows so block content is checkable."""
    layers, two, heads, dim = shape
    rows = np.stack([
        np.full((layers, two, heads, dim), t, np.float32) for t in tokens
    ], axis=2)  # [L, 2, len(tokens), H, D]
    return rows


def test_shared_prefix_dedup_and_lookup():
    mgr, shape = _manager(capacity_blocks=16)
    prefix = [1, 2, 3, 4, 5, 6, 7, 8]          # 2 blocks of 4
    a = prefix + [10, 11, 12, 13]               # +1 block
    b = prefix + [20, 21, 22, 23]               # +1 block, shares 2
    assert mgr.insert(a, _kv_for(a, shape)) == 3
    assert mgr.insert(b, _kv_for(b, shape)) == 1  # prefix blocks dedup'd
    stats = mgr.stats()
    assert stats["blocks_resident"] == 4
    assert stats["inserted_blocks"] == 4

    # Longest-match lookup, capped at len-1 so one token always prefills.
    lease = mgr.lookup(a + [99])
    assert lease is not None and lease.matched_tokens == 12
    kv = lease.kv()
    assert kv.shape[2] == 12
    np.testing.assert_array_equal(kv, _kv_for(a, shape))
    lease.release()

    # Whole-prompt coverage is capped one block short of the full prompt.
    lease = mgr.lookup(a)
    assert lease is not None and lease.matched_tokens == 8
    lease.release()

    # Re-inserting an existing chain adds nothing (pure dedup walk).
    assert mgr.insert(a, _kv_for(a, shape)) == 0
    assert mgr.stats()["hit_tokens"] == 20


def test_lru_eviction_refuses_ref_held_blocks():
    mgr, shape = _manager(capacity_blocks=3)
    a = [1, 2, 3, 4, 5, 6, 7, 8]    # 2 blocks
    b = [9, 10, 11, 12, 13, 14, 15, 16]
    assert mgr.insert(a, _kv_for(a, shape)) == 2
    lease = mgr.lookup(a + [99])     # pins both of a's blocks
    assert lease.matched_tokens == 8
    # b needs 2 blocks; only 1 slot is free and a is pinned: the tail drops.
    assert mgr.insert(b, _kv_for(b, shape)) == 1
    stats = mgr.stats()
    assert stats["evicted_blocks"] == 0
    assert stats["rejected_blocks"] == 1
    # a survives intact while leased.
    check = mgr.lookup(a + [99])
    assert check is not None and check.matched_tokens == 8
    check.release()
    lease.release()
    # Unpinned now: inserting a fresh chain evicts LRU (b's lone block first,
    # then a's leaf) instead of rejecting.
    c = [30, 31, 32, 33, 34, 35, 36, 37]
    assert mgr.insert(c, _kv_for(c, shape)) == 2
    stats = mgr.stats()
    assert stats["evicted_blocks"] == 2
    assert stats["blocks_resident"] == 3
    assert mgr.lookup(b + [99]) is None  # b was the LRU victim


def test_eviction_unwinds_chains_leaf_first():
    mgr, shape = _manager(capacity_blocks=2)
    a = [1, 2, 3, 4, 5, 6, 7, 8]    # 2 blocks: parent + leaf
    assert mgr.insert(a, _kv_for(a, shape)) == 2
    b = [9, 10, 11, 12, 13, 14, 15, 16]
    # Both of a's blocks must go (leaf, then its parent becomes a leaf).
    assert mgr.insert(b, _kv_for(b, shape)) == 2
    assert mgr.stats()["evicted_blocks"] == 2
    assert mgr.lookup(a + [99]) is None


def test_namespaces_isolate_adapters():
    from ray_tpu.llm.kvcache import RadixIndex

    mgr, shape = _manager(capacity_blocks=8)
    tokens = [1, 2, 3, 4, 5, 6, 7, 8]
    mgr.insert(tokens, _kv_for(tokens, shape), namespace=0)
    assert mgr.lookup(tokens + [9], namespace=1) is None  # other adapter
    with mgr.lookup(tokens + [9], namespace=0) as lease:  # leaksan: release the pin
        assert lease.matched_tokens == 8

    idx = RadixIndex(4)
    assert idx.chunks([1, 2, 3, 4, 5]) == [(1, 2, 3, 4)]
    assert idx.match([1, 2, 3, 4], namespace=3) == []


def test_concurrent_insert_lookup():
    mgr, shape = _manager(capacity_blocks=8, block_size=4)
    rng = np.random.default_rng(0)
    prefixes = [list(map(int, rng.integers(0, 50, 8))) for _ in range(4)]
    errors = []

    def worker(seed):
        r = np.random.default_rng(seed)
        try:
            for _ in range(50):
                tokens = list(prefixes[int(r.integers(0, 4))])
                tokens += list(map(int, r.integers(50, 99, int(r.integers(0, 8)))))
                if r.random() < 0.5:
                    mgr.insert(tokens, _kv_for(tokens, shape))
                else:
                    lease = mgr.lookup(tokens + [99])
                    if lease is not None:
                        kv = lease.kv()
                        # leased rows always spell the looked-up prefix
                        np.testing.assert_array_equal(
                            kv, _kv_for(tokens[: lease.matched_tokens], shape)
                        )
                        lease.release()
        except Exception as e:  # pragma: no cover - surfaced via errors list
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    stats = mgr.stats()
    assert stats["blocks_resident"] <= 8
    # every lease released: nothing pinned, a full-capacity insert succeeds
    big = list(range(200, 232))
    assert mgr.insert(big, _kv_for(big, shape)) == 8


# -- engine integration ----------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _generate(engine, prompt, n, **sp):
    from ray_tpu.llm import SamplingParams

    out, done = [], threading.Event()

    def cb(tok, fin):
        out.append(tok)
        if fin:
            done.set()

    engine.submit(prompt, SamplingParams(max_tokens=n, **sp), cb)
    assert done.wait(180)
    return out


def test_cached_greedy_matches_uncached(tiny_model):
    """Token-exact equivalence: warm prefix-cache hits (suffix-only prefill)
    emit the same greedy tokens as a cache-disabled engine."""
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    rng = np.random.default_rng(11)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 40)))
    prompts = [prefix + list(map(int, rng.integers(0, cfg.vocab_size, k)))
               for k in (5, 9, 2)]

    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False)
    cached = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128,
        prefix_cache=PrefixCacheManager(16, 8 << 20, name="equiv-test"),
    )
    try:
        expected = [_generate(plain, p, 6) for p in prompts]
        got_cold = _generate(cached, prompts[0], 6)
        assert cached.last_prefill["offset"] == 0
        cold_bucket = cached.last_prefill["bucket"]
        got_warm = [_generate(cached, p, 6) for p in prompts[1:]]
        # Suffix-only prefill actually happened: 2 shared blocks attached,
        # and the prefill bucket shrank to the suffix's bucket.
        assert cached.last_prefill["offset"] == 32
        assert cached.last_prefill["bucket"] < cold_bucket
        stats = cached.prefix_cache_stats()
        assert stats["hits"] == 2 and stats["hit_tokens"] == 64
        assert [got_cold] + got_warm == expected
        # Repeating a warm prompt is still deterministic.
        assert _generate(cached, prompts[1], 6) == expected[1]
    finally:
        plain.shutdown()
        cached.shutdown()


@pytest.mark.parametrize("prompt_len,bucket", [(21, 32), (32, 32), (9, 8)])
def test_inserted_blocks_equal_the_slots_rows_bit_for_bit(tiny_model, monkeypatch, prompt_len, bucket):
    """What the gather program and its asynchronous copy hand to the pool is
    `caches[l][k][slot, :n]`, bit for bit, whether the bucket pads the rows
    (20 of 32), holds exactly them (32 of 32) or the prompt's whole blocks fill
    a smaller bucket than its prefill did (8 rows of a 9-token prompt)."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    rng = np.random.default_rng(prompt_len)
    warm = list(map(int, rng.integers(0, cfg.vocab_size, 6)))
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, prompt_len)))
    n = (prompt_len // 4) * 4
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64,
                          prefix_cache=PrefixCacheManager(4, 1 << 20, name="bits"))
    try:
        # A request whose first callback blocks the stepper holds slot 0 while the
        # prompt under test is queued, so that one is prefilled into slot 1: the
        # gather reads the slot it is given, not the first.
        from ray_tpu.llm import SamplingParams

        hold, started = threading.Event(), threading.Event()
        done = [threading.Event(), threading.Event()]

        def first(tok, fin):
            started.set()
            hold.wait(60)
            if fin:
                done[0].set()

        engine.submit(warm, SamplingParams(max_tokens=2), first)
        assert started.wait(180), engine.error
        engine.submit(prompt, SamplingParams(max_tokens=3),
                      lambda tok, fin: fin and done[1].set())
        hold.set()
        assert done[0].wait(180) and done[1].wait(180), engine.error
        assert list(engine._jit_kv_gather) == [("kv_gather", 4), ("kv_gather", bucket)]
        lease = engine.lease_prefix(prompt)
        assert lease is not None and lease.matched_tokens == n
        try:
            got = lease.kv()
        finally:
            lease.release()
        slot = 1
        assert int(engine._lens[slot]) == prompt_len + 3 - 1  # it did run there
        want = np.stack([
            np.stack([np.asarray(ck[slot, :n]), np.asarray(cv[slot, :n])])
            for ck, cv in engine._caches
        ])
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.abs(want).max() > 0
        np.testing.assert_array_equal(got, want)
    finally:
        engine.shutdown()


def test_pd_transfer_feeds_decode_cache(tiny_model):
    """A transferred prefix (submit_prefilled + token_ids) lands in the decode
    engine's pool and serves later direct submits suffix-only."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    rng = np.random.default_rng(5)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 36)))
    p1 = prefix + [7, 8]
    p2 = prefix + [3]

    prefiller = DecodeEngine(cfg, params, num_slots=1, max_seq=128,
                             decode_loop=False, prefix_cache=False)
    decoder = DecodeEngine(cfg, params, num_slots=2, max_seq=128)
    plain = DecodeEngine(cfg, params, num_slots=1, max_seq=128,
                         prefix_cache=False)
    try:
        first_logits, kv, plen = prefiller.prefill_detached(p1)
        out, done = [], threading.Event()

        def cb(tok, fin):
            out.append(tok)
            if fin:
                done.set()

        decoder.submit_prefilled(kv, plen, first_logits,
                                 SamplingParams(max_tokens=6), cb, token_ids=p1)
        assert done.wait(180)
        assert out == _generate(plain, p1, 6)
        assert decoder.prefix_cache_stats()["inserted_blocks"] == 2
        # The transferred prefix now serves direct submits from cache.
        assert _generate(decoder, p2, 6) == _generate(plain, p2, 6)
        assert decoder.last_prefill["offset"] == 32
    finally:
        prefiller.shutdown()
        decoder.shutdown()
        plain.shutdown()


def test_prompt_overflow_raises(tiny_model):
    """Oversized prompts raise instead of silently truncating (submit and
    prefill_detached), and a tight generation budget shrinks max_tokens, not
    the prompt."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=32,
                          decode_loop=False, prefix_cache=False)
    try:
        with pytest.raises(ValueError, match="exceeds this engine"):
            engine.submit(list(range(32)), SamplingParams(), lambda *a: None)
        with pytest.raises(ValueError, match="exceeds this prefill engine"):
            engine.prefill_detached(list(range(40)))
        # max_seq - 1 tokens still fits (boundary).
        engine.submit(list(range(31)), SamplingParams(), lambda *a: None)
    finally:
        engine.shutdown()


def test_admission_queue_depth_cap(tiny_model):
    from ray_tpu.llm import DecodeEngine, EngineOverloadedError, SamplingParams

    cfg, model, params = tiny_model
    # decode_loop=False: nothing drains the queue, so the cap is exact.
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=64,
                          decode_loop=False, prefix_cache=False,
                          max_queue_depth=2)
    try:
        engine.submit([1, 2], SamplingParams(), lambda *a: None)
        engine.submit([3, 4], SamplingParams(), lambda *a: None)
        with pytest.raises(EngineOverloadedError, match="admission queue"):
            engine.submit([5, 6], SamplingParams(), lambda *a: None)
        with pytest.raises(EngineOverloadedError):
            engine.submit_prefilled(
                np.zeros((cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.head_dim),
                         np.float32),
                8, np.zeros((cfg.vocab_size,), np.float32),
                SamplingParams(), lambda *a: None,
            )
    finally:
        engine.shutdown()


# -- DP router ------------------------------------------------------------


class _FakeResponse:
    def __init__(self, value):
        self._value = value

    def __await__(self):
        async def _v():
            return self._value

        return _v().__await__()


class _FakeMethod:
    def __init__(self, calls, result):
        self._calls = calls
        self._result = result

    def remote(self, *args, **kwargs):
        self._calls.append((args, kwargs))
        return _FakeResponse(self._result)


class _FakeHandle:
    def __init__(self, calls):
        self.generate = _FakeMethod(calls, {"token_ids": [1], "dp_rank": 0})


def test_dp_router_forwards_full_sampling_surface():
    """DPRouter.__call__ must await coroutine request bodies and forward
    top_k / stop_token_id / lora, not just max_tokens + temperature."""
    import asyncio

    from ray_tpu.llm.dp_serve import DPRouter

    calls = []
    router = DPRouter(_FakeHandle(calls), assigner=None)

    class _Request:
        async def json(self):
            return {"prompt": "hi", "model": "m:tuned", "max_tokens": 7,
                    "temperature": 0.5, "top_k": 3, "stop_token_id": 9}

    out = asyncio.run(router(_Request()))
    assert out["dp_rank"] == 0
    (args, kwargs), = calls
    assert args == ("hi",)
    assert kwargs == {"max_tokens": 7, "temperature": 0.5, "top_k": 3,
                      "stop_token_id": 9, "lora": "tuned"}

    # Sync-json request objects (plain dicts of the body) keep working.
    calls.clear()

    class _SyncRequest:
        def json(self):
            return {"prompt": "yo", "max_tokens": 2}

    asyncio.run(router(_SyncRequest()))
    (args, kwargs), = calls
    assert args == ("yo",) and kwargs["max_tokens"] == 2


def test_dp_router_fingerprint_chain():
    """Chain hashes identify whole-block prefixes: equal prefixes share chain
    entries, divergent blocks fork, and partial blocks add nothing."""
    from ray_tpu.llm.dp_serve import DPRouter

    router = DPRouter(_FakeHandle([]), assigner=None)
    bs = router._block
    a = list(range(3 * bs + 2))
    b = list(range(2 * bs)) + [999] * bs
    ca, cb = router._chain(a), router._chain(b)
    assert len(ca) == 3 and len(cb) == 3
    assert ca[:2] == cb[:2] and ca[2] != cb[2]
    assert router._chain(a[: bs - 1]) == []

    # _record + longest-match bookkeeping (pure, no cluster needed).
    router._record("r1", ca)
    router._record("r2", cb)
    fps = router._fingerprints
    assert set(fps) == {"r1", "r2"}
    m = 0
    for h in cb:
        if h not in fps["r1"]:
            break
        m += 1
    assert m == 2  # r1 matches b's first two blocks only


def test_dp_cache_aware_routing_end_to_end(ray_start_regular):
    """Two requests sharing a whole-block prefix land on the SAME replica
    (longest-expected-match routing) and the router counts a cache-routed
    dispatch; output stays deterministic."""
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.dp_serve import build_dp_openai_app

    app = build_dp_openai_app(
        LLMConfig(model_id="test-tiny", num_slots=2), dp_size=2
    )
    handle = serve.run(app, name="dp-kv", route_prefix=None, _timeout_s=300)
    try:
        # ByteTokenizer: 40+ chars = 2+ whole 16-token blocks of prefix.
        prompt = "system: you are a poet who answers in rhyme. user: hi"
        a = handle.generate.remote(prompt, max_tokens=4).result(timeout_s=300)
        b = handle.generate.remote(prompt, max_tokens=4).result(timeout_s=300)
        assert a["token_ids"] == b["token_ids"]
        assert a["dp_rank"] == b["dp_rank"], "repeat prefix left its replica"
        stats = handle.routing_stats.remote().result(timeout_s=120)
        assert stats["cache_routed"] >= 1, stats
        assert stats["fingerprints"] >= 1
        # Short prompts (no whole block) still fan out via the balanced path.
        outs = [
            handle.generate.remote(f"p{i}", max_tokens=2).result(timeout_s=300)
            for i in range(4)
        ]
        assert all(len(o["token_ids"]) == 2 for o in outs)
        stats = handle.routing_stats.remote().result(timeout_s=120)
        assert stats["untracked"] >= 4, stats
    finally:
        serve.delete("dp-kv")
    # Graceful retirement (round 12): deleting the app runs each replica's
    # shutdown() hook, which hands the dp rank back to the assigner
    # EXPLICITLY — the lazy dead-actor reclamation is the backstop, not the
    # path — so the rank map empties promptly, not at the next exhaustion.
    import time as _time

    import ray_tpu

    assigner = ray_tpu.get_actor("DPRankAssigner-test-tiny", namespace="llm_dp")
    deadline = _time.monotonic() + 30
    held = None
    while _time.monotonic() < deadline:
        held = ray_tpu.get(assigner.ranks.remote())
        if held == {}:
            break
        _time.sleep(0.25)
    assert held == {}, f"dp ranks not released on app delete: {held}"


# -- error-path lease lifetime (leaklint/leaksan round 12) --------------------

def test_detached_prefill_releases_lease_when_attach_raises(tiny_model):
    """prefill_detached on a cache hit must release its lease even when
    materializing the cached rows raises: a leaked lease pins its chain
    against eviction for the engine's whole life (the detached path has no
    scheduler drain to back-stop it)."""
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    mgr = PrefixCacheManager(16, 8 << 20, name="detached-leak-test")
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=128,
                          prefix_cache=mgr, decode_loop=False)
    try:
        rng = np.random.default_rng(3)
        prompt = list(map(int, rng.integers(0, cfg.vocab_size, 40)))
        engine.prefill_detached(prompt)          # warm: inserts 2 blocks
        assert mgr.stats()["blocks_resident"] > 0

        real_get = mgr._pool.get

        def poisoned_get(bid):
            raise RuntimeError("injected pool failure")

        mgr._pool.get = poisoned_get
        try:
            with pytest.raises(RuntimeError, match="injected pool failure"):
                engine.prefill_detached(prompt + [1, 2, 3])  # hit -> kv() raises
        finally:
            mgr._pool.get = real_get
        # The decisive assertion: the failed attach released its lease, so
        # nothing is pinned and the engine keeps serving.
        assert mgr.stats()["leases_active"] == 0
        first_logits, kv, n = engine.prefill_detached(prompt + [1, 2, 3])
        assert n == 43 and kv.shape[2] >= 43
        assert mgr.stats()["leases_active"] == 0
    finally:
        engine.shutdown()


def test_chunked_prefill_releases_lease_when_attach_raises(tiny_model):
    """The scheduler path: a cache-hit request whose leased-row
    materialization raises mid-attach must still release the lease (finally
    in _exec_chunk, scheduler drain as the backstop) and fail the caller's
    callback instead of hanging it."""
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    mgr = PrefixCacheManager(16, 8 << 20, name="chunk-leak-test")
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                          prefix_cache=mgr)
    rng = np.random.default_rng(5)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 40)))
    try:
        assert _generate(engine, prompt, 4)  # warm the cache
        real_get = mgr._pool.get
        mgr._pool.get = lambda bid: (_ for _ in ()).throw(
            RuntimeError("injected pool failure")
        )
        done = threading.Event()
        tokens = []

        def cb(tok, fin):
            tokens.append(tok)
            if fin:
                done.set()

        try:
            engine.submit(prompt + [7], SamplingParams(max_tokens=4), cb)
            # stepper dies on the poisoned attach; the caller must be failed
            # (token=-1, finished=True), never left hanging
            assert done.wait(60), "callback never fired after attach failure"
        finally:
            mgr._pool.get = real_get
        assert tokens[-1] == -1
        assert mgr.stats()["leases_active"] == 0
        # a dead engine rejects new work loudly instead of enqueueing it
        with pytest.raises(RuntimeError, match="stepper died"):
            engine.submit([1, 2, 3], SamplingParams(max_tokens=2), cb)
    finally:
        engine.shutdown()


def test_shutdown_fails_queued_requests_and_releases_leases(tiny_model):
    """shutdown() must drain: requests admitted but never scheduled get
    their callbacks failed (no hung submitters) and queued leases release."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    # decode_loop=False: nothing ever drains the queue except shutdown
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=64,
                          prefix_cache=False, decode_loop=False)
    results = []
    engine.submit([1, 2, 3], SamplingParams(max_tokens=2),
                  lambda tok, fin: results.append((tok, fin)))
    assert results == []
    engine.shutdown()
    assert results == [(-1, True)]
    # idempotent: a second shutdown neither raises nor double-fails
    engine.shutdown()
    assert results == [(-1, True)]
    with pytest.raises(RuntimeError, match="shut down"):
        engine.submit([4], SamplingParams(max_tokens=1),
                      lambda tok, fin: None)


def test_scheduler_drain_is_exception_safe():
    """One lease whose release raises must not leave the remaining drained
    requests leased or unreported."""
    from ray_tpu.llm.scheduler import Request, Scheduler

    sched = Scheduler(num_slots=1, buckets=(8, 16), max_seq=32,
                      token_budget=0, max_queue_depth=0)

    class _Lease:
        def __init__(self, blow_up):
            self.blow_up = blow_up
            self.released = False

        def release(self):
            if self.blow_up:
                raise RuntimeError("poisoned release")
            self.released = True

    reqs = [Request("prompt", prompt=[1, 2, 3], callback=lambda t, f: None)
            for _ in range(3)]
    leases = [_Lease(False), _Lease(True), _Lease(False)]
    for r, l in zip(reqs, leases):
        r.lease = l
        sched.submit(r)
    drained = sched.drain()
    assert len(drained) == 3
    assert leases[0].released and leases[2].released
    assert all(r.lease is None for r in drained)
    assert sched.queue_depth() == 0
