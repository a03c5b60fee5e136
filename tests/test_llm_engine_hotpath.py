"""Decode-engine hot-path regressions: bounded jit-program caches and a
host-native decode loop (the two compute-plane fixes jaxlint RL602/RL603
gate — see docs/raylint.md "writing jit-safe hot paths")."""

import contextlib
import threading

import numpy as np
import pytest


def _tiny_engine(**kwargs):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return DecodeEngine(cfg, params, **kwargs)


def _collect(engine, submit):
    """The tokens a request's callback got, `submit(callback)` having sent it."""
    acc, done = [], threading.Event()

    def cb(tok, fin):
        acc.append(tok)
        if fin:
            done.set()

    submit(cb)
    assert done.wait(180), engine.error
    return acc


def _generate(engine, prompt, lora="", **sp):
    from ray_tpu.llm import SamplingParams

    return _collect(engine, lambda cb: engine.submit(prompt, SamplingParams(**sp), cb, lora=lora))


def test_jit_program_cache_bounded_under_adversarial_length_mix(monkeypatch):
    """An adversarial prompt-length mix (every bucket distinct) must not grow
    the compiled-program caches past llm_max_jit_programs — and an evicted
    program must rebuild with identical numerics when its bucket returns."""
    from ray_tpu._private.config import CONFIG

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 2)
    monkeypatch.setitem(CONFIG._cache, "llm_max_jit_programs", 3)
    monkeypatch.setitem(CONFIG._cache, "llm_prefix_cache_bytes", 0)
    engine = _tiny_engine(num_slots=1, max_seq=64, decode_loop=False)
    try:
        assert engine._prefill_buckets == (2, 4, 8, 16, 32, 64)
        first_ref, _, _ = engine.prefill_detached([5, 9])
        lengths = (3, 5, 9, 17, 33)  # buckets 4, 8, 16, 32, 64
        for n in lengths:
            engine.prefill_detached(list(range(1, n + 1)))
            assert len(engine._jit_prefill) <= 3, engine._jit_prefill.keys()
        # bucket-2 program was evicted along the way; re-running the same
        # prompt re-jits and must reproduce the original logits exactly
        assert ("detached", 2) not in engine._jit_prefill
        first_again, _, _ = engine.prefill_detached([5, 9])
        np.testing.assert_allclose(first_ref, first_again, rtol=1e-5)
        assert len(engine._jit_prefill) <= 3
        # The eviction rebuild IS the planted retrace the program registry
        # exists to catch: the bucket-2 key compiled twice, and exactly the
        # rebuild shows up as a recompile (xla_recompiles_total's source).
        rows = {r["key"]: r
                for r in engine._xprof.report(owner=engine._xprof_owner)["programs"]}
        bucket2 = rows[("detached", 2)]
        assert bucket2["compiles"] == 2 and bucket2["recompiles"] == 1, bucket2
    finally:
        engine.shutdown()


def test_jit_program_cache_bounded_under_adversarial_chunk_mix(monkeypatch):
    """Chunked prefill must add ZERO program-cache growth: an adversarial
    prompt-length mix driven through the scheduler with a tiny token budget
    (so every prompt splits into chunks) draws every chunk shape from the
    bucket table, and the spec plane's verify program is keyed only by k —
    all through the capped `_program` helper."""
    from ray_tpu._private.config import CONFIG

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    monkeypatch.setitem(CONFIG._cache, "llm_max_jit_programs", 3)
    monkeypatch.setitem(CONFIG._cache, "llm_prefix_cache_bytes", 0)
    engine = _tiny_engine(num_slots=2, max_seq=64, token_budget=4,
                          prefix_cache=False,
                          spec_config={"method": "ngram", "num_spec_tokens": 3})
    try:
        assert engine._prefill_buckets == (4, 8, 16, 32, 64)
        for n in (3, 5, 9, 17, 33, 21, 13):   # every bucket, revisited
            out = _generate(engine, list(range(1, n + 1)), max_tokens=2)
            assert len(out) == 2
            assert len(engine._jit_prefill) <= 3, engine._jit_prefill.keys()
            assert len(engine._jit_spec_verify) <= 1
        stats = engine.scheduler_stats()
        assert stats["prefill_chunks"] > 7  # the mix really was chunked
    finally:
        engine.shutdown()


def test_jit_program_cap_zero_is_unbounded(monkeypatch):
    from ray_tpu._private.config import CONFIG

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 2)
    monkeypatch.setitem(CONFIG._cache, "llm_max_jit_programs", 0)
    monkeypatch.setitem(CONFIG._cache, "llm_prefix_cache_bytes", 0)
    engine = _tiny_engine(num_slots=1, max_seq=64, decode_loop=False)
    try:
        for n in (2, 3, 5, 9, 17):
            engine.prefill_detached(list(range(1, n + 1)))
        assert len(engine._jit_prefill) == 5
    finally:
        engine.shutdown()


def test_adapter_paging_adds_zero_programs_under_churn(monkeypatch):
    """Paging adapters through a smaller-than-registry device table must not
    grow ANY program cache: churn across 6 adapters on 2 slots re-uses the
    same prefill/decode programs and exactly ONE adapter-install trace (the
    RL602/RL604 contract: slot index is a traced scalar, blob shapes are
    fixed at construction). See docs/multitenancy.md."""
    import numpy as np

    from ray_tpu._private.config import CONFIG

    monkeypatch.setitem(CONFIG._cache, "llm_prefix_cache_bytes", 0)
    engine = _tiny_engine(
        num_slots=2, max_seq=64, decode_loop=True, prefix_cache=False,
        lora_config={"max_loras": 6, "rank": 2, "cache_slots": 2},
    )
    try:
        hidden = engine.cfg.hidden
        for i in range(6):
            engine.add_lora(f"a{i}", {0: {"q_A": np.random.default_rng(i).normal(
                size=(hidden, 2)).astype(np.float32)}}, alpha=4.0)
        _generate(engine, [5, 9, 17], max_tokens=2)   # warm base programs
        programs = len(engine._jit_prefill)
        # churn: every adapter twice through the 2-slot budget
        for _ in range(2):
            for i in range(6):
                _generate(engine, [5, 9, 17], max_tokens=2, lora=f"a{i}")
        stats = engine.adapter_stats()
        assert stats["evictions"] > 0, stats       # churn really paged
        assert stats["install_programs"] in (1, None), stats
        assert len(engine._jit_prefill) == programs, (
            "adapter paging grew the prefill program cache"
        )
    finally:
        engine.shutdown()


class _NpSpy:
    """Stand-in for the engine module's `np` that counts device->host pulls
    (np.asarray/np.array on jax Arrays) and delegates everything else."""

    def __init__(self):
        import jax

        self._jax = jax
        self.device_pulls = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *args, **kwargs):
        if isinstance(x, self._jax.Array):
            self.device_pulls += 1
        return np.asarray(x, *args, **kwargs)

    def array(self, x, *args, **kwargs):
        if isinstance(x, self._jax.Array):
            self.device_pulls += 1
        return np.array(x, *args, **kwargs)


def test_decode_loop_is_host_native_one_pull_per_dispatch(monkeypatch):
    """The micro-assert for the decode loop: slot bookkeeping (lens,
    last_token, adapter ids) lives host-side, decode never calls
    jax.device_get, and the ONLY device->host transfer per decode dispatch
    is the batched logits readback — so max_tokens tokens cost exactly
    1 admission pull + (max_tokens - 1) decode pulls."""
    import jax

    from ray_tpu.llm import _engine as engine_mod

    spy = _NpSpy()
    monkeypatch.setattr(engine_mod, "np", spy)

    def _no_device_get(*a, **k):  # decode path must never block through this
        raise AssertionError("jax.device_get called in the decode path")

    monkeypatch.setattr(jax, "device_get", _no_device_get)

    # multi_step=1 pins one dispatch per token (the tightest accounting)
    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1,
                          prefix_cache=False)
    try:
        assert isinstance(engine._lens, np.ndarray)
        assert isinstance(engine._last_token, np.ndarray)
        assert isinstance(engine._adapter_ids, np.ndarray)
        # The flight recorder must be LIVE for this accounting: the bound
        # being asserted is that per-request observability adds zero
        # device syncs to the decode loop (docs/observability.md).
        assert engine._recorder.capacity > 0
        max_tokens = 8
        out = _generate(engine, [5, 9, 17, 3], max_tokens=max_tokens)
        assert len(out) == max_tokens
        assert spy.device_pulls == max_tokens  # 1 admission + 7 decode steps
        # host mirrors advanced without ever pulling device state
        assert int(engine._lens[0]) == 4 + max_tokens - 1
        assert int(engine._last_token[0]) == out[-1]
        # ...and the recorder really observed the request (phases + every
        # token timestamped) without a single extra pull showing up above.
        rec = engine._recorder.records()[-1]
        assert rec["tokens"] == max_tokens
        assert "prefill-chunk" in rec["phases"] and "decode" in rec["phases"]
    finally:
        engine.shutdown()


def test_observability_reports_add_zero_pulls_and_zero_programs(monkeypatch):
    """The round-18 micro-assert: the program registry and device-memory
    ledger ride the existing report paths — exercising scheduler_stats()
    (which now carries both reports) against a WARM engine adds zero
    device->host pulls, zero compiled programs, and zero recompiles, and a
    warm generate after the reports costs exactly its token accounting."""
    from ray_tpu.llm import _engine as engine_mod

    spy = _NpSpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1,
                          prefix_cache=False)
    try:
        _generate(engine, [5, 9, 17, 3], max_tokens=4)  # warm every program
        programs = len(engine._jit_prefill)
        pulls = spy.device_pulls
        recompiles_before = engine._xprof.recompiles_total
        for _ in range(2):
            stats = engine.scheduler_stats()
        assert spy.device_pulls == pulls, "stats reports pulled device state"
        assert len(engine._jit_prefill) == programs
        assert engine._xprof.recompiles_total == recompiles_before
        # the reports really flowed: registry rows for this engine's owner
        # and a ledger row attributing its KV bytes
        prog_report = stats["programs"]
        assert prog_report["totals"]["programs"] > 0
        assert all(r["owner"] == engine._xprof_owner
                   for r in prog_report["programs"])
        mem = stats["memory"]
        owner_row = mem["owners"][engine._xprof_owner]
        assert owner_row["components"]["kv_slots"] > 0
        assert mem["tracked_bytes_total"] >= owner_row["bytes"]
        # a warm generate after the reports stays at the exact pull bound
        out = _generate(engine, [5, 9, 17, 3], max_tokens=4)
        assert len(out) == 4
        assert spy.device_pulls == pulls + 4  # 1 admission + 3 decode steps
    finally:
        engine.shutdown()


def test_multi_step_decode_single_pull_per_chunk(monkeypatch):
    """Multi-step chunks amortize further: n tokens per dispatch -> one
    batched token readback per CHUNK, never a lens/last_token pull."""
    import jax

    from ray_tpu.llm import _engine as engine_mod

    spy = _NpSpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    engine = _tiny_engine(num_slots=1, max_seq=64, multi_step=4,
                          prefix_cache=False)
    try:
        out = _generate(engine, [5, 9, 17, 3], max_tokens=9)
        assert len(out) == 9
        # 1 admission pull + ceil(8 / 4) = 2 chunk pulls
        assert spy.device_pulls == 3
    finally:
        engine.shutdown()


# ---- the prefix-cache insert (docs/kvcache.md "the insert path") -------------


def _cache_engine(monkeypatch, **kwargs):
    """An engine with a prefix cache of 4-token blocks and 4, 8, 16, ... buckets."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm.kvcache import PrefixCacheManager

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    kwargs.setdefault("prefix_cache", PrefixCacheManager(4, 1 << 20, name="hotpath"))
    return _tiny_engine(num_slots=2, max_seq=64, multi_step=1, **kwargs)


def _settled(engine):
    """The engine once the worker thread has nothing left to finish."""
    engine._finish_kv_inserts()
    return engine


def _compiles(engine):
    return engine._xprof.report(owner=engine._xprof_owner)["totals"]["compiles_total"]


def test_prefix_insert_is_one_pull_and_one_program_per_bucket(monkeypatch):
    """An admission with the prefix cache on costs ONE device->host pull for the
    insert (not 2 x n_layers), through one `rt_kv_gather` program per prefill
    bucket: a warm engine builds nothing for a further insert of a seen bucket,
    padded or not."""
    from ray_tpu.llm import _engine as engine_mod

    spy = _NpSpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    engine = _cache_engine(monkeypatch)
    try:
        out = _generate(engine, list(range(1, 14)), max_tokens=4)  # 12 rows -> bucket 16
        assert len(out) == 4
        assert _settled(engine)._kv_thread.is_alive()
        assert spy.device_pulls == 4 + 1  # 1 admission + 3 decode steps + 1 insert
        assert list(engine._jit_kv_gather) == [("kv_gather", 16)]
        pulls, compiles = spy.device_pulls, _compiles(engine)
        _generate(engine, list(range(21, 35)), max_tokens=4)       # 12 rows again
        _generate(engine, list(range(41, 57)), max_tokens=4)       # 16 rows: the bucket, unpadded
        assert _settled(engine) and spy.device_pulls == pulls + 2 * (4 + 1)
        assert _compiles(engine) == compiles, "a warm insert built a program"
        _generate(engine, list(range(61, 70)), max_tokens=4)       # 8 rows -> bucket 8
        assert sorted(engine._jit_kv_gather) == [("kv_gather", 8), ("kv_gather", 16)]
        rows = engine._xprof.report(owner=engine._xprof_owner)["programs"]
        gathers = [r for r in rows if isinstance(r["key"], tuple) and r["key"][0] == "kv_gather"]
        assert len(gathers) == 2 and all(r["compiles"] == 1 for r in gathers), gathers
        assert {r["key"][1] for r in gathers} <= set(engine._prefill_buckets)
        stats = _settled(engine).scheduler_stats()["prefix_cache"]
        assert stats["inserts_issued"] == stats["inserts_completed"] == 4
        assert stats["inserts_pending"] == 0 and stats["inserted_blocks"] == 3 + 3 + 4 + 2
    finally:
        engine.shutdown()


def test_same_prompt_back_to_back_hits_on_the_second(monkeypatch):
    """A lookup sees every insert issued before it, with no sleep anywhere: the
    second send of a prompt attaches the first one's blocks."""
    from ray_tpu.util import xprof

    engine = _cache_engine(monkeypatch)
    prompt = list(range(1, 14))
    try:
        attaches = xprof.span_totals().get("rt.engine.attach", {"count": 0})["count"]
        first = _generate(engine, prompt, max_tokens=3)
        assert engine.last_attach is None
        second = _generate(engine, prompt, max_tokens=3)
        assert second == first
        assert engine.last_attach == {"tier": "host", "cached_tokens": 12}
        assert xprof.span_totals()["rt.engine.attach"]["count"] == attaches + 1
        stats = engine.prefix_cache_stats()
        assert stats["hits"] == 1 and stats["hit_tokens"] == 12
        # the second prompt's whole blocks were all cached: nothing more to insert
        assert stats["inserts_issued"] == stats["inserts_completed"] == 1
    finally:
        engine.shutdown()


def test_a_lookup_finishes_the_pending_inserts_first(monkeypatch):
    """With no stepper there is no worker thread, and inserts stay pending until something looks:
    the scheduler's admission lookup, `lease_prefix` and the detached prefill's
    lookup each finish them first, and the wait is counted."""
    engine = _cache_engine(monkeypatch, decode_loop=False)
    a, b, c = list(range(1, 14)), list(range(21, 30)), list(range(41, 46))
    try:
        engine._insert_prompt_kv(0, a, 0, 0, rid="a")
        stats = engine.prefix_cache_stats()
        assert (stats["inserts_issued"], stats["inserts_pending"], stats["inserted_blocks"]) == (1, 1, 0)
        lease = engine._sched._lookup(a, 0)
        assert lease is not None and lease.matched_tokens == 12
        lease.release()
        engine._insert_prompt_kv(1, b, 0, 0, rid="b")
        lease = engine.lease_prefix(b)
        assert lease is not None and lease.matched_tokens == 8
        lease.release()
        engine._insert_prompt_kv(0, c, 0, 0, rid="c")
        engine.prefill_detached(c + [7, 8])
        assert engine.last_prefill["offset"] == 4
        stats = engine.prefix_cache_stats()
        assert stats["inserts_completed"] == 3 and stats["inserts_pending"] == 0
        assert stats["insert_waits"] == 3 and stats["insert_wait_s"] >= 0.0
    finally:
        engine.shutdown()


def test_a_third_insert_waits_for_the_oldest_and_shutdown_drops_the_rest(monkeypatch):
    """At most two inserts are in flight: the third finishes the oldest first
    (counted). `shutdown()` with inserts pending frees their device buffers."""
    engine = _cache_engine(monkeypatch, decode_loop=False)
    prompts = [list(range(s, s + 9)) for s in (1, 21, 41)]
    try:
        for slot, prompt in enumerate(prompts[:2]):
            engine._insert_prompt_kv(slot, prompt, 0, 0)
        stats = engine.prefix_cache_stats()
        assert (stats["inserts_pending"], stats["insert_waits"]) == (2, 0)
        engine._insert_prompt_kv(0, prompts[2], 0, 0)
        stats = engine.prefix_cache_stats()
        assert (stats["inserts_issued"], stats["inserts_completed"], stats["inserts_pending"],
                stats["insert_waits"]) == (3, 1, 2, 1)
        assert engine._prefix_cache.lookup(prompts[0]) is not None   # the oldest is in
        assert engine._prefix_cache.lookup(prompts[1]) is None       # the others not yet
        buffers = [p.kv for p in engine._kv_pending]
        assert len(buffers) == 2 and not any(b.is_deleted() for b in buffers)
    finally:
        engine.shutdown()
    assert not engine._kv_pending and all(b.is_deleted() for b in buffers)
    assert engine.prefix_cache_stats()["inserts_completed"] == 1


def test_inserts_survive_submitters_and_lookups_racing_the_worker(monkeypatch):
    """Stepper, insert worker, submitting threads and threads that lease prefixes all
    touch the pending queue at once, with the interpreter switching threads every 10 us:
    no insert is lost or made twice, and every prompt's blocks are in the pool."""
    import sys

    from ray_tpu.llm.kvcache import PrefixCacheManager

    engine = _cache_engine(monkeypatch, prefix_cache=PrefixCacheManager(4, 8 << 20, name="race"))
    prompts = [[100 + 7 * i + j for j in range(9 + i % 8)] for i in range(24)]
    stop, errors = threading.Event(), []

    def submitter(mine):
        try:
            for prompt in mine:
                assert len(_generate(engine, prompt, max_tokens=2)) == 2
        except BaseException as e:  # noqa: BLE001 - reported by the main thread
            errors.append(e)

    def leaser():
        try:
            while not stop.is_set():
                for prompt in prompts[::5]:
                    lease = engine.lease_prefix(prompt)
                    if lease is not None:
                        lease.release()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=submitter, args=(prompts[i::8],)) for i in range(8)]
        leasers = [threading.Thread(target=leaser) for _ in range(4)]
        for t in workers + leasers:
            t.start()
        for t in workers:
            t.join(240)
        stop.set()
        for t in leasers:
            t.join(60)
        assert not any(t.is_alive() for t in workers + leasers) and not errors, errors
        stats = _settled(engine).prefix_cache_stats()
        assert stats["inserts_issued"] == stats["inserts_completed"] == len(prompts)
        assert stats["inserts_pending"] == 0 and stats["leases_active"] == 0
        assert stats["inserted_blocks"] == sum(len(p) // 4 for p in prompts)
        for prompt in prompts:
            lease = engine._prefix_cache.lease_prefix(prompt)
            assert lease is not None and lease.matched_tokens == (len(prompt) // 4) * 4
            lease.release()
    finally:
        sys.setswitchinterval(interval)
        engine.shutdown()


def test_shutdown_with_an_insert_in_flight_leaves_no_thread_and_no_buffer(monkeypatch):
    """The worker is stopped in the middle of its work (the test holds its lock, so
    the insert of the running request stays in flight): `shutdown()` ends both
    threads, frees the gathered buffer and never hands it to the pool."""
    threads = threading.active_count()
    engine = _cache_engine(monkeypatch)
    assert threading.active_count() == threads + 2  # the stepper and the insert worker
    with engine._kv_lock:
        _generate(engine, list(range(1, 14)), max_tokens=2)
        (pending,) = engine._kv_pending
        engine._stop = True          # shutdown()'s first two steps, while the lock is held
        engine._kv_wake.set()
    engine.shutdown()
    assert not engine._thread.is_alive() and not engine._kv_thread.is_alive()
    assert threading.active_count() == threads
    assert not engine._kv_pending and pending.kv.is_deleted()
    stats = engine.prefix_cache_stats()
    assert (stats["inserts_issued"], stats["inserts_completed"], stats["inserted_blocks"]) == (1, 0, 0)


# ---- rt.engine.* spans (docs/observability.md "compute plane") --------------

_ITER_CHILDREN = ("rt.engine.prefill", "rt.engine.attach", "rt.engine.kv_insert",
                  "rt.engine.dispatch", "rt.engine.readback", "rt.engine.sample")
_ENGINE_SPANS = ("rt.engine.iter", "rt.engine.idle", "rt.engine.plan") + _ITER_CHILDREN
# the parts of the three coarse spans (child -> parent), and a request's three instants
_PARTS = {"rt.engine.readback.wait": "rt.engine.readback", "rt.engine.readback.copy": "rt.engine.readback",
          "rt.engine.dispatch.args": "rt.engine.dispatch", "rt.engine.dispatch.call": "rt.engine.dispatch",
          "rt.engine.sample.draw": "rt.engine.sample", "rt.engine.sample.emit": "rt.engine.sample"}
_REQUEST_EVENTS = ("rt.sched.admit", "rt.engine.first_token", "rt.engine.finish")


def _loop_counts():
    from ray_tpu.util import xprof

    totals = xprof.span_totals()
    return {name: totals.get(name, {"count": 0})["count"] for name in _ENGINE_SPANS + tuple(_PARTS)}


def test_spans_add_zero_pulls_and_zero_programs_on_a_warm_engine(monkeypatch):
    """The spans ride the decode loop for free: a warm generate under them
    (and under a live profiler session, which is when they record) costs
    exactly its token accounting in device pulls and builds no program."""
    import tempfile

    from ray_tpu.llm import _engine as engine_mod
    from ray_tpu.util import xprof

    spy = _NpSpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1,
                          prefix_cache=False)
    try:
        _generate(engine, [5, 9, 17, 3], max_tokens=4)  # warm every program
        programs = (len(engine._jit_prefill), len(engine._jit_decode_multi),
                    engine._jit_decode._cache_size())
        compiles = engine._xprof.report(owner=engine._xprof_owner)["totals"]["compiles_total"]
        pulls, before = spy.device_pulls, _loop_counts()
        cap = xprof.start_capture(log_dir=tempfile.mkdtemp(prefix="xprof_test_"))
        try:
            out = _generate(engine, [5, 9, 17, 3], max_tokens=6)
        finally:
            cap.stop_capture()
        assert len(out) == 6
        assert spy.device_pulls == pulls + 6  # 1 admission + 5 decode steps
        assert programs == (len(engine._jit_prefill), len(engine._jit_decode_multi),
                            engine._jit_decode._cache_size())
        assert engine._xprof.report(owner=engine._xprof_owner)["totals"]["compiles_total"] == compiles
        after = _loop_counts()
        assert after["rt.engine.dispatch"] == before["rt.engine.dispatch"] + 5
        assert after["rt.engine.readback"] == before["rt.engine.readback"] + 6
        assert after["rt.engine.sample"] == before["rt.engine.sample"] + 6
        # the split readback is still one pull a dispatch (counted above): a wait and a copy each
        for part, n in (("rt.engine.readback.wait", 6), ("rt.engine.readback.copy", 6),
                        ("rt.engine.dispatch.args", 5), ("rt.engine.dispatch.call", 5),
                        ("rt.engine.sample.draw", 0), ("rt.engine.sample.emit", 5)):  # greedy rows: the device's
            assert after[part] == before[part] + n, part
    finally:
        engine.shutdown()


def test_mixed_run_yields_every_span_with_iter_covering_its_children(tmp_path, monkeypatch):
    """Prefix-cache insert, a cache-hit attach, chunked prefill, single and
    multi-step decode and an idle loop, under a CPU profiler capture: every
    span of the table is an event of the host plane, every child lies inside an
    `rt.engine.iter`, and a request's engine spans carry its flight-record id. The
    three coarse spans' parts each lie inside their parent, and a request's admission,
    first token and end are instants that carry its record's id and its record's times."""
    import glob
    import os
    import time

    from jax.profiler import ProfileData

    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import SamplingParams
    from ray_tpu.util import xprof

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    monkeypatch.setitem(CONFIG._cache, "llm_kv_block_size", 4)
    monkeypatch.setitem(CONFIG._cache, "llm_prefix_cache_bytes", 1 << 20)
    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=4, token_budget=8)
    prompt = list(range(1, 14))
    try:
        cap = xprof.start_capture(log_dir=str(tmp_path))
        try:
            _generate(engine, prompt, max_tokens=6)                  # chunks, kv_insert, multi-step
            done = []
            engine.submit(prompt + [40, 41], SamplingParams(max_tokens=3, temperature=0.8, top_k=8),
                          lambda tok, fin: done.append(fin), request_id="req-hit")  # attach, one step at a time, drawn on the host
            deadline = time.time() + 120
            while not (done and done[-1]) and time.time() < deadline:
                time.sleep(0.01)
            assert done and done[-1], engine.error
            time.sleep(0.02)                                         # a few idle plans
        finally:
            cap.stop_capture()
        assert engine.last_attach is not None, "the second prompt did not hit the prefix cache"
        records = {r["rid"]: r for r in engine._recorder.records()}
        stats = engine.scheduler_stats()
    finally:
        engine.shutdown()
    # the whole run's plans by what held them: every iteration and every decode token under one limit
    by_limit = stats["plans"]["by_limit"]
    assert stats["plans"]["steps_max"] == 4 and sum(row["iterations"] for row in by_limit.values()) == stats["iterations"]
    assert sum(row["decode_tokens"] for row in by_limit.values()) == stats["decode_tokens"] > 0
    assert {k for k, row in by_limit.items() if row["iterations"]} == {"no_decode", "none", "tail", "sampling"}
    assert by_limit["tail"]["decode_tokens_possible"] == 4 * by_limit["tail"]["decode_tokens"] == 4
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("rt.engine.", "rt.sched.")):
                    events.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    parts = {name: events.pop(name) for name in _PARTS}
    instants = {name: events.pop(name) for name in _REQUEST_EVENTS}
    assert set(events) - {"rt.engine.kv_copy"} == set(_ENGINE_SPANS), sorted(set(_ENGINE_SPANS) ^ set(events))
    iters = events["rt.engine.iter"]
    # every part inside a span of its parent; a readback is a wait and a copy, a dispatch its
    # arguments and its call, and a round's sample an emission after (where the host draws) a draw
    for name, parent in _PARTS.items():
        for a, b, _ in parts[name]:
            assert any(p0 <= a and b <= p1 for p0, p1, _ in events[parent]), name
    for name in ("rt.engine.readback.wait", "rt.engine.readback.copy", "rt.engine.dispatch.args", "rt.engine.dispatch.call"):
        assert len(parts[name]) == len(events[_PARTS[name]]), name
    rounds = len(events["rt.engine.dispatch"])                       # one `sample` a round, one a first token
    assert len(parts["rt.engine.sample.emit"]) == rounds == len(events["rt.engine.sample"]) - 2
    assert 0 < len(parts["rt.engine.sample.draw"]) < rounds          # the multi-step rounds draw nothing on the host
    # the single-step rounds say how many rows: the greedy tail's on the device, the top-k request's two on the host
    assert sorted(int(s["host_rows"]) for _, _, s in events["rt.engine.sample"] if "host_rows" in s) == [0, 1, 1]
    assert len(parts["rt.engine.sample.draw"]) == 2
    # and no round's program drew at a temperature: the top-k request's rows are the host's (`hot` counts the program's)
    assert [int(s["hot"]) for _, _, s in events["rt.engine.dispatch"]] == [0] * rounds
    # a request's three instants: its record's id, and its record's own times
    for name in _REQUEST_EVENTS:
        assert {str(s["rid"]) for _, _, s in instants[name]} == set(records) and len(records) == 2, name
    for _, _, s in instants["rt.sched.admit"]:
        rec = records[str(s["rid"])]
        assert int(s["queue_us"]) == int(rec["queue_s"] * 1e6) and int(s["slot"]) in (0, 1)
        assert int(s["cached_tokens"]) == (12 if s["rid"] == "req-hit" else 0)
    for _, _, s in instants["rt.engine.first_token"]:
        rec = records[str(s["rid"])]
        assert int(s["ttft_us"]) == int(rec["ttft_s"] * 1e6)
        assert int(s["prefill_wait_us"]) == int(rec["prefill_wait_s"] * 1e6) >= 0
        assert int(s["chunks"]) == rec["phases"]["prefill-chunk"]["count"]
    assert {(str(s["rid"]), int(s["tokens"]), str(s["status"])) for _, _, s in instants["rt.engine.finish"]} == {
        (rid, rec["tokens"], "ok") for rid, rec in records.items()}
    # an iteration says why its plan ran the steps it ran, and what time it was on the records' clock
    # (prompts one at a time: a chunk's plan has no slot decoding; the 5 tokens after the first are
    # a full run of 4 and a last one alone)
    assert {str(s["limit"]) for _, _, s in iters} == {"no_decode", "none", "tail", "sampling"}
    assert all(int(s["steps_max"]) == 4 for _, _, s in iters)
    assert [(str(s["limit"]), int(s["steps"])) for _, _, s in iters if s["limit"] in ("none", "tail")] == [("none", 4), ("tail", 1)]
    assert all({"waiting", "prefilling", "unix_us"} <= set(s) for _, _, s in iters)
    t_lo = min(r["t_submit"] for r in records.values())
    assert all(t_lo - 1 < int(s["unix_us"]) / 1e6 < t_lo + 600 for _, _, s in iters)
    # An insert is the gather's dispatch, a `rt.engine.kv_insert` inside the prompt's last
    # chunk, and its hand-over to the pool: `rt.engine.kv_copy` on the worker thread, or a
    # second `rt.engine.kv_insert` inside the `rt.engine.plan` of a lookup that came first.
    handed = events.pop("rt.engine.kv_copy", []) + events["rt.engine.kv_insert"][1:]
    del events["rt.engine.kv_insert"][1:]
    assert len(handed) == 1 and int(handed[0][2]["rows"]) == 12, handed
    for name in _ITER_CHILDREN:
        for a, b, _ in events[name]:
            assert any(i0 <= a and b <= i1 for i0, i1, _ in iters), name
    for name in ("rt.engine.plan", "rt.engine.idle"):
        for a, b, _ in events[name]:
            assert not any(i0 < b and a < i1 for i0, i1, _ in iters), name
    steps = sorted({int(s["steps"]) for _, _, s in events["rt.engine.dispatch"]})
    assert steps[0] == 1 and steps[-1] > 1, steps           # single and multi-step dispatches
    assert all(int(s["rows"]) > 0 and int(s["slots"]) >= 1 for _, _, s in events["rt.engine.dispatch"])
    assert {str(s["rid"]) for _, _, s in events["rt.engine.attach"]} == {"req-hit"}
    assert "req-hit" in {str(s["rid"]) for _, _, s in events["rt.engine.prefill"]}
    assert all(int(s["rows"]) == 12 for _, _, s in events["rt.engine.kv_insert"])
    assert all(int(s["bytes"]) > 0 for _, _, s in events["rt.engine.readback"])


def test_scheduler_stats_reports_the_loop_table_with_distsan_silent():
    """`scheduler_stats()["loop"]` is the span table, read on the report path;
    the spans themselves touch no metric and make no GCS call from the loop."""
    from ray_tpu.devtools import distsan

    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1,
                          prefix_cache=False)
    try:
        before = _loop_counts()
        _generate(engine, [5, 9, 17, 3], max_tokens=4)
        loop = engine.scheduler_stats()["loop"]
        for name in ("rt.engine.iter", "rt.engine.plan", "rt.engine.prefill",
                     "rt.engine.dispatch", "rt.engine.readback", "rt.engine.sample"):
            assert loop[name]["count"] > before[name], name
            assert loop[name]["seconds"] > 0.0, name
        children = sum(loop[n]["seconds"] for n in _ITER_CHILDREN if n in loop)
        nested = loop.get("rt.engine.attach", {"seconds": 0})["seconds"]  # inside prefill, as kv_insert is
        assert loop["rt.engine.iter"]["seconds"] >= 0.5 * (children - nested)
        assert distsan.violations() == []
    finally:
        engine.shutdown()


# -- every program consumes the caches it is given (PERF.md §6, PR 33) ------------------


@contextlib.contextmanager
def _undonated():
    """A context in which `jax.jit` drops `donate_argnums`: engines built and driven
    inside it run the programs the engine ran before it donated, the reference here."""
    import jax

    real = jax.jit

    def jit(fn, **options):
        options.pop("donate_argnums", None)
        return real(fn, **options)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "jit", jit)
        yield


def _checking_programs(monkeypatch, seen):
    """Wrap every program an engine (or its draft) builds: after each call, note under
    the program's key whether the caches it was given are deleted."""
    import jax

    from ray_tpu.llm import DecodeEngine

    def caches_at(key):
        if isinstance(key, int):
            return 3                                   # rt_prefill_b<bucket>
        return {"decode": 4, "decode_multi": 4, "verify": 4, "attach": 0, "kv_gather": 0,
                "propose": 1, "dprefill": 1}.get(key[0])

    def checking(key, prog):
        at = caches_at(key)
        if at is None:
            return prog

        def call(*args):
            out = prog(*args)
            given = jax.tree_util.tree_leaves(args[at])
            seen.setdefault("prefill" if isinstance(key, int) else key[0], []).append(
                all(a.is_deleted() for a in given))
            return out

        return call

    real_program = DecodeEngine._program

    def program(self, cache, key, make):
        return checking(key, real_program(self, cache, key, make))

    monkeypatch.setattr(DecodeEngine, "_program", program)
    return checking


@pytest.fixture(scope="module")
def consumed():
    """Drive every program that takes the caches once or more, and return, by program,
    whether each call left the caches it was given deleted."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm.kvcache import PrefixCacheManager

    seen = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
        checking = _checking_programs(patch, seen)
        prompt = list(range(1, 14))
        # prefill, decode, the multi-step scan, the insert's gather and a hit's attach
        engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=4,
                              prefix_cache=PrefixCacheManager(4, 1 << 20, name="consumed"))
        engine._jit_decode = checking(("decode",), engine._jit_decode)
        try:
            _generate(engine, prompt, max_tokens=6)
            _generate(engine, prompt, max_tokens=6)
            assert engine.last_attach["cached_tokens"] == 12
            _generate(engine, prompt[:5], max_tokens=3, temperature=0.7)  # sampled: a round of two steps, drawn in the program
        finally:
            engine.shutdown()
        # the verify round and the draft model's two programs over its own caches
        engine = _tiny_engine(num_slots=2, max_seq=64, prefix_cache=False,
                              spec_config={"num_spec_tokens": 3})
        try:
            _generate(engine, prompt, max_tokens=9)
        finally:
            engine.shutdown()
    return seen


@pytest.mark.parametrize("program", ["decode", "decode_multi", "prefill", "attach", "verify",
                                     "propose", "dprefill"])
def test_a_program_leaves_the_caches_it_was_given_deleted(consumed, program):
    """The donation was taken: after `rt_decode`, `rt_decode_multi_n<n>`, `rt_prefill_b<b>`,
    `rt_attach_b<b>`, `rt_verify_s<S>` and the draft's `rt_draft_propose_k<k>` and
    `rt_draft_prefill_b<b>`, the previous generation of the caches is gone. A program
    that copied them would leave them alive."""
    assert consumed.get(program), sorted(consumed)
    assert all(consumed[program]), consumed[program]


def test_the_gather_reads_the_caches_and_leaves_them(consumed):
    """`rt_kv_gather_b<b>` returns a new array and consumes nothing."""
    assert consumed.get("kv_gather") and not any(consumed["kv_gather"]), consumed.get("kv_gather")


@pytest.mark.parametrize("round_", ["decode", "multi_step"])
def test_an_insert_issued_just_before_a_consuming_step_holds_the_rows_it_saw(monkeypatch, round_):
    """`_insert_prompt_kv` dispatches the gather on the caches and the very next program
    consumes those buffers and writes into them. The runtime orders the donation after
    the read already enqueued: the rows the pool gets are the rows of a reference gather
    taken from a host copy beforehand, whatever the step then wrote."""
    import jax.numpy as jnp

    engine = _cache_engine(monkeypatch, decode_loop=False)
    rng = np.random.default_rng(7)
    try:
        for turn in range(4):
            host = [(rng.standard_normal(ck.shape).astype(np.float32), rng.standard_normal(cv.shape).astype(np.float32))
                    for ck, cv in engine._caches]
            engine._caches = [(jnp.asarray(k, ck.dtype), jnp.asarray(v, cv.dtype))
                              for (k, v), (ck, cv) in zip(host, engine._caches)]
            given = [a for layer in engine._caches for a in layer]
            want = np.stack([np.stack([np.asarray(ck)[1, :12], np.asarray(cv)[1, :12]])
                             for ck, cv in engine._caches])
            prompt = [100 * turn + i for i in range(1, 14)]   # 12 rows of whole blocks, bucket 16
            engine._lens[:] = 3                               # the step writes rows inside [0, 12)
            engine._insert_prompt_kv(1, prompt, 0, 0)
            if round_ == "decode":
                engine._decode_round([0, 1])
            else:
                engine._multi_round([0, 1], 4)
            assert all(a.is_deleted() for a in given)
            wrote = np.asarray(engine._caches[0][0])[1, 3]
            assert not np.array_equal(wrote, want[0, 0, 3])   # and the step did write there
            engine._finish_kv_inserts()
            lease = engine._prefix_cache.lookup(prompt)
            try:
                assert lease is not None and lease.matched_tokens == 12
                np.testing.assert_array_equal(lease.kv(), want)
            finally:
                lease.release()
    finally:
        engine.shutdown()


def _greedy_ids(path):
    """The greedy ids of one path that hands rows to, or verifies against, consumed caches."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.kvcache import PrefixCacheManager

    prompt = list(range(1, 14))
    if path == "prefix_attach":
        engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1,
                              prefix_cache=PrefixCacheManager(4, 1 << 20, name="ids"))
        try:
            first = _generate(engine, prompt, max_tokens=8)
            second = _generate(engine, prompt, max_tokens=8)
            assert engine.last_attach["cached_tokens"] == 12
            return first + second
        finally:
            engine.shutdown()
    if path == "spec_verify":
        engine = _tiny_engine(num_slots=2, max_seq=64, prefix_cache=False,
                              spec_config={"num_spec_tokens": 3})
        try:
            out = _generate(engine, prompt, max_tokens=12)
            assert engine.scheduler_stats()["spec"]["rounds"] > 0
            return out
        finally:
            engine.shutdown()
    assert path == "pd_attach"
    prefiller = _tiny_engine(num_slots=1, max_seq=64, decode_loop=False, prefix_cache=False)
    decoder = _tiny_engine(num_slots=2, max_seq=64, prefix_cache=False)
    try:
        first_logits, kv, plen = prefiller.prefill_detached(prompt)
        return _collect(decoder, lambda cb: decoder.submit_prefilled(
            kv, plen, first_logits, SamplingParams(max_tokens=8), cb))
    finally:
        prefiller.shutdown()
        decoder.shutdown()


@pytest.mark.parametrize("path", ["prefix_attach", "pd_attach", "spec_verify"])
def test_attached_and_verified_rows_give_the_ids_of_an_undonated_run(monkeypatch, path):
    """A prefix-cache attach, a PD attach and a speculative verify round each write into
    caches the program consumed: the greedy ids are those of the same engine built with
    no `donate_argnums` anywhere."""
    from ray_tpu._private.config import CONFIG

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    with _undonated():
        want = _greedy_ids(path)
    got = _greedy_ids(path)
    assert got == want and len(got) >= 8


@pytest.mark.parametrize("tp", [1, 2])
def test_reports_from_another_thread_never_touch_a_consumed_array(monkeypatch, tp):
    """`_memory_owner_report` (the memory ledger's callback) and `scheduler_stats()` run on
    report threads while the stepper's programs consume one generation of the caches after
    another, under a mesh too, where the per-device split used to walk the arrays' shards."""
    import sys

    import jax

    if tp > len(jax.devices()):
        pytest.skip("needs 2 devices")
    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=2, prefix_cache=False, tp=tp)
    errors, stop, reports = [], threading.Event(), [0]

    def report():
        while not stop.is_set():
            try:
                row = engine._memory_owner_report()
                stats = engine.scheduler_stats()
                assert row["components"]["kv_slots"] == stats["model"]["cache_bytes"] > 0
                if tp > 1:
                    assert sum(row["per_device"].values()) == row["components"]["kv_slots"]
                reports[0] += 1
            except Exception as e:  # noqa: BLE001 - whatever a consumed array raises
                errors.append(e)
                return

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    thread = threading.Thread(target=report, daemon=True)
    thread.start()
    try:
        for start in (1, 21, 41):
            _generate(engine, list(range(start, start + 9)), max_tokens=12)
    finally:
        stop.set()
        thread.join(30)
        sys.setswitchinterval(switch)
        engine.shutdown()
    assert not errors, errors
    assert reports[0] > 0


def test_a_program_that_raises_after_consuming_the_caches_ends_the_engine(monkeypatch):
    """What the stepper does when a program fails once its inputs are gone (for every
    block): the request in flight ends with the error, the engine refuses further work
    and never dispatches on the deleted arrays again, and the reports still answer."""
    from ray_tpu.llm import SamplingParams

    engine = _tiny_engine(num_slots=2, max_seq=64, multi_step=1, prefix_cache=False)
    real = engine._jit_decode
    given = []

    def failing(*args):
        real(*args)
        given.extend(a for layer in args[4] for a in layer)
        raise RuntimeError("RESOURCE_EXHAUSTED: planted")

    engine._jit_decode = failing
    try:
        acc = _generate(engine, list(range(1, 10)), max_tokens=8)
        engine._thread.join(30)
        assert acc[-1] == -1 and isinstance(engine.error, RuntimeError)
        assert given and all(a.is_deleted() for a in given)
        assert all(a.is_deleted() for layer in engine._caches for a in layer)
        with pytest.raises(RuntimeError, match="stepper died"):
            engine.submit([1, 2, 3], SamplingParams(max_tokens=2), lambda tok, fin: None)
        assert engine.scheduler_stats()["model"]["cache_bytes"] > 0
        assert engine._memory_owner_report()["components"]["kv_slots"] > 0
    finally:
        engine.shutdown()


# -- the engine holds the dense block's weights in the type its programs multiply in -----


def _wide_tree(cfg_name="test-tiny", **cfg_kw):
    """(cfg, tree): a tiny dense model that computes in bfloat16 over float32 weights, as a
    train step or a checkpoint leaves them (`param_dtype`), flax's boxes stripped."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.models.transformer import get_config
    from ray_tpu.parallel.mesh import unbox

    cfg = get_config(cfg_name, scan_layers=False, remat=False, dtype=jnp.bfloat16, **cfg_kw)
    assert cfg.param_dtype == jnp.float32
    return cfg, unbox(llama.init_params(cfg, jax.random.PRNGKey(0)))


@contextlib.contextmanager
def _serving_the_tree_as_given():
    """Engines built inside keep the dense block's tree in the types it came in: what every
    engine did before the block said which leaves it reads through a cast."""
    from ray_tpu.models import llama

    was = llama.serving_params
    llama.serving_params = lambda cfg, params: params
    try:
        yield
    finally:
        llama.serving_params = was


def test_an_engine_built_from_a_float32_tree_holds_what_its_programs_multiply_in():
    """Kernels and table in `cfg.dtype`, cast once at construction; norm scales as given;
    the tree the caller passed untouched; `scheduler_stats()["model"]` counts the tree's
    bytes and those that were cast."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine

    cfg, tree = _wide_tree()
    engine = DecodeEngine(cfg, tree, num_slots=2, max_seq=64, decode_loop=False, prefix_cache=False)
    try:
        flat = {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(engine.params)[0]}
        kernels = {k: v for k, v in flat.items() if k.endswith("['kernel']") or k == "['embedding']"}
        scales = {k: v for k, v in flat.items() if k.endswith("['scale']")}
        assert len(kernels) == 7 * cfg.n_layers + 2 and len(scales) == 2 * cfg.n_layers + 1
        assert len(kernels) + len(scales) == len(flat)
        assert {v.dtype for v in kernels.values()} == {jnp.dtype(jnp.bfloat16)}
        assert {v.dtype for v in scales.values()} == {jnp.dtype(jnp.float32)}
        assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(tree))  # the caller's own
        given = jax.tree_util.tree_leaves(tree)
        for (path, leaf), was in zip(jax.tree_util.tree_flatten_with_path(engine.params)[0], given):
            assert np.array_equal(np.asarray(leaf), np.asarray(was.astype(leaf.dtype))), path
        model = engine.scheduler_stats()["model"]
        assert model["weight_bytes_cast"] == sum(2 * v.size for v in kernels.values())
        assert model["weight_bytes"] == model["weight_bytes_cast"] + sum(4 * v.size for v in scales.values())
    finally:
        engine.shutdown()


@pytest.mark.parametrize("program", ["rt_prefill_b16", "rt_decode", "rt_decode_multi_n4"])
def test_the_served_trees_programs_give_the_float32_trees_logits_bit_for_bit(program):
    """The engine's own bodies, jitted as the engine jits them, over the tree it holds and over
    the float32 tree it was given: the same tokens, logits and cache rows to the last bit (every
    product read `bf16(w)` before and reads it now; the cast moved, the value did not)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine

    cfg, tree = _wide_tree()
    engine = DecodeEngine(cfg, tree, num_slots=2, max_seq=64, decode_loop=False, prefix_cache=False)
    try:
        assert engine.params["lm_head"]["kernel"].dtype == jnp.bfloat16 and tree["lm_head"]["kernel"].dtype == jnp.float32
        prompt = np.zeros((1, 16), np.int32)
        prompt[0, :11] = np.arange(3, 14)
        prefill = jax.jit(engine._prefill_at)
        ids = jnp.zeros((2,), jnp.int32)

        def run(params):
            caches = engine._block.init_caches(cfg, 2, 64)
            last, caches = prefill(params, None, jnp.asarray(prompt), caches, jnp.int32(1), jnp.int32(0),
                                   jnp.int32(11), jnp.int32(0))
            if program == "rt_prefill_b16":
                return last, caches
            step = (params, None, ids, jnp.asarray([0, 7], jnp.int32), caches, jnp.asarray([0, 11], jnp.int32),
                    jnp.asarray([False, True]))
            step += (jnp.zeros((2,), jnp.float32), jax.random.PRNGKey(0))
            if program == "rt_decode":
                return jax.jit(engine._decode_sample)(*step)
            return jax.jit(functools.partial(engine._decode_multi, n=4))(*step)

        served, wide = run(engine.params), run(tree)
        for got, want in zip(jax.tree_util.tree_leaves(served), jax.tree_util.tree_leaves(wide), strict=True):
            assert got.dtype == want.dtype and np.array_equal(np.asarray(got), np.asarray(want))
        logits = jax.tree_util.tree_leaves(served)[0 if program == "rt_prefill_b16" else 1]
        if program != "rt_decode_multi_n4":
            assert logits.dtype == jnp.float32 and logits.shape[-1] == cfg.vocab_size and float(jnp.std(logits)) > 0
    finally:
        engine.shutdown()


@pytest.mark.parametrize("program", ["rt_decode", "rt_decode_multi_n4"])
def test_the_decode_programs_through_the_kernel_that_writes_give_the_xla_paths_step(monkeypatch, program):
    """The engine's decode bodies as the TPU runs them (the kernel `cached_attn` writes the step's
    K and V rows itself and attends; here interpreted, heads of 128) against the same bodies on
    XLA's gated write and the two products: the same tokens, the first layer's slabs to the last
    bit (its rows are a function of the tokens alone; a gated-off slot's untouched), and the
    logits and the later layers' rows as near as another order of the softmax's sums leaves them."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine
    from ray_tpu.models import llama
    from ray_tpu.models.transformer import get_config
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import unbox

    cfg = get_config("test-tiny", hidden=256, n_heads=2, n_kv_heads=1, scan_layers=False, remat=False)
    assert cfg.head_dim == 128 and attention.cached_attention_takes(cfg.head_dim)
    engine = DecodeEngine(cfg, unbox(llama.init_params(cfg, jax.random.PRNGKey(0))), num_slots=3, max_seq=64,
                          decode_loop=False, prefix_cache=False)
    try:
        prompt = np.zeros((1, 16), np.int32)
        prompt[0, :11] = np.arange(3, 14)
        prefill = jax.jit(engine._prefill_at)

        def run():
            caches = engine._block.init_caches(cfg, 3, 64)
            for slot in (1, 2):
                _, caches = prefill(engine.params, None, jnp.asarray(prompt), caches, jnp.int32(slot), jnp.int32(0),
                                    jnp.int32(11), jnp.int32(0))
            before = [np.asarray(k) for k, _ in caches]
            step = (engine.params, None, jnp.zeros((3,), jnp.int32), jnp.asarray([0, 7, 9], jnp.int32), caches,
                    jnp.asarray([0, 11, 11], jnp.int32), jnp.asarray([False, True, True]),
                    jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0))
            body = engine._decode_sample if program == "rt_decode" else functools.partial(engine._decode_multi, n=4)
            return before, jax.jit(body)(*step)

        before, want = run()
        calls = []
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
        monkeypatch.setattr(attention, "cached_attention", lambda *a, kernel=attention.cached_attention, **kw: (
            calls.append(kw["new_k"].shape), kernel(*a, **{**kw, "interpret": True}))[1])
        _, got = run()
        assert calls == [(3, 1, 1, 128)] * cfg.n_layers  # traced once a layer, a scan's body once for its steps
        steps = 1 if program == "rt_decode" else 4
        got_caches, want_caches = got[2 if program == "rt_decode" else 1], want[2 if program == "rt_decode" else 1]
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))  # the tokens
        for layer, ((gk, gv), (wk, wv)) in enumerate(zip(got_caches, want_caches)):
            for g, w in ((gk, wk), (gv, wv)):
                np.testing.assert_array_equal(np.asarray(g)[0], np.asarray(w)[0])  # the gated-off slot
                np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)
            np.testing.assert_array_equal(np.asarray(gk)[0], before[layer][0])
            assert not np.array_equal(np.asarray(gk)[1, 11:11 + steps], before[layer][1, 11:11 + steps])  # the rows landed
        np.testing.assert_array_equal(np.asarray(got_caches[0][0])[:, :12], np.asarray(want_caches[0][0])[:, :12])
        if program == "rt_decode":
            np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=2e-4, atol=2e-4)  # the logits
    finally:
        engine.shutdown()


def _served_greedy_ids(path):
    """Greedy ids through one path that reads `engine.params` somewhere else than the plain
    decode round, from an engine built from the float32 tree."""
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, tree = _wide_tree()
    prompt = list(range(1, 14))
    build = lambda **kw: DecodeEngine(cfg, tree, max_seq=64, **{"num_slots": 2, "prefix_cache": False, **kw})  # noqa: E731
    if path == "pd":
        prefiller, decoder = build(num_slots=1, decode_loop=False), build()
        try:
            first_logits, kv, plen = prefiller.prefill_detached(prompt)
            return _collect(decoder, lambda cb: decoder.submit_prefilled(
                kv, plen, first_logits, SamplingParams(max_tokens=8), cb))
        finally:
            prefiller.shutdown()
            decoder.shutdown()
    engine = build(**{
        "lora": dict(lora_config={"max_loras": 2, "rank": 2}),
        "tp2": dict(tp=2),
        "speculation": dict(spec_config={"num_spec_tokens": 3}),
        "early_exit_draft": dict(spec_config={"num_spec_tokens": 3, "draft_layers": 1}),
        "prefix_attach": dict(multi_step=1, prefix_cache=PrefixCacheManager(4, 1 << 20, name="served")),
    }[path])
    try:
        if path == "lora":
            rng = np.random.default_rng(0)
            engine.add_lora("a", {0: {"q_A": rng.normal(size=(cfg.hidden, 2)).astype(np.float32),
                                      "q_B": rng.normal(size=(2, cfg.hidden)).astype(np.float32)}}, alpha=4.0)
            return _generate(engine, prompt, max_tokens=8) + _generate(engine, prompt, max_tokens=8, lora="a")
        out = _generate(engine, prompt, max_tokens=12)
        if path == "prefix_attach":
            out += _generate(engine, prompt, max_tokens=12)
            assert engine.last_attach["cached_tokens"] == 12
        if path in ("speculation", "early_exit_draft"):
            assert engine.scheduler_stats()["spec"]["rounds"] > 0
        return out
    finally:
        engine.shutdown()


@pytest.mark.parametrize("path", ["lora", "tp2", "speculation", "early_exit_draft", "prefix_attach", "pd"])
def test_the_served_tree_gives_the_float32_trees_greedy_ids(monkeypatch, path):
    """Everything that takes `engine.params` beside the decode round (LoRA's base kernels, the
    TP engine's shards, a speculative round's verify and its drafts, a prefix-cache attach's
    re-prefill, PD's detached prefill and `submit_prefilled`) emits, from the tree the engine
    holds, the ids of an engine that keeps the float32 tree it was given."""
    import jax

    from ray_tpu._private.config import CONFIG

    if path == "tp2" and len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    with _serving_the_tree_as_given():
        want = _served_greedy_ids(path)
    got = _served_greedy_ids(path)
    assert got == want and len(got) >= 8


def test_a_tree_in_the_served_type_is_held_as_the_very_arrays():
    """Nothing to cast: every leaf the engine holds is the array it was given (no copy), and
    `weight_bytes_cast` says 0. So for a tree that arrives partly cast."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine
    from ray_tpu.models import llama

    cfg, wide = _wide_tree()
    served = llama.serving_params(cfg, jax.tree_util.tree_map(lambda x: x, wide))
    engine = DecodeEngine(cfg, served, num_slots=2, max_seq=64, decode_loop=False, prefix_cache=False)
    try:
        for got, given in zip(jax.tree_util.tree_leaves(engine.params), jax.tree_util.tree_leaves(served), strict=True):
            assert got is given
        model = engine.scheduler_stats()["model"]
        assert model["weight_bytes_cast"] == 0
        assert model["weight_bytes"] == sum(x.nbytes for x in jax.tree_util.tree_leaves(served))
    finally:
        engine.shutdown()
    mixed = jax.tree_util.tree_map(lambda x: x, served)
    mixed["lm_head"]["kernel"] = wide["lm_head"]["kernel"]
    engine = DecodeEngine(cfg, mixed, num_slots=2, max_seq=64, decode_loop=False, prefix_cache=False)
    try:
        assert engine.params["embedding"] is served["embedding"]
        assert engine.params["lm_head"]["kernel"].dtype == jnp.bfloat16
        assert engine.scheduler_stats()["model"]["weight_bytes_cast"] == 2 * wide["lm_head"]["kernel"].size
    finally:
        engine.shutdown()


def test_a_tree_handed_over_as_a_function_goes_leaf_by_leaf_as_it_is_cast(monkeypatch):
    """A replica hands the engine `load_model` as a function and keeps no tree: while the engine
    casts, every float32 leaf already cast is gone (nothing holds it), so start-up never has
    both trees whole; a tree passed as a tree stays its caller's."""
    import gc
    import weakref

    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.llm import DecodeEngine

    cfg = _wide_tree()[0]
    wide, alive_at_cast = [], []

    def load():
        _, tree = _wide_tree()
        wide.extend(weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(tree))
        return tree

    wait = jax.block_until_ready

    def counting(x):
        gc.collect()
        alive_at_cast.append(sum(ref() is not None for ref in wide))
        return wait(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    engine = DecodeEngine(cfg, load, num_slots=2, max_seq=64, decode_loop=False, prefix_cache=False)
    monkeypatch.undo()
    try:
        casts = 7 * cfg.n_layers + 2
        scales = 2 * cfg.n_layers + 1
        assert len(wide) == casts + scales and len(alive_at_cast) == casts
        # at the n-th cast's wait the n - 1 leaves cast before it are free (the n-th is the loop's own)
        assert all(alive <= len(wide) - n for n, alive in enumerate(alive_at_cast)), alive_at_cast
        gc.collect()
        assert sum(ref() is not None for ref in wide) == scales  # held on, as given
        assert all(leaf.dtype == (jnp.float32 if leaf.ndim == 1 else jnp.bfloat16)
                   for leaf in jax.tree_util.tree_leaves(engine.params))
    finally:
        engine.shutdown()
    assert models.cast_leaves({}, jnp.bfloat16, lambda path: True) == {}


def test_a_replica_keeps_no_tree_beside_its_engines_and_weights_returns_that(monkeypatch):
    """`LLMServer` (and the PD servers and the batch stage, which build their engine the same
    way) hands `load_model` over as a function: the one tree of the process is the engine's,
    and `weights()` returns it, in the served types."""
    import gc

    import jax
    import jax.numpy as jnp

    from ray_tpu.data.llm import EngineProcessorConfig, EngineStage
    from ray_tpu.llm import LLMConfig, LLMServer
    from ray_tpu.llm.pd_disagg import DecodeServer, PrefillServer

    cfg = _wide_tree(vocab_size=272, mlp_dim=144)[0]  # widths no other test's engine has
    config = LLMConfig(model_id="tiny-wide", model_config=cfg, num_slots=2, max_seq=64)
    server = LLMServer(config)
    try:
        got_cfg, params = server.weights()
        assert got_cfg == cfg and params is server._engine.params
        assert params["embedding"].dtype == jnp.bfloat16 and params["final_norm"]["scale"].dtype == jnp.float32
        assert server._engine.scheduler_stats()["model"]["weight_bytes_cast"] > 0
    finally:
        server._engine.shutdown()
    stage = EngineStage(EngineProcessorConfig(model_id="tiny-wide", model_config=cfg,
                                              engine_kwargs={"num_slots": 2, "max_seq": 64}))
    builders = {"LLMServer": lambda: LLMServer(config), "PrefillServer": lambda: PrefillServer(config),
                "DecodeServer": lambda: DecodeServer(config), "EngineStage": lambda: stage}
    for name, build in builders.items():
        owner = build()
        try:
            gc.collect()
            wide = [x.shape for x in gc.get_objects() if isinstance(x, jax.Array) and x.dtype == jnp.float32
                    and x.ndim == 2 and {cfg.vocab_size, cfg.mlp_dim} & set(x.shape)]  # the table, the head, the MLPs
            assert not wide, (name, wide)
            held = [x.shape for x in jax.tree_util.tree_leaves(owner._engine.params) if x.dtype == jnp.bfloat16]
            assert len(held) == 7 * cfg.n_layers + 2
        finally:
            owner._engine.shutdown()


def test_granite_hybrids_recurrence_parameters_stay_float32_in_the_engine():
    """A block whose `init_params` draws in the served type hands the engine's tree back as it
    came: `A_log` and `dt_bias` float32 beside bfloat16 matrices, the very arrays."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine
    from ray_tpu.models import granite_hybrid
    from ray_tpu.models.transformer import ModelConfig

    cfg = ModelConfig(block="granite_hybrid", vocab_size=64, hidden=32, n_layers=2, n_heads=4, n_kv_heads=2,
                      mlp_dim=64, max_seq=64, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, scan_layers=False,
                      remat=False, tie_embeddings=True, layer_types=("mamba", "attention"), mamba_n_heads=4,
                      mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8, attention_multiplier=0.25,
                      position_embedding_type="nope")
    tree = granite_hybrid.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(cfg, tree, num_slots=2, max_seq=64, decode_loop=False)
    try:
        flat = {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(engine.params)[0]}
        wide = {k for k, v in flat.items() if v.dtype == jnp.float32}
        assert wide and all(k.endswith(("['A_log']", "['dt_bias']")) for k in wide), wide
        assert any(v.dtype == jnp.bfloat16 for v in flat.values())
        for got, given in zip(jax.tree_util.tree_leaves(engine.params), jax.tree_util.tree_leaves(tree), strict=True):
            assert got is given
        assert engine.scheduler_stats()["model"]["weight_bytes_cast"] == 0
    finally:
        engine.shutdown()
