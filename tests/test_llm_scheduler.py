"""Iteration-level scheduler (ray_tpu/llm/scheduler, docs/scheduler.md):
chunked prefill interleaved with decode under a token budget, and
speculative decoding as a scheduler-scheduled phase with batched verify.

The load-bearing invariants:
- greedy output is TOKEN-IDENTICAL across every scheduling shape (whole
  prompt vs chunked, cached prefix vs cold, spec vs plain decode);
- a long prefill cannot stall in-flight decodes beyond the token budget;
- prefix-cache hits stay spec-eligible (the PR-3 behavior of silently
  downgrading to plain decode is gone);
- every chunk shape comes from the static bucket table (no new programs).
"""

import threading

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return cfg, model, params


def _generate(engine, prompt, n, **sp):
    from ray_tpu.llm import SamplingParams

    out, done = [], threading.Event()

    def cb(tok, fin):
        out.append(tok)
        if fin:
            done.set()

    engine.submit(prompt, SamplingParams(max_tokens=n, **sp), cb)
    assert done.wait(180), engine.error
    return out


# -- scheduler unit tests (no device work) ---------------------------------


def _unit_sched(**kw):
    from ray_tpu.llm.scheduler import Scheduler

    args = dict(num_slots=2, buckets=(16, 32, 64, 128), max_seq=128,
                token_budget=64, max_queue_depth=0, multi_step=1)
    args.update(kw)
    return Scheduler(**args)


def _fake_running(sched, slot, max_tokens=1000):
    """Put a fabricated request into the decode phase on `slot`."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request

    req = Request("prompt", prompt=[1, 2, 3],
                  sampling=SamplingParams(max_tokens=max_tokens),
                  callback=lambda *a: None)
    req.slot = slot
    sched.start_decode(req, 7)
    return req


def test_scheduler_chunks_long_prefill_and_never_stalls_decode():
    """Unit-level starvation bound: with a decode in flight, a long prompt
    is split into bucketed chunks and EVERY iteration still schedules the
    decode slot — prefill can never exclude decode from an iteration."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request

    sched = _unit_sched(token_budget=64)
    _fake_running(sched, 0)
    long_req = Request("prompt", prompt=list(range(1, 121)),
                       sampling=SamplingParams(max_tokens=4),
                       callback=lambda *a: None)
    sched.submit(long_req)

    chunks_seen, iters = [], 0
    while long_req.prefilled < long_req.prompt_len:
        iters += 1
        assert iters < 20, "prefill failed to make progress"
        plan = sched.next_plan()
        assert plan.decode_slots == [0], "decode stalled by prefill"
        # budget respected: decode reserved first, chunks fill the rest
        assert plan.decode_tokens + plan.prefill_tokens <= 64
        assert plan.chunks, "no prefill progress scheduled"
        for chunk in plan.chunks:
            assert chunk.bucket in (16, 32, 64, 128)
            chunks_seen.append(len(chunk.tokens))
            sched.chunk_done(chunk)
        sched.slots[0].generated += 1  # simulate the decode phase
    assert len(chunks_seen) >= 3, chunks_seen   # 120 tokens / <=63-token grants
    assert sum(chunks_seen) == 120
    stats = sched.stats()
    assert stats["interleaved_iterations"] == iters
    assert stats["prefill_chunks"] == len(chunks_seen)


def test_scheduler_head_of_line_prefill_progress_under_full_decode_load():
    """Even when decode reservations consume the whole budget, the
    head-of-line prefill still gets one minimum bucket per iteration."""
    sched = _unit_sched(num_slots=8, token_budget=8)  # 8 decode slots > budget
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request

    for i in range(7):
        _fake_running(sched, i)
    req = Request("prompt", prompt=list(range(1, 40)),
                  sampling=SamplingParams(max_tokens=2),
                  callback=lambda *a: None)
    sched.submit(req)
    plan = sched.next_plan()
    assert len(plan.decode_slots) == 7
    assert len(plan.chunks) == 1 and plan.chunks[0].bucket == 16


def test_scheduler_unbudgeted_mode_is_whole_prompt():
    """token_budget=0 reproduces the legacy shape: one whole-prompt chunk."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request

    sched = _unit_sched(token_budget=0)
    req = Request("prompt", prompt=list(range(1, 121)),
                  sampling=SamplingParams(max_tokens=4),
                  callback=lambda *a: None)
    sched.submit(req)
    plan = sched.next_plan()
    assert len(plan.chunks) == 1
    assert len(plan.chunks[0].tokens) == 120
    assert plan.chunks[0].is_first and plan.chunks[0].is_last


def test_scheduler_queue_cap_and_drain():
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request
    from ray_tpu.llm.scheduler.scheduler import EngineOverloadedError

    sched = _unit_sched(max_queue_depth=2)
    mk = lambda: Request("prompt", prompt=[1, 2],
                         sampling=SamplingParams(), callback=lambda *a: None)
    sched.submit(mk())
    sched.submit(mk())
    with pytest.raises(EngineOverloadedError, match="admission queue"):
        sched.submit(mk())
    assert len(sched.drain()) == 2
    assert sched.queue_depth() == 0


# -- a plan says what held it under `multi_step` (docs/scheduler.md) ---------


class _OneSlotDraft:
    """A draft that proposes two tokens for slot 0 and nothing for any other."""

    k = 2

    def eligible(self, i, s):
        return i == 0

    def propose(self, i, s):
        return [11, 12]


def _limit_case(limit):
    """(scheduler in a crafted state, draft, slots its plan decodes, steps, its chunks as
    (tokens, bucket)): one state that gives a plan that `limit`."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler import Request

    def prompt_request(n=40):
        return Request("prompt", prompt=list(range(1, n + 1)), sampling=SamplingParams(max_tokens=4),
                       callback=lambda *a: None)

    sched = _unit_sched(multi_step=1 if limit == "off" else 8)
    draft = None
    if limit != "no_decode":
        _fake_running(sched, 0)
    if limit in ("none", "tail", "spec", "queue"):
        _fake_running(sched, 1, max_tokens=7 if limit == "tail" else 1000)  # 6 left: under 8, and 4 a bucket
    if limit == "sampling":
        sched.slots[0].params = SamplingParams(max_tokens=1000, temperature=0.7, top_k=40)  # a row the host draws
    if limit in ("no_decode", "chunk", "queue"):
        sched.submit(prompt_request())  # admitted to a free slot, or left queued where both decode
    if limit == "prefilling":
        done = prompt_request()        # admitted, every row cached: nothing of it left to run
        done.slot, done.prefilled = 1, done.prompt_len
        sched._prefilling.append(done)
    if limit == "spec":
        draft = _OneSlotDraft()
    decode_slots = {"no_decode": [], "spec": [1], "none": [0, 1], "tail": [0, 1], "queue": [0, 1]}.get(limit, [0])
    steps = {"none": 8, "tail": 4}.get(limit, 1)
    # a 40-token prompt under a budget of 64: whole in a bucket of 64, or 32 of it beside a decoding slot
    chunks = {"no_decode": [(40, 64)], "chunk": [(32, 32)]}.get(limit, [])
    return sched, draft, decode_slots, steps, chunks


def _the_parents_steps(sched, plan):
    """`next_plan`'s multi-step decision as it stood before a plan carried its reason."""
    if not (sched.multi_step > 1 and plan.decode_slots and not plan.chunks and not plan.spec_slots
            and not sched._prefilling and sched.queue_depth() == 0):
        return 1
    slots = [sched.slots[i] for i in plan.decode_slots]
    if any(s.params.temperature > 0 or s.constraint is not None for s in slots):
        return 1
    n = max(1, min(sched.multi_step, min(s.params.max_tokens - s.generated for s in slots)))
    return 1 << (n.bit_length() - 1)


@pytest.mark.parametrize("limit", ["none", "off", "no_decode", "spec", "chunk", "prefilling",
                                   "queue", "sampling", "tail"])
def test_a_plan_says_what_held_it_under_multi_step(limit):
    """One crafted state a `Plan.limit` value: the plan names it, runs the steps the
    parent's rule ran (every other field what that state always planned), and
    `stats()["plans"]` counts the iteration, its decode tokens and what
    `decode_slots x multi_step` would have been under that limit and no other."""
    from ray_tpu.llm.scheduler.scheduler import LIMITS

    sched, draft, decode_slots, steps, chunks = _limit_case(limit)
    plan = sched.next_plan(draft=draft)
    assert (plan.limit, plan.steps_max) == (limit, sched.multi_step) and limit in LIMITS
    assert plan.multi_step == steps == _the_parents_steps(sched, plan)
    assert plan.decode_slots == decode_slots and plan.decode_tokens == len(decode_slots) * steps
    assert plan.spec_slots == ([0] if limit == "spec" else []) and plan.verify_tokens == (3 if limit == "spec" else 0)
    assert [(len(c.tokens), c.bucket) for c in plan.chunks] == chunks and not plan.idle
    assert plan.prefill_tokens == sum(bucket for _, bucket in chunks)
    stats = sched.stats()
    assert stats["plans"]["steps_max"] == sched.multi_step and set(stats["plans"]["by_limit"]) == set(LIMITS)
    for name, row in stats["plans"]["by_limit"].items():
        assert row == ({"iterations": 1, "decode_tokens": plan.decode_tokens,
                        "decode_tokens_possible": len(decode_slots) * sched.multi_step} if name == limit
                       else {"iterations": 0, "decode_tokens": 0, "decode_tokens_possible": 0}), name
    assert (stats["iterations"], stats["decode_tokens"]) == (1, plan.decode_tokens)
    assert (stats["spec_rounds"], stats["verify_tokens"]) == ((1, 3) if limit == "spec" else (0, 0))
    assert (stats["queue_depth"], stats["prefilling"]) == (int(limit == "queue"), len(chunks) + int(limit == "prefilling"))
    # the configuration a replica runs under, echoed for its operator (docs/scheduler.md)
    assert (stats["token_budget"], stats["wfq"], stats["tenant_quota"]) == (64, True, sched._tenant_quota)


@pytest.mark.parametrize("case, steps, limit", [
    ("plain temperature", 8, "none"),
    ("tail", 4, "tail"),
    ("guided", 1, "sampling"),
    ("top-k at a temperature", 1, "sampling"),
    ("top-k at temperature 0", 8, "none"),
])
def test_only_a_row_the_host_draws_holds_a_plan_to_one_step(case, steps, limit):
    """A greedy slot beside one slot of each kind, nothing else to run: a plain temperature is
    the program's to draw and plans every step `multi_step` allows (or what the slot's last
    tokens leave: 6 left is a bucket of 4); a guided slot and a top-k filter at a temperature
    are the host's (`_host_drawn`) and hold the plan to one step under `sampling`; at
    temperature 0 a top-k filter filters nothing. The engine's rounds ask the same predicate."""
    from ray_tpu.llm import SamplingParams, _engine
    from ray_tpu.llm.scheduler import scheduler

    assert _engine._host_drawn is scheduler._host_drawn
    sched = _unit_sched(multi_step=8)
    _fake_running(sched, 0)
    _fake_running(sched, 1)
    slot = sched.slots[1]
    slot.params = {
        "plain temperature": SamplingParams(max_tokens=1000, temperature=0.7),
        "tail": SamplingParams(max_tokens=7, temperature=0.7),
        "guided": SamplingParams(max_tokens=1000),
        "top-k at a temperature": SamplingParams(max_tokens=1000, temperature=0.7, top_k=40),
        "top-k at temperature 0": SamplingParams(max_tokens=1000, top_k=40),
    }[case]
    if case == "guided":
        slot.constraint = object()  # the rule asks only whether the slot carries one
    assert scheduler._host_drawn(slot.params, slot.constraint) == (limit == "sampling")
    assert sched._choose_multi_step([0, 1]) == (steps, limit)
    plan = sched.next_plan()
    assert (plan.multi_step, plan.limit, plan.decode_slots, plan.decode_tokens) == (steps, limit, [0, 1], 2 * steps)


# -- token-identity across scheduling shapes -------------------------------


def test_chunked_prefill_token_identical(tiny_model):
    """Multi-chunk prefill (budget forces >= 3 chunks) emits exactly the
    same greedy tokens as whole-prompt prefill."""
    from ray_tpu.llm import DecodeEngine

    cfg, model, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 70)))

    whole = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False, token_budget=0)
    chunked = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                           prefix_cache=False, token_budget=32)
    try:
        expect = _generate(whole, prompt, 8)
        got = _generate(chunked, prompt, 8)
        assert got == expect
        lp = chunked.last_prefill
        assert lp["chunks"] >= 3, lp        # 70 tokens through a 32 budget
        assert lp["offset"] == 0 and lp["prompt_len"] == 70
        stats = chunked.scheduler_stats()
        assert stats["prefill_chunks"] >= 3
    finally:
        whole.shutdown()
        chunked.shutdown()


def test_chunked_prefill_with_cached_prefix_token_identical(tiny_model):
    """Chunked prefill composes with prefix-cache leases: a warm hit
    attaches cached blocks, the SUFFIX prefills in chunks, and greedy
    output still matches the cache-disabled whole-prompt engine."""
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    rng = np.random.default_rng(7)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 48)))
    p1 = prefix + list(map(int, rng.integers(0, cfg.vocab_size, 40)))
    p2 = prefix + list(map(int, rng.integers(0, cfg.vocab_size, 37)))

    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False, token_budget=0)
    cached = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128, token_budget=32,
        prefix_cache=PrefixCacheManager(16, 8 << 20, name="sched-equiv"),
    )
    try:
        expect = [_generate(plain, p, 6) for p in (p1, p2)]
        got1 = _generate(cached, p1, 6)
        assert cached.last_prefill["offset"] == 0
        assert cached.last_prefill["chunks"] >= 2
        got2 = _generate(cached, p2, 6)
        lp = cached.last_prefill
        assert lp["offset"] == 48, lp       # 3 whole blocks attached
        assert lp["chunks"] >= 2, lp        # 37-token suffix through budget 32
        assert [got1, got2] == expect
        stats = cached.prefix_cache_stats()
        assert stats["hits"] == 1 and stats["leases_active"] == 0
    finally:
        plain.shutdown()
        cached.shutdown()


def _prompt_slot_kv(engine, prompt):
    """Host copy of the prompt's KV rows [0, len(prompt)) on whichever slot
    served it: [L, 2, len(prompt), Hkv, D]. Call only on an idle engine."""
    slot = next(i for i, s in enumerate(engine._slots)
                if s.history[: len(prompt)] == prompt)
    n = len(prompt)
    return np.stack([
        np.stack([np.asarray(ck[slot, :n]), np.asarray(cv[slot, :n])])
        for ck, cv in engine._caches
    ])


def test_long_prefill_does_not_stall_decode_integration(tiny_model):
    """Integration starvation bound AND interleaving correctness: tokens
    keep flowing on a running decode while a long prompt prefills in chunks,
    and BOTH streams emit exactly the tokens a whole-prompt (unchunked)
    reference engine emits. A decode dispatch that writes an ungated KV row
    into the mid-prefill slot (stale lens) corrupts the long prompt's cache
    permanently — sequential token-identity tests can never catch that. One
    corrupted row of ~110 may not flip a tiny model's argmax, so the
    prompt's KV rows themselves are ALSO compared against the reference
    (the decisive detector)."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    stream_prompt = [5, 9, 17]
    long_prompt = list(map(
        int, np.random.default_rng(0).integers(0, cfg.vocab_size, 110)))

    ref = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                       prefix_cache=False, token_budget=0)
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                          prefix_cache=False, token_budget=16, multi_step=1)
    try:
        # Sequential, whole-prompt prefill: no interleaving anywhere.
        expect_stream = _generate(ref, stream_prompt, 60)
        expect_long = _generate(ref, long_prompt, 4)

        stream_done = threading.Event()
        stream_out = []

        def stream_cb(tok, fin):
            stream_out.append(tok)
            if fin:
                stream_done.set()

        engine.submit(stream_prompt, SamplingParams(max_tokens=60), stream_cb)
        while len(stream_out) < 5:          # the stream is decoding
            assert engine.error is None
            threading.Event().wait(0.01)
        got = _generate(engine, long_prompt, 4)   # ~7 chunks at budget 16
        assert got == expect_long, (
            "interleaved decode corrupted the chunk-prefilling slot's KV"
        )
        assert stream_done.wait(180)
        assert stream_out == expect_stream
        stats = engine.scheduler_stats()
        # the long prefill's chunks shared iterations with the live decode
        assert stats["interleaved_iterations"] >= 3, stats
        assert stats["prefill_chunks"] >= 7, stats
        # Row-level corruption check: the interleaved engine's prompt KV
        # must match the whole-prompt reference row for row (tolerance for
        # the different prefill program shapes, decisive against a stray
        # decode write replacing a row outright).
        np.testing.assert_allclose(
            _prompt_slot_kv(engine, long_prompt),
            _prompt_slot_kv(ref, long_prompt),
            atol=5e-2, rtol=0,
            err_msg="interleaved decode dispatch wrote into prompt KV rows",
        )
    finally:
        ref.shutdown()
        engine.shutdown()


# -- speculative decoding as a scheduler phase -----------------------------


def test_spec_ngram_repeat_traffic_token_identical_and_accepts(tiny_model):
    """Retrieval (ngram) speculation: the first request builds the
    continuation store, a repeat re-proposes its completion and the batched
    verify accepts — output stays token-identical to a plain engine, at a
    measured (non-all-accept) acceptance rate."""
    from ray_tpu.llm import DecodeEngine

    cfg, model, params = tiny_model
    prompt = [5, 9, 17, 3, 42, 8, 7, 21]
    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False)
    spec = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128, prefix_cache=False,
        spec_config={"method": "ngram", "num_spec_tokens": 8},
    )
    try:
        expect = _generate(plain, prompt, 24)
        first = _generate(spec, prompt, 24)     # builds the store on finish
        repeat = _generate(spec, prompt, 24)
        assert first == expect and repeat == expect
        stats = spec.scheduler_stats()["spec"]
        assert stats["rounds"] > 0
        assert stats["accepted_tokens"] > 0
        assert 0 < stats["accept_rate"] <= 1.0
        assert stats["draft"]["kind"] == "ngram"
    finally:
        plain.shutdown()
        spec.shutdown()


def test_spec_stays_eligible_on_prefix_cache_hit(tiny_model):
    """A slot admitted via a prefix-cache hit must STILL run speculative
    rounds (draft cache catch-up on the attached prefix) instead of
    silently downgrading to plain decode — and emit identical tokens."""
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    cfg, model, params = tiny_model
    rng = np.random.default_rng(13)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 40)))

    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False)
    spec = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128,
        prefix_cache=PrefixCacheManager(16, 8 << 20, name="spec-hit"),
        spec_config={"num_spec_tokens": 4},   # self-draft: all-accept rig
    )
    try:
        expect = _generate(plain, prompt, 10)
        got_cold = _generate(spec, prompt, 10)
        rounds_cold = spec.scheduler_stats()["spec"]["rounds"]
        assert rounds_cold > 0
        got_warm = _generate(spec, prompt, 10)
        lp = spec.last_prefill
        assert lp["offset"] == 32, lp           # the cache hit really happened
        stats = spec.scheduler_stats()["spec"]
        assert stats["rounds"] > rounds_cold, (
            "cache-hit admission downgraded to plain decode"
        )
        assert got_cold == expect and got_warm == expect
    finally:
        plain.shutdown()
        spec.shutdown()


def test_spec_multi_slot_batched_verify_token_identical(tiny_model):
    """Several slots speculate CONCURRENTLY through one batched gated
    verify dispatch; every stream stays token-identical to the plain
    engine."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    prompts = [[5, 9, 17, 3], [8, 2, 44, 7, 19, 21, 6], [33, 11, 90]]
    plain = DecodeEngine(cfg, params, num_slots=4, max_seq=128,
                         prefix_cache=False)
    spec = DecodeEngine(
        cfg, params, num_slots=4, max_seq=128, prefix_cache=False,
        spec_config={"num_spec_tokens": 4},   # self-draft: deterministic
    )
    try:
        expect = [_generate(plain, p, 12) for p in prompts]
        results = {}
        done = threading.Event()

        def cb_for(idx):
            acc = []

            def cb(tok, fin):
                acc.append(tok)
                if fin:
                    results[idx] = acc
                    if len(results) == len(prompts):
                        done.set()

            return cb

        for idx, p in enumerate(prompts):
            spec.submit(p, SamplingParams(max_tokens=12), cb_for(idx))
        assert done.wait(180), spec.error
        assert [results[i] for i in range(len(prompts))] == expect
        stats = spec.scheduler_stats()["spec"]
        assert stats["rounds"] > 0
        # self-draft accepts everything it proposes
        assert stats["accepted_tokens"] == stats["proposed_tokens"] > 0
    finally:
        plain.shutdown()
        spec.shutdown()


def test_spec_eligible_after_pd_transfer_with_token_ids(tiny_model):
    """A PD-disagg transferred prefix that carries its token ids feeds the
    scheduler's running queue AND stays spec-eligible (the draft catches up
    on the token history)."""
    from ray_tpu.llm import DecodeEngine, SamplingParams

    cfg, model, params = tiny_model
    prompt = [5, 9, 17, 3, 42, 8]
    plain = DecodeEngine(cfg, params, num_slots=1, max_seq=128,
                         prefix_cache=False)
    prefiller = DecodeEngine(cfg, params, num_slots=1, max_seq=128,
                             decode_loop=False, prefix_cache=False)
    decoder = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128, prefix_cache=False,
        spec_config={"num_spec_tokens": 4},
    )
    try:
        expect = _generate(plain, prompt, 10)
        first_logits, kv, plen = prefiller.prefill_detached(prompt)
        out, done = [], threading.Event()

        def cb(tok, fin):
            out.append(tok)
            if fin:
                done.set()

        decoder.submit_prefilled(kv, plen, first_logits,
                                 SamplingParams(max_tokens=10), cb,
                                 token_ids=prompt)
        assert done.wait(180), decoder.error
        assert out == expect
        stats = decoder.scheduler_stats()["spec"]
        assert stats["rounds"] > 0, "transferred prefix downgraded to plain"
    finally:
        plain.shutdown()
        prefiller.shutdown()
        decoder.shutdown()


def test_early_exit_draft_shares_target_params(tiny_model):
    """EAGLE-style early-exit draft: first j layers + embeddings shared with
    the target (no copies), and generation stays token-identical (the
    verify phase corrects every wrong proposal)."""
    from ray_tpu.llm import DecodeEngine
    from ray_tpu.llm.scheduler import early_exit_draft

    cfg, model, params = tiny_model
    d_cfg, d_params = early_exit_draft(cfg, params, 1)
    assert d_cfg.n_layers == 1
    assert d_params["embedding"] is params["embedding"]  # shared, not copied
    with pytest.raises(ValueError, match="draft_layers"):
        early_exit_draft(cfg, params, cfg.n_layers)

    prompt = [5, 9, 17, 3]
    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128,
                         prefix_cache=False)
    spec = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128, prefix_cache=False,
        spec_config={"draft_layers": 1, "num_spec_tokens": 4},
    )
    try:
        expect = _generate(plain, prompt, 16)
        got = _generate(spec, prompt, 16)
        assert got == expect
        stats = spec.scheduler_stats()["spec"]
        assert stats["rounds"] > 0
        assert stats["draft"]["draft_layers"] == 1
    finally:
        plain.shutdown()
        spec.shutdown()
