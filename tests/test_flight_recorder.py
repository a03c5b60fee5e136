"""Flight recorder + SLO metrics plane (docs/observability.md): bounded
ring/event accounting, timing breakdowns, span export shape, SLO/goodput
classification, and leak-free shutdown (this suite runs under leaksan —
tests/conftest.py LEAKSAN_SUITES — so a stranded flight_record handle is a
test failure, not a slow leak)."""

import threading
import time

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
    return DecodeEngine(cfg, params, **kwargs), cfg


def _generate(engine, prompt, rid=None, **sp):
    from ray_tpu.llm import SamplingParams

    acc, done = [], threading.Event()

    def cb(tok, fin):
        acc.append(tok)
        if fin:
            done.set()

    engine.submit(prompt, SamplingParams(**sp), cb, request_id=rid)
    assert done.wait(180), engine.error
    return acc


# -- recorder unit behavior ---------------------------------------------------


def test_ring_and_event_caps_bounded():
    from ray_tpu.llm import flight_recorder as fr

    rec = fr.FlightRecorder(name="unit", capacity=4)
    for i in range(10):
        r = rec.start(f"r{i}")
        r.mark("queued")
        rec.finish(r)
    stats = rec.stats()
    assert stats["ring"] == 4 and stats["finished"] == 10
    assert stats["live"] == 0
    # per-record event cap: overflow counts, never grows
    r = rec.start("big")
    for i in range(fr._MAX_EVENTS + 50):
        r.mark(f"e{i}")
    assert len(r.events) == fr._MAX_EVENTS and r.dropped_events == 50
    summary = rec.finish(r)
    assert summary["dropped_events"] == 50


def test_capacity_zero_disables():
    from ray_tpu.llm.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=0)
    assert rec.start("x") is None
    assert rec.finish(None) is None  # None-guards hold end to end
    assert rec.stats()["started"] == 0


def test_finish_idempotent_and_lookup():
    from ray_tpu.llm.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=8)
    r = rec.start("a", tenant="t1", route="cache_routed")
    r.mark("admitted", slot=0)
    r.token()
    time.sleep(0.01)
    r.token()
    s1 = rec.finish(r)
    s2 = rec.finish(r)  # second retire is a no-op, books stay balanced
    assert rec.stats()["finished"] == 1
    assert s1["tokens"] == 2 and s1["ttft_s"] is not None
    assert s1["tpot_s"] == pytest.approx(
        s1["events"] and (r.token_times[1] - r.token_times[0]), rel=0.2
    )
    assert s2["tenant"] == "t1"
    found = rec.lookup("a")
    assert found is not None and found["route"] == "cache_routed"
    assert rec.lookup("missing") is None


def test_span_export_tree_shape():
    """Span export: one root per record, phase children parented under it,
    trace ids preserved — the shape to_otlp_json/spans_to_otel consume."""
    from ray_tpu.llm.flight_recorder import FlightRecorder
    from ray_tpu.util.tracing_export import to_otlp_json

    rec = FlightRecorder(name="spans", capacity=8)
    trace = {"trace_id": "f" * 32, "span_id": "1" * 16}
    r = rec.start("req", trace=trace, tenant="t")
    r.mark("queued")
    r.span("prefill-chunk", time.time() - 0.01, time.time(), bucket=32)
    rec.finish(r)
    spans = rec.spans()
    root = next(s for s in spans if s["name"] == "llm:request")
    assert root["trace_id"] == "f" * 32
    assert root["parent_span_id"] == "1" * 16  # the serve task's span
    children = [s for s in spans if s["name"] != "llm:request"]
    assert {s["name"] for s in children} == {"llm:queued", "llm:prefill-chunk"}
    assert all(s["parent_span_id"] == root["span_id"] for s in children)
    chunk = next(s for s in children if s["name"] == "llm:prefill-chunk")
    assert chunk["attributes"]["ray_tpu.llm.bucket"] == 32
    # and the OTLP mapping accepts it wholesale
    otlp = to_otlp_json(spans)
    names = [s["name"]
             for s in otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]]
    assert "llm:request" in names and "llm:prefill-chunk" in names


def test_serve_metrics_slo_classification_and_burn():
    from ray_tpu.llm.flight_recorder import ServeMetrics

    m = ServeMetrics("unit", slo_ttft_s=0.1, slo_tpot_s=0.01,
                     error_budget=0.1)
    good = {"status": "ok", "ttft_s": 0.05, "tpot_s": 0.005, "e2e_s": 0.2,
            "tenant": ""}
    bad_ttft = {**good, "ttft_s": 0.5}
    bad_tpot = {**good, "tpot_s": 0.02}
    rejected = {**good, "status": "rejected"}
    assert m.good(good) and not m.good(bad_ttft)
    assert not m.good(bad_tpot) and not m.good(rejected)
    for s in (good, good, bad_ttft, good):
        m.record(s)
    m.flush()  # no cluster: metrics export is best-effort, window still fills
    # 1 breach in 4 over a 0.1 budget -> burn 2.5
    assert m.burn_rate("") == pytest.approx((1 / 4) / 0.1)


@pytest.mark.parametrize("events, want", [
    ((("queued", 0.0), ("admitted", 1.0), ("prefill-chunk", 1.25), ("prefill-chunk", 1.5)), 0.25),
    ((("queued", 0.0), ("admitted", 1.0), ("cache-attach", 1.1), ("prefill-chunk", 1.4)), 0.4),
    ((("queued", 0.0), ("admitted", 1.0), ("pd-attach", 1.2)), None),   # a transferred prefix runs no chunk
    ((("queued", 0.0),), None),                                         # not admitted yet
], ids=["two_chunks", "behind_an_attach", "no_chunk", "not_admitted"])
def test_prefill_wait_is_the_first_chunks_start_less_admitted(events, want):
    """What a request waits after it has a slot, for the chunks of those ahead of it: from a
    record's own events, in its summary beside `queue_s`; None where either event is absent."""
    from ray_tpu.llm import flight_recorder as fr

    rec = fr.RequestRecord("r")
    for name, t0 in events:
        rec.span(name, rec.t_submit + t0, rec.t_submit + t0 + 0.05)
    summary = rec.summary()
    assert summary["prefill_wait_s"] == (None if want is None else pytest.approx(want))
    assert rec.prefill_wait_s() == summary["prefill_wait_s"]
    assert summary["queue_s"] == (pytest.approx(1.0) if len(events) > 1 else None)


# -- engine integration -------------------------------------------------------


def test_engine_timing_breakdown_and_phases():
    engine, _cfg = _tiny_engine(num_slots=2, max_seq=64)
    try:
        out = _generate(engine, [1, 2, 3, 4, 5], rid="req-tb", max_tokens=6)
        assert len(out) == 6
        t = engine.request_timing("req-tb")
        assert t is not None and t["tokens"] == 6
        assert t["queue_s"] is not None and t["queue_s"] >= 0
        assert 0 <= t["prefill_wait_s"] <= t["ttft_s"] - t["queue_s"]
        assert t["ttft_s"] > 0 and t["e2e_s"] >= t["ttft_s"]
        assert "prefill-chunk" in t["phases"] and "decode" in t["phases"]
        rec = engine._recorder.records()[-1]
        names = [e[0] for e in rec["events"]]
        assert names[0] == "queued" and "admitted" in names
    finally:
        engine.shutdown()


def test_engine_shutdown_drops_live_records():
    """Requests still queued/active at shutdown retire as dropped — the
    leaksan flight_record books balance (this suite's autouse guard is the
    enforcement) and counters stay exact."""
    from ray_tpu.llm import SamplingParams

    engine, _cfg = _tiny_engine(num_slots=1, max_seq=64)
    try:
        stall = threading.Event()
        first = threading.Event()

        def cb(tok, fin):
            first.set()
            stall.wait(0.01)  # slow consumer keeps the slot occupied

        engine.submit(list(range(1, 9)), SamplingParams(max_tokens=64), cb)
        # a second request that stays QUEUED behind the busy slot
        engine.submit(list(range(1, 5)), SamplingParams(max_tokens=4),
                      lambda t, f: None)
        assert first.wait(60), engine.error
    finally:
        engine.shutdown()
    stats = engine._recorder.stats()
    assert stats["live"] == 0
    assert stats["started"] == stats["finished"] + stats["dropped"] + \
        stats["rejected"]
    assert stats["dropped"] >= 1  # the queued request never got a slot


def test_overload_rejection_records():
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.scheduler.scheduler import EngineOverloadedError

    engine, _cfg = _tiny_engine(num_slots=1, max_seq=64, max_queue_depth=1,
                                tenant_quota=0)
    try:
        started = threading.Event()

        def slow(t, f):
            started.set()
            time.sleep(0.005)

        engine.submit([1, 2, 3], SamplingParams(max_tokens=64), slow)
        assert started.wait(60), engine.error  # admitted: the queue is empty
        engine.submit([1, 2], SamplingParams(max_tokens=2),
                      lambda t, f: None)  # fills the depth-1 queue
        with pytest.raises(EngineOverloadedError):
            engine.submit([1], SamplingParams(max_tokens=2),
                          lambda t, f: None)
        assert engine._recorder.stats()["rejected"] == 1
    finally:
        engine.shutdown()


def test_spec_and_cache_phases_recorded():
    """A cache-hit + spec-decode request's record carries the cache-attach
    and spec-verify phases (the events the tuning loops read)."""
    import numpy as np

    from ray_tpu._private.config import CONFIG

    bs = CONFIG.llm_kv_block_size
    engine, cfg = _tiny_engine(
        num_slots=2, max_seq=128,
        spec_config={"method": "ngram", "num_spec_tokens": 4},
    )
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, 3 * bs).tolist()
    try:
        _generate(engine, prefix + [1, 2], rid="cold", max_tokens=12)
        _generate(engine, prefix + [3, 4], rid="warm", max_tokens=12)
        warm = engine.request_timing("warm")
        assert "cache-attach" in warm["phases"], warm["phases"]
        # repeated greedy traffic: the ngram draft proposes on the warm run
        recs = engine._recorder.records()
        phases = [e[0] for r in recs for e in r["events"]]
        assert "spec-verify" in phases or "prefill-chunk" in phases
    finally:
        engine.shutdown()
