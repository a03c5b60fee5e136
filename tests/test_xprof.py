"""Compute-plane observatory: XLA program registry, device-memory ledger,
OOM forensics, and profiler capture (docs/observability.md "compute plane").

The registry's core contract: a warm program never counts a compile again
(`xla_recompiles_total` reads 0 across any warm run), while a planted retrace
— rebuilding a program the registry has already seen compiled — fires it.
"""

import json
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.util import xprof


@pytest.fixture()
def reg():
    r = xprof.ProgramRegistry()
    yield r


# ---- program registry -------------------------------------------------------

def test_registry_counts_one_compile_per_program(reg):
    prog = reg.instrument("eng", ("decode",), jax.jit(lambda x: x + 1))
    for i in range(3):
        _ = prog(jnp.zeros(4))
    rep = reg.report()
    assert rep["totals"] == {
        "programs": 1, "compiles_total": 1, "recompiles_total": 0,
        "compile_s_total": pytest.approx(rep["totals"]["compile_s_total"]),
    }
    (row,) = rep["programs"]
    assert row["owner"] == "eng" and row["compiles"] == 1
    assert row["invocations"] == 3 and row["recompiles"] == 0
    assert row["compile_s"] > 0.0  # first call paid a real trace+compile


def test_planted_retrace_fires_recompile_counter(reg):
    """The adversarial shape: re-instrumenting an already-seen (owner, key) —
    what a cache eviction rebuild or a shape-retrace storm looks like at the
    registry — increments recompiles, not warmup compiles."""
    prog = reg.instrument("eng", ("prefill", 64), jax.jit(lambda x: x * 2))
    _ = prog(jnp.zeros(4))
    assert reg.recompiles_total == 0

    # Plant the retrace: the engine rebuilds the same bucket's program.
    prog2 = reg.instrument("eng", ("prefill", 64), jax.jit(lambda x: x * 2))
    _ = prog2(jnp.zeros(4))
    assert reg.recompiles_total == 1
    rep = reg.report()
    (row,) = rep["programs"]
    assert row["compiles"] == 2 and row["recompiles"] == 1
    # Warm calls after the retrace stay free.
    _ = prog2(jnp.zeros(4))
    assert reg.recompiles_total == 1


def test_note_span_and_note_exec_never_count_compiles(reg):
    reg.note_span("checkpoint", ("restore",), 1.5)
    reg.note_exec("learner", ("update", "sig"), 0.25)
    rep = reg.report()
    assert rep["totals"]["compiles_total"] == 0
    assert rep["totals"]["recompiles_total"] == 0
    by_owner = {r["owner"]: r for r in rep["programs"]}
    assert by_owner["checkpoint"]["invocations"] == 1
    assert by_owner["checkpoint"]["exec_s"] == pytest.approx(1.5)
    assert by_owner["learner"]["invocations"] == 0
    assert by_owner["learner"]["exec_s"] == pytest.approx(0.25)


def test_report_filters_by_owner_and_forget_owner(reg):
    a = reg.instrument("a", ("k",), jax.jit(lambda x: x + 1))
    b = reg.instrument("b", ("k",), jax.jit(lambda x: x - 1))
    _ = a(jnp.zeros(2))
    _ = b(jnp.zeros(2))
    assert len(reg.report(owner="a")["programs"]) == 1
    assert len(reg.report()["programs"]) == 2
    reg.forget_owner("a")
    assert reg.report(owner="a")["programs"] == []
    # totals watermarks survive the forget: no negative deltas on next report
    assert reg.report()["totals"]["programs"] == 1


def test_instrumented_program_delegates_attributes(reg):
    jitted = jax.jit(lambda x: x + 1)
    prog = reg.instrument("eng", ("k",), jitted)
    _ = prog(jnp.zeros(2))
    # the adapters stats() probe and any other jit attribute ride through
    assert prog._cache_size() == jitted._cache_size()
    assert prog.__wrapped__ is jitted


def test_unhashable_key_is_frozen(reg):
    prog = reg.instrument("eng", ["prefill", [1, 2]], jax.jit(lambda x: x))
    _ = prog(jnp.zeros(2))
    (row,) = reg.report()["programs"]
    assert row["key"] == ("prefill", (1, 2))


# ---- device-memory ledger ---------------------------------------------------

def test_memory_ledger_attributes_owner_bytes():
    xprof.register_memory_owner("san-owner", lambda: {
        "bytes": 1024, "components": {"kv": 1024},
        "per_device": {"0": 512, "1": 512},
    })
    try:
        rep = xprof.device_memory_report()
        assert rep["owners"]["san-owner"]["bytes"] == 1024
        assert rep["tracked_bytes_total"] >= 1024
        assert rep["per_device_tracked_bytes"]["0"] == 512
        assert rep["devices"], "jax.devices() must appear in the report"
        assert {"id", "platform"} <= set(rep["devices"][0])
    finally:
        xprof.unregister_memory_owner("san-owner")
    assert "san-owner" not in xprof.device_memory_report()["owners"]


def test_memory_ledger_owner_error_is_contained():
    def broken():
        raise RuntimeError("owner died")

    xprof.register_memory_owner("san-broken", broken)
    try:
        rep = xprof.device_memory_report()
        assert "owner died" in rep["owners"]["san-broken"]["error"]
    finally:
        xprof.unregister_memory_owner("san-broken")


# ---- OOM forensics ----------------------------------------------------------

def test_is_resource_exhausted_matches_xla_shapes():
    assert xprof.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                     "allocate 21474836480 bytes."))
    assert xprof.is_resource_exhausted(ValueError("Resource exhausted: HBM"))
    assert not xprof.is_resource_exhausted(ValueError("shape mismatch"))


def test_oom_snapshot_ranks_owners_descending():
    xprof.register_memory_owner("san-big", lambda: {"bytes": 2048})
    xprof.register_memory_owner("san-small", lambda: {"bytes": 16})
    try:
        snap = xprof.oom_snapshot()
        ranked = [r["owner"] for r in snap["ranked_owners"]
                  if r["owner"].startswith("san-")]
        assert ranked == ["san-big", "san-small"]
        assert snap["ts"] > 0
    finally:
        xprof.unregister_memory_owner("san-big")
        xprof.unregister_memory_owner("san-small")


def test_flight_recorder_keeps_first_oom_snapshot():
    from ray_tpu.llm.flight_recorder import FlightRecorder

    rec = FlightRecorder(name="san-oom", capacity=4)
    try:
        rec.note_oom({"ts": 1.0, "ranked_owners": [{"owner": "kv", "bytes": 9}]})
        rec.note_oom({"ts": 2.0, "ranked_owners": []})  # cascade: noise
        stats = rec.stats()
        assert stats["oom"] == 2
        assert stats["last_oom"]["ts"] == 1.0
    finally:
        rec.close()


# ---- profiler capture -------------------------------------------------------

def test_capture_round_trip_yields_manifest_and_files():
    log_dir = tempfile.mkdtemp(prefix="xprof_test_")
    out = xprof.capture(duration_s=0.05, log_dir=log_dir)
    assert out["log_dir"] == log_dir
    assert out["manifest"]["duration_s"] >= 0.05
    assert out["manifest"]["pid"] == os.getpid()
    # at minimum the manifest itself is gathered inline
    assert "capture_manifest.json" in out["files"]
    manifest = json.loads(out["files"]["capture_manifest.json"])
    assert manifest["log_dir"] == log_dir


def test_second_start_capture_raises_while_active():
    cap = xprof.start_capture(log_dir=tempfile.mkdtemp(prefix="xprof_test_"))
    try:
        with pytest.raises(RuntimeError):
            xprof.start_capture()
    finally:
        cap.stop_capture()
    # idempotent stop, and the slot frees for the next capture
    cap.stop_capture()
    cap2 = xprof.start_capture(log_dir=tempfile.mkdtemp(prefix="xprof_test_"))
    cap2.close()


# ---- metrics exposition (report path) ---------------------------------------

def test_registry_report_emits_metrics_deltas(reg, ray_start_isolated):
    from ray_tpu.util.metrics import render_prometheus

    prog = reg.instrument("eng", ("decode",), jax.jit(lambda x: x + 1))
    _ = prog(jnp.zeros(2))
    reg.report()  # the ONLY place counters become util.metrics series
    text = render_prometheus()
    assert "xla_compiles_total" in text
    assert "xla_recompiles_total" in text


def test_render_prometheus_alias_preserved():
    from ray_tpu.util import metrics

    assert metrics.prometheus_text is metrics.render_prometheus


# ---- spans ------------------------------------------------------------------

def _row(name):
    return xprof.span_totals().get(name, {"count": 0, "seconds": 0.0})


def test_span_accumulates_count_and_seconds():
    import time

    before = _row("rt.test.acc")
    for _ in range(3):
        with xprof.span("rt.test.acc", slots=2) as sp:
            time.sleep(0.01)
    after = _row("rt.test.acc")
    assert after["count"] == before["count"] + 3
    assert after["seconds"] - before["seconds"] >= 0.03
    # the two clock reads are the caller's to reuse (the flight recorder does)
    assert sp.t1 - sp.t0 >= 0.01


def test_spans_nest_and_the_outer_covers_the_inner():
    import time

    outer0, inner0 = _row("rt.test.outer"), _row("rt.test.inner")
    with xprof.span("rt.test.outer") as outer:
        with xprof.span("rt.test.inner") as inner:
            time.sleep(0.005)
        with xprof.span("rt.test.inner"):
            pass
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert _row("rt.test.outer")["count"] == outer0["count"] + 1
    assert _row("rt.test.inner")["count"] == inner0["count"] + 2
    grew = lambda name, was: _row(name)["seconds"] - was["seconds"]  # noqa: E731
    assert grew("rt.test.outer", outer0) >= grew("rt.test.inner", inner0) >= 0.005


def test_span_survives_an_exception():
    before = _row("rt.test.raises")
    with pytest.raises(KeyError):
        with xprof.span("rt.test.raises", rid="r1"):
            raise KeyError("boom")
    assert _row("rt.test.raises")["count"] == before["count"] + 1
    with xprof.span("rt.test.raises"):  # and the next one opens as usual
        pass
    assert _row("rt.test.raises")["count"] == before["count"] + 2


def test_span_table_loses_no_update_under_many_threads():
    """More threads than cores, the interpreter switching as often as it can: the
    table counts every span (several engines' steppers may share a process)."""
    import sys
    import threading

    before, n_threads, each = _row("rt.test.stress"), 16, 500

    def work():
        for _ in range(each):
            with xprof.span("rt.test.stress"):
                pass

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert _row("rt.test.stress")["count"] == before["count"] + n_threads * each


def test_span_is_an_event_of_the_captured_host_plane(tmp_path):
    """Under a profiler session the span is a host-plane event of the same
    `.xplane.pb` as the device's operations, with its attributes as stats. The
    spans are made on a second thread, as the engine's stepper makes them."""
    import glob
    import threading

    from jax.profiler import ProfileData

    def work():
        for i in range(3):
            with xprof.span("rt.test.traced", slots=i, rid="req-7"):
                jnp.ones((8,)).block_until_ready()

    cap = xprof.start_capture(log_dir=str(tmp_path))
    try:
        t = threading.Thread(target=work)
        t.start()
        t.join()
    finally:
        cap.stop_capture()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events if e.name == "rt.test.traced"]
    assert len(events) == 3
    stats = [dict(e.stats) for e in events]
    assert sorted(int(s["slots"]) for s in stats) == [0, 1, 2]
    assert all(str(s["rid"]) == "req-7" for s in stats)
    assert all(e.duration_ns > 0 for e in events)
