"""Request-lifecycle flight recorder + SLO metrics for the LLM serve plane.

Design parity: the reference treats observability as a first-class layer
(dashboard/state API, `ray timeline` Chrome traces, per-node metrics agent ->
Prometheus; PAPER.md layers 9 and 13). The serving-world shape this module
adds on top is vLLM's per-request metrics/tracing: every request accrues
host-timestamped PHASE EVENTS as it moves through the serve path —

    queued -> admitted (slot, cached prefix tokens, adapter page-in)
           -> prefill-chunk[i] (bucket, offset) / cache-attach / pd-attach
           -> spec-verify (proposed/accepted) -> decode (aggregated; per-token
              host timestamps power TTFT/TPOT) -> finished

— into a bounded per-engine ring buffer. Three hard rules, learned in PRs
9 and 11:

- **Host-side only.** Recording is list appends of plain tuples under the
  GIL; no device handle is ever touched, so the decode loop's device-pull
  count is unchanged (tests/test_llm_engine_hotpath.py asserts it).
- **Flush only from report paths.** A `util.metrics` flush is a GCS KV RPC;
  one in the dispatch loop would put the control plane on the token hot
  path. Completion summaries queue host-side and become Histogram/Counter
  observations (and synthetic task events for `timeline()` / OTel export)
  ONLY when `flush()` runs from `scheduler_stats()` / `recorder_stats()`.
- **Bounded everything.** The ring holds `llm_flight_records` finished
  records; each record caps its events and token timestamps, counting (not
  growing on) overflow. leaksan tracks every live record
  (`flight_record`), so an engine shutdown that strands one is a test
  failure, not a slow leak.

Span export rides the EXISTING machinery: a finished traced record flushes
as synthetic task events (RUNNING/FINISHED pairs carrying
trace_id/span_id/parent_span_id), so `ray_tpu.util.state.timeline()` renders
the phases in Perfetto and `tracing_export.spans_from_task_events` /
`spans_to_otel` emit the same tree to OTel — one HTTP request becomes one
trace spanning proxy -> router -> replica task spans with the engine's phase
spans nested under the replica's. See docs/observability.md.
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

# Per-record caps: phase events beyond this count (and token timestamps
# beyond _MAX_TOKEN_TIMES) are dropped-and-counted, never grown.
_MAX_EVENTS = 128
_MAX_TOKEN_TIMES = 4096


def _new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class RequestRecord:
    """One request's in-flight lifecycle state. Appends are plain list ops
    (GIL-atomic) from whichever thread owns the phase — the submitting
    asyncio thread, the scheduler's admission path, the engine stepper —
    with no lock and no device access."""

    __slots__ = ("rid", "trace_id", "span_id", "parent_span_id", "tenant",
                 "route", "t_submit", "events", "dropped_events",
                 "token_times", "meta", "__weakref__")

    def __init__(self, rid: str, *, trace: Optional[dict] = None,
                 tenant: str = "", route: Optional[str] = None,
                 meta: Optional[dict] = None):
        self.rid = rid
        self.trace_id = (trace or {}).get("trace_id")
        self.parent_span_id = (trace or {}).get("span_id")
        self.span_id = _new_span_id()
        self.tenant = tenant
        self.route = route
        self.t_submit = time.time()
        self.events: List[tuple] = []  # (name, t0, t1, attrs | None)
        self.dropped_events = 0
        self.token_times: List[float] = []
        self.meta = meta

    # -- recording (any thread; never blocks, never touches a device) ------
    def mark(self, name: str, **attrs) -> float:
        """Instant event (rendered as a zero-duration span); returns its
        stamp, for a caller that names the same instant in a profiler trace."""
        t = time.time()
        self.span(name, t, t, **attrs)
        return t

    def span(self, name: str, t0: float, t1: float, **attrs):
        if len(self.events) >= _MAX_EVENTS:
            self.dropped_events += 1
            return
        self.events.append((name, t0, t1, attrs or None))

    def token(self):
        """One generated token's host timestamp (TTFT = first, TPOT = gaps)."""
        if len(self.token_times) < _MAX_TOKEN_TIMES:
            self.token_times.append(time.time())

    # -- summarization ------------------------------------------------------
    def _first(self, name: str) -> Optional[float]:
        """Start of the first event of that name, or None."""
        return next((t0 for n, t0, _t1, _a in self.events if n == name), None)

    def prefill_wait_s(self) -> Optional[float]:
        """Seconds a request spent among the admitted before its own first
        chunk ran: the first `prefill-chunk`'s start less `admitted` (the
        chunks of those ahead of it, one an iteration). None where either
        event is absent (not admitted yet; a transferred prefix has no chunk)."""
        admitted, chunk = self._first("admitted"), self._first("prefill-chunk")
        return None if admitted is None or chunk is None else chunk - admitted

    def summary(self, status: str = "ok") -> dict:
        """The completion record that feeds the ring, the SLO metrics, and
        the response-metadata timing breakdown."""
        t_end = time.time()
        tt = self.token_times
        ttft = (tt[0] - self.t_submit) if tt else None
        gaps = [b - a for a, b in zip(tt, tt[1:])]
        tpot = (sum(gaps) / len(gaps)) if gaps else None
        phases: Dict[str, dict] = {}
        for name, t0, t1, _attrs in self.events:
            p = phases.setdefault(name, {"count": 0, "seconds": 0.0})
            p["count"] += 1
            p["seconds"] += max(0.0, t1 - t0)
        admitted = self._first("admitted")
        return {
            "rid": self.rid,
            "status": status,
            "tenant": self.tenant,
            "route": self.route,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "t_submit": self.t_submit,
            "t_end": t_end,
            "e2e_s": t_end - self.t_submit,
            "queue_s": (admitted - self.t_submit) if admitted else None,
            "prefill_wait_s": self.prefill_wait_s(),
            "ttft_s": ttft,
            "tpot_s": tpot,
            "tokens": len(tt),
            "phases": phases,
            "events": list(self.events),
            "dropped_events": self.dropped_events,
            "meta": self.meta,
        }


class FlightRecorder:
    """Bounded per-engine ring of finished request records plus the live
    set. `llm_flight_records <= 0` disables recording entirely (start()
    returns None and every caller is None-guarded)."""

    def __init__(self, name: str = "", capacity: Optional[int] = None):
        if capacity is None:
            from ray_tpu._private.config import CONFIG

            capacity = CONFIG.llm_flight_records
        self.name = name
        self.capacity = max(0, int(capacity))
        self._live: Dict[str, RequestRecord] = {}
        self._ring: deque = deque(maxlen=self.capacity or 1)
        self._unexported: deque = deque()  # summaries awaiting span export
        self._lock = threading.Lock()
        self._counters = {"started": 0, "finished": 0, "dropped": 0,
                          "rejected": 0, "cancelled": 0, "exported_spans": 0}
        # OOM forensics (docs/observability.md "compute plane"): the ranked
        # device-memory ledger snapshot a RESOURCE_EXHAUSTED escape pinned
        # here before the engine re-raised. One slot — the FIRST OOM is the
        # attributable one; later ones are cascade noise.
        self._last_oom: Optional[dict] = None

    # -- lifecycle ----------------------------------------------------------
    def start(self, rid: Optional[str] = None, *, trace: Optional[dict] = None,
              tenant: str = "", route: Optional[str] = None,
              meta: Optional[dict] = None) -> Optional[RequestRecord]:
        if self.capacity <= 0:
            return None
        rec = RequestRecord(rid or uuid.uuid4().hex, trace=trace,
                            tenant=tenant, route=route, meta=meta)
        from ray_tpu.devtools import leaksan

        leaksan.track("flight_record", token=rec.rid)
        with self._lock:
            self._counters["started"] += 1
            self._live[rec.rid] = rec
        return rec

    def _retire(self, rec: RequestRecord, status: str, counter: str) -> dict:
        summary = rec.summary(status)
        from ray_tpu.devtools import leaksan

        with self._lock:
            if self._live.pop(rec.rid, None) is None:
                return summary  # already retired (idempotent)
            self._counters[counter] += 1
            self._ring.append(summary)
            if rec.trace_id:
                self._unexported.append(summary)
        leaksan.untrack("flight_record", token=rec.rid)
        return summary

    def finish(self, rec: Optional[RequestRecord],
               status: str = "ok") -> Optional[dict]:
        """Normal completion: move the record to the ring and queue its
        summary for the report-path metrics flush. Idempotent.
        status="cancelled" (the mid-stream-disconnect path,
        docs/generation.md) keeps its own counter so operators can tell
        client hang-ups from served completions at a glance."""
        if rec is None:
            return None
        counter = status if status in ("rejected", "cancelled") else "finished"
        return self._retire(rec, status, counter)

    def drop(self, rec: Optional[RequestRecord]) -> Optional[dict]:
        """Abnormal end (drain, stepper death, shutdown): books still
        balance — the record retires with status "dropped"."""
        if rec is None:
            return None
        return self._retire(rec, "dropped", "dropped")

    def note_oom(self, snapshot: dict):
        """Pin a device-memory ledger snapshot (xprof.oom_snapshot()) to
        this recorder. Keeps the first — cascading OOMs repeat the story."""
        with self._lock:
            self._counters["oom"] = self._counters.get("oom", 0) + 1
            if self._last_oom is None:
                self._last_oom = dict(snapshot)

    def close(self):
        """Engine shutdown: retire every live record so leaksan's
        flight_record books balance exactly."""
        with self._lock:
            live = list(self._live.values())
        for rec in live:
            self.drop(rec)

    # -- read paths ---------------------------------------------------------
    def lookup(self, rid: str) -> Optional[dict]:
        """Timing breakdown for one request (ring first, then live)."""
        with self._lock:
            for summary in reversed(self._ring):
                if summary["rid"] == rid:
                    return dict(summary)
            rec = self._live.get(rid)
        return rec.summary("running") if rec is not None else None

    def records(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-n:]

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._counters)
            out["live"] = len(self._live)
            out["ring"] = len(self._ring)
            out["capacity"] = self.capacity
            out["unexported_spans"] = len(self._unexported)
            if self._last_oom is not None:
                out["last_oom"] = dict(self._last_oom)
        return out

    # -- report-path export (NEVER called from the dispatch loop) ----------
    def spans(self, summaries: Optional[List[dict]] = None) -> List[dict]:
        """tracing_export-shaped span dicts: one request-root span per
        record, phase events as children — feed straight into
        `to_otlp_json` / `spans_to_otel`."""
        if summaries is None:
            summaries = self.records()
        spans: List[dict] = []
        for s in summaries:
            root = {
                "trace_id": s["trace_id"] or s["rid"],
                "span_id": s["span_id"],
                "parent_span_id": s["parent_span_id"],
                "name": "llm:request",
                "start_s": s["t_submit"],
                "end_s": s["t_end"],
                "ok": s["status"] in ("ok", "running"),
                "attributes": {
                    "ray_tpu.llm.rid": s["rid"],
                    "ray_tpu.llm.tenant": s["tenant"] or None,
                    "ray_tpu.llm.route": s["route"],
                    "ray_tpu.llm.tokens": s["tokens"],
                    "ray_tpu.llm.ttft_s": s["ttft_s"],
                    "ray_tpu.llm.engine": self.name,
                },
            }
            spans.append(root)
            for name, t0, t1, attrs in s["events"]:
                spans.append({
                    "trace_id": root["trace_id"],
                    "span_id": _new_span_id(),
                    "parent_span_id": s["span_id"],
                    "name": f"llm:{name}",
                    "start_s": t0,
                    "end_s": t1,
                    "ok": True,
                    "attributes": {
                        f"ray_tpu.llm.{k}": v for k, v in (attrs or {}).items()
                    },
                })
        return spans

    def flush_task_events(self):
        """Emit finished TRACED records as synthetic task events (RUNNING +
        FINISHED pairs carrying trace/span ids) into the worker's buffered
        event pipeline, so `timeline()` and the OTel exporters pick the
        phase spans up exactly like task spans. Report-path only: the
        worker's own flush loop batches these to the GCS."""
        with self._lock:
            batch = []
            while self._unexported:
                batch.append(self._unexported.popleft())
        if not batch:
            return 0
        try:
            import ray_tpu

            worker = ray_tpu.global_worker()
        except Exception:
            return 0  # no connected worker (unit tests): spans stay local
        n = 0
        for span in self.spans(batch):
            tid = f"llm-{span['span_id']}"
            base = {
                "task_id": tid, "name": span["name"],
                "trace_id": span["trace_id"], "span_id": span["span_id"],
                "parent_span_id": span.get("parent_span_id"),
            }
            try:
                worker._record_event(state="RUNNING", **base)
                worker._record_event(state="FINISHED", **base)
                # _record_event stamps time itself; rewrite with the phase's
                # real host timestamps (the recorder's times ARE the span).
                with worker._events_lock:
                    worker._task_events[-2]["time"] = span["start_s"]
                    worker._task_events[-1]["time"] = span["end_s"]
                n += 1
            except Exception:
                break  # event plane unavailable; retry on the next report
        with self._lock:
            self._counters["exported_spans"] += n
        return n


class ServeMetrics:
    """Per-tenant TTFT/TPOT/e2e Histograms + SLO burn-rate and goodput
    counters (docs/observability.md). `record()` is host-side accumulation
    (deque append, callable from completion paths); `flush()` — report-path
    only — turns the backlog into util.metrics observations:

    - llm_ttft_seconds / llm_tpot_seconds / llm_e2e_seconds{engine,tenant}:
      latency-scale Histograms (the util.metrics log-spaced default).
    - llm_requests_total{engine,tenant,outcome}: ok | dropped | rejected.
    - llm_slo_good_total / llm_slo_breach_total{engine,tenant}: completions
      meeting / missing BOTH SLOs (TTFT <= llm_slo_ttft_s AND mean TPOT <=
      llm_slo_tpot_s). goodput-under-SLO = rate(llm_slo_good_total).
    - llm_slo_burn_rate{engine,tenant}: windowed breach fraction over the
      error budget (1.0 = burning exactly the budget; >1 = on track to
      exhaust it)."""

    WINDOW = 256  # completions per tenant in the burn-rate window

    def __init__(self, name: str = "", *, slo_ttft_s: Optional[float] = None,
                 slo_tpot_s: Optional[float] = None,
                 error_budget: Optional[float] = None):
        from ray_tpu._private.config import CONFIG

        self.slo_ttft_s = (CONFIG.llm_slo_ttft_s if slo_ttft_s is None
                           else float(slo_ttft_s))
        self.slo_tpot_s = (CONFIG.llm_slo_tpot_s if slo_tpot_s is None
                           else float(slo_tpot_s))
        self.error_budget = max(1e-6, (
            CONFIG.llm_slo_error_budget if error_budget is None
            else float(error_budget)
        ))
        self._name = name
        self._backlog: deque = deque()
        self._window: Dict[str, deque] = {}  # tenant -> recent good/bad bits
        self._lock = threading.Lock()
        self._metrics: Optional[dict] = None

    def good(self, summary: dict) -> bool:
        """Did this completion meet the SLO? (Rejected/dropped never do.)"""
        if summary.get("status") != "ok":
            return False
        ttft, tpot = summary.get("ttft_s"), summary.get("tpot_s")
        if ttft is None or ttft > self.slo_ttft_s:
            return False
        return tpot is None or tpot <= self.slo_tpot_s

    def record(self, summary: dict):
        """Hot-path-safe accumulation: one deque append, no metrics flush."""
        self._backlog.append(summary)

    def _ensure_metrics(self) -> dict:
        if self._metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            tag = {"engine": self._name}
            keys = ("engine", "tenant")
            self._metrics = {
                "ttft": Histogram(
                    "llm_ttft_seconds", "time to first token",
                    tag_keys=keys).set_default_tags(tag),
                "tpot": Histogram(
                    "llm_tpot_seconds",
                    "mean inter-token latency per request",
                    tag_keys=keys).set_default_tags(tag),
                "e2e": Histogram(
                    "llm_e2e_seconds", "submit-to-last-token latency",
                    tag_keys=keys).set_default_tags(tag),
                "requests": Counter(
                    "llm_requests_total", "completed requests by outcome",
                    tag_keys=("engine", "tenant", "outcome"),
                ).set_default_tags(tag),
                "good": Counter(
                    "llm_slo_good_total",
                    "completions that met the TTFT and TPOT SLOs "
                    "(goodput-under-SLO numerator)",
                    tag_keys=keys).set_default_tags(tag),
                "breach": Counter(
                    "llm_slo_breach_total",
                    "completions that missed an SLO (or failed)",
                    tag_keys=keys).set_default_tags(tag),
                "burn": Gauge(
                    "llm_slo_burn_rate",
                    "windowed SLO breach fraction over the error budget",
                    tag_keys=keys).set_default_tags(tag),
            }
        return self._metrics

    def flush(self) -> int:
        """Report-path only (PR 9/11 lesson: a metrics flush is a GCS RPC).
        Drains the backlog into Histograms/Counters and recomputes the
        per-tenant burn-rate gauge. Returns summaries flushed."""
        drained: List[dict] = []
        while self._backlog:
            try:
                drained.append(self._backlog.popleft())
            except IndexError:
                break
        if not drained:
            return 0
        try:
            m = self._ensure_metrics()
            burn_tenants = set()
            for s in drained:
                tenant = s.get("tenant") or ""
                tags = {"tenant": tenant}
                if s["status"] == "cancelled":
                    # A client hang-up is visible (requests_total{outcome=
                    # "cancelled"}) but NOT an SLO breach: it must not feed
                    # the burn window the autopilot scales on, or a flaky
                    # client could scale the fleet (docs/generation.md).
                    m["requests"].inc(1, tags={**tags, "outcome": "cancelled"})
                    continue
                good = self.good(s)
                with self._lock:
                    w = self._window.setdefault(
                        tenant, deque(maxlen=self.WINDOW))
                    w.append(good)
                burn_tenants.add(tenant)
                m["requests"].inc(1, tags={**tags, "outcome": s["status"]})
                (m["good"] if good else m["breach"]).inc(1, tags=tags)
                if s.get("ttft_s") is not None:
                    m["ttft"].observe(s["ttft_s"], tags=tags)
                if s.get("tpot_s") is not None:
                    m["tpot"].observe(s["tpot_s"], tags=tags)
                if s.get("e2e_s") is not None and s["status"] == "ok":
                    m["e2e"].observe(s["e2e_s"], tags=tags)
            for tenant in burn_tenants:
                m["burn"].set(self.burn_rate(tenant),
                              tags={"tenant": tenant})
        except Exception:
            pass  # metrics must never break the report path
        return len(drained)

    def burn_rate(self, tenant: str = "") -> float:
        """Breach fraction in the recent window over the error budget."""
        with self._lock:
            w = self._window.get(tenant)
            if not w:
                return 0.0
            breaches = sum(1 for ok in w if not ok)
            return (breaches / len(w)) / self.error_budget

    def burn_rates(self) -> Dict[str, float]:
        """Every tenant's current burn rate (the autopilot's signal vector;
        "" is untenanted traffic). Pure window math — no metric mutation,
        safe from any path."""
        with self._lock:
            out = {}
            for tenant, w in self._window.items():
                if not w:
                    continue
                breaches = sum(1 for ok in w if not ok)
                out[tenant] = (breaches / len(w)) / self.error_budget
            return out


__all__ = ["FlightRecorder", "RequestRecord", "ServeMetrics"]
