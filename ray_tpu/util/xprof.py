"""Compute-plane observatory: XLA program registry, device-memory ledger,
and on-demand profiler capture (docs/observability.md "compute plane").

Four pieces, all host-side and pull-free:

- **ProgramRegistry** — a per-process registry every ``_program``-style jit
  cache hooks into (DecodeEngine prefill/decode/verify/install, Learner
  update, checkpoint restore).  Each compiled program gets one entry keyed
  ``(owner, key)`` recording compile wall time, invocation counts, and a
  cumulative execution estimate.  A process-wide ``xla_recompiles_total``
  counter distinguishes warmup compiles (first compile of a key) from
  post-warmup retrace storms (any later compile of an already-seen key) —
  the runtime complement to jaxlint RL602/RL604.
- **Device-memory ledger** — components register a callable returning their
  byte accounting; ``device_memory_report()`` joins every owner with the
  raw ``device.memory_stats()`` the backend provides (TPU/GPU only — the
  CPU backend returns nothing and the report says so instead of guessing).
  ``oom_snapshot()`` ranks owners by bytes for RESOURCE_EXHAUSTED
  forensics.
- **ProfilerCapture** — ``start_capture()`` / ``stop_capture()`` around
  ``jax.profiler`` trace capture, leaksan-tracked (kind
  ``profiler_capture``) and leaklint-paired so an abandoned capture cannot
  pin trace buffers forever.  ``capture(duration_s)`` is the one-shot
  helper the actor surfaces expose to ``util.state.capture_profile``.

- **span** — ``with span("rt.engine.sample", slots=3):`` names a phase of a
  host loop twice over: as a ``jax.profiler.TraceAnnotation``, so that under
  any profiler session (``capture()``, ``util.state.capture_profile``, a
  benchmark's ``start_trace``) it is an event of the host plane of the same
  ``.xplane.pb`` as the device's operations, on one clock; and as a row of a
  per-process table ``{name: [count, seconds]}`` that ``span_totals()``
  returns, for an operator without a profiler.  There is no switch: the
  annotation costs half a microsecond when no session runs.

Flush rule (PR 9/11/13): nothing here touches ``util.metrics`` on the hot
path.  Registry mutation is plain-int arithmetic; metric objects are
created lazily and updated only inside ``report()`` /
``device_memory_report()``, which are called exclusively from
``scheduler_stats()``-style report paths.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "ProgramRegistry",
    "ProfilerCapture",
    "backend_initialized",
    "capture",
    "device_memory_report",
    "is_resource_exhausted",
    "named",
    "oom_snapshot",
    "register_memory_owner",
    "registry",
    "span",
    "span_totals",
    "start_capture",
    "stop_capture",
    "unregister_memory_owner",
]

# Backstop on registry size: well past any sane program count (the engine
# caps its own caches at llm_max_jit_programs); oldest entries evicted.
_MAX_ENTRIES = 4096


class _InstrumentedProgram:
    """A compiled-program wrapper that times its first call (jax compiles
    synchronously on first invocation: trace + lower + compile happen
    inline, only execution is async) and counts every later one.  Attribute
    access falls through to the underlying jit object so callers that poke
    ``_cache_size()`` etc. keep working.  Adds zero device syncs."""

    __slots__ = ("_fn", "_entry", "_registry", "_compiled")

    def __init__(self, fn, entry, reg):
        self._fn = fn
        self._entry = entry
        self._registry = reg
        self._compiled = False

    def __call__(self, *args, **kwargs):
        if self._compiled:
            self._entry["invocations"] += 1  # GIL-cheap; no lock, no sync
            return self._fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        self._registry._note_compiled(self._entry, time.perf_counter() - t0)
        self._compiled = True
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)

    @property
    def __wrapped__(self):
        return self._fn


class ProgramRegistry:
    """Per-process registry of compiled XLA programs, keyed (owner, key)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, Any], dict] = {}
        self._recompiles_total = 0
        self._compiles_total = 0
        # metric-export watermarks: counters are exported as deltas from the
        # report path only, never from the mutation path
        self._exported = {"compiles": 0, "recompiles": 0}
        self._metrics: Dict[str, Any] = {}

    # -- registration --------------------------------------------------------

    def instrument(self, owner: str, key: Any, fn) -> _InstrumentedProgram:
        """Wrap a freshly built (uncompiled) jit program.  Re-instrumenting
        an already-seen (owner, key) — an eviction rebuild or a retrace —
        marks the next compile as a *recompile*, not warmup."""
        entry = self._entry(owner, key)
        return _InstrumentedProgram(fn, entry, self)

    def _entry(self, owner: str, key: Any) -> dict:
        rkey = (owner, _freeze(key))
        with self._lock:
            entry = self._entries.get(rkey)
            if entry is None:
                if len(self._entries) >= _MAX_ENTRIES:
                    self._entries.pop(next(iter(self._entries)))
                entry = self._entries[rkey] = {
                    "owner": owner,
                    "key": rkey[1],
                    "compiles": 0,
                    "recompiles": 0,
                    "invocations": 0,
                    "compile_s": 0.0,
                    "last_compile_s": 0.0,
                    "exec_s": 0.0,
                }
            return entry

    def _note_compiled(self, entry: dict, seconds: float) -> None:
        with self._lock:
            first = entry["compiles"] == 0
            entry["compiles"] += 1
            entry["invocations"] += 1
            entry["compile_s"] += seconds
            entry["last_compile_s"] = seconds
            self._compiles_total += 1
            if not first:
                entry["recompiles"] += 1
                self._recompiles_total += 1

    # -- call-site hooks (for programs not built through instrument()) ------

    def note_exec(self, owner: str, key: Any, seconds: float) -> None:
        """Record measured execution time at a call site that already pays
        a host sync (e.g. Learner.update after its device_get)."""
        entry = self._entry(owner, key)
        entry["exec_s"] += seconds

    def note_span(self, owner: str, key: Any, seconds: float) -> None:
        """Record a one-shot compute span (checkpoint restore): invocation
        plus wall time, with no compile accounting — restores build fresh
        programs by design and must never read as a retrace storm."""
        entry = self._entry(owner, key)
        entry["invocations"] += 1
        entry["exec_s"] += seconds

    # -- report path ---------------------------------------------------------

    @property
    def recompiles_total(self) -> int:
        return self._recompiles_total

    def report(self, owner: Optional[str] = None) -> dict:
        """Per-program rows plus process totals.  Report-path only: this is
        also where the metric objects are updated (flush rule)."""
        with self._lock:
            rows = [
                dict(e) for e in self._entries.values()
                if owner is None or e["owner"] == owner
            ]
            totals = {
                "programs": len(self._entries),
                "compiles_total": self._compiles_total,
                "recompiles_total": self._recompiles_total,
                "compile_s_total": sum(
                    e["compile_s"] for e in self._entries.values()),
            }
            compile_delta = self._compiles_total - self._exported["compiles"]
            recompile_delta = (
                self._recompiles_total - self._exported["recompiles"])
            self._exported["compiles"] = self._compiles_total
            self._exported["recompiles"] = self._recompiles_total
        rows.sort(key=lambda e: (-e["compiles"], -e["invocations"]))
        self._emit_metrics(totals, compile_delta, recompile_delta)
        return {"programs": rows, "totals": totals}

    def forget_owner(self, owner: str) -> None:
        with self._lock:
            for rkey in [k for k in self._entries if k[0] == owner]:
                del self._entries[rkey]

    def _emit_metrics(self, totals, compile_delta, recompile_delta) -> None:
        try:
            from ray_tpu.util import metrics as m

            mm = self._metrics
            if not mm:
                mm["programs"] = m.Gauge(
                    "xla_programs_registered",
                    "compiled XLA programs known to the registry")
                mm["compiles"] = m.Counter(
                    "xla_compiles_total", "XLA program compilations")
                mm["recompiles"] = m.Counter(
                    "xla_recompiles_total",
                    "post-warmup recompilations of an already-seen program "
                    "key (retrace storms; runtime RL602/RL604 complement)")
            mm["programs"].set(totals["programs"])
            if compile_delta:
                mm["compiles"].inc(compile_delta)
            if recompile_delta:
                mm["recompiles"].inc(recompile_delta)
            # report() IS the flush point (the PR 9/11/13 rule): force the
            # export so a scrape right after a stats call sees fresh counters.
            for metric in mm.values():
                metric.flush()
        except Exception:
            pass  # metrics plane unavailable (no ray runtime): report still works

    def reset(self) -> None:
        """Test hook: drop every entry and counter."""
        with self._lock:
            self._entries.clear()
            self._recompiles_total = 0
            self._compiles_total = 0
            self._exported = {"compiles": 0, "recompiles": 0}


def _freeze(key):
    if isinstance(key, list):
        return tuple(_freeze(k) for k in key)
    if isinstance(key, tuple):
        return tuple(_freeze(k) for k in key)
    return key


_REGISTRY = ProgramRegistry()


def registry() -> ProgramRegistry:
    """The per-process program registry singleton."""
    return _REGISTRY


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

_SPAN_TOTALS: Dict[str, list] = {}  # name -> [count, seconds]
_SPAN_LOCK = threading.Lock()  # held for two additions, never across a call
_TRACE_ANNOTATION = None


class span:
    """One named phase of a host loop.  ``t0``/``t1`` are the two
    ``time.time()`` reads it makes, for a caller (the flight recorder) that
    stamps the same interval: one pair of reads serves both.  Attributes go
    to the profiler event only.  Plain arithmetic on exit: no metric object,
    no device access, no lock held across a call (the flush rule)."""

    __slots__ = ("name", "t0", "t1", "_annotation")

    def __init__(self, name: str, **attrs):
        global _TRACE_ANNOTATION
        if _TRACE_ANNOTATION is None:
            from jax.profiler import TraceAnnotation

            _TRACE_ANNOTATION = TraceAnnotation
        self.name = name
        self._annotation = _TRACE_ANNOTATION(name, **attrs)

    def __enter__(self):
        self._annotation.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.time()
        self._annotation.__exit__(exc_type, exc, tb)
        with _SPAN_LOCK:  # several engines' steppers may share the process
            row = _SPAN_TOTALS.get(self.name)
            if row is None:
                row = _SPAN_TOTALS[self.name] = [0, 0.0]
            row[0] += 1
            row[1] += self.t1 - self.t0
        return False


def named(name: str, fn, **static):
    """`fn` (with `static` keyword arguments bound) under a `__name__` of its
    own. jax names a compiled program after the function it traced
    (`jit_<name>`), and that name is what a device trace shows: the engine's
    and the draft model's programs are named here, by what they do and their
    static sizes, and not by whatever the Python function happens to be called
    (PERF.md §3)."""

    def program(*args):
        return fn(*args, **static)

    program.__name__ = program.__qualname__ = name
    return program


def span_totals() -> Dict[str, dict]:
    """``{name: {"count", "seconds"}}`` of every span this process has closed."""
    with _SPAN_LOCK:
        return {name: {"count": row[0], "seconds": row[1]}
                for name, row in sorted(_SPAN_TOTALS.items())}


# ---------------------------------------------------------------------------
# Device-memory ledger
# ---------------------------------------------------------------------------

_MEM_LOCK = threading.Lock()
_MEM_OWNERS: Dict[str, Callable[[], dict]] = {}
_MEM_METRICS: Dict[str, Any] = {}
#: own lock so metric creation/set never holds _MEM_LOCK through a flush RPC
_MEM_METRICS_LOCK = threading.Lock()


def register_memory_owner(name: str, fn: Callable[[], dict]) -> None:
    """Register a byte-accounting callable under ``name``.  ``fn`` returns
    ``{"bytes": int}`` at minimum; optional ``"per_device": {dev: bytes}``
    and ``"host_bytes": int`` refine the attribution.  It is called from
    report paths only and must not touch device state (shape metadata is
    fine; ``device_get`` is not)."""
    with _MEM_LOCK:
        _MEM_OWNERS[name] = fn


def unregister_memory_owner(name: str) -> None:
    with _MEM_LOCK:
        _MEM_OWNERS.pop(name, None)


def backend_initialized() -> bool:
    """True once this process has initialised a JAX backend (and so, on a TPU
    host, holds the chip). Asking does not initialise one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def device_memory_report() -> dict:
    """One per-device view of framework-attributed bytes by owner plus raw
    backend ``memory_stats()`` (peak/in-use) where available.  Report-path
    only (also updates the ledger gauges)."""
    with _MEM_LOCK:
        owners = dict(_MEM_OWNERS)
    out_owners: Dict[str, dict] = {}
    per_device: Dict[str, int] = {}
    tracked_total = 0
    for name, fn in sorted(owners.items()):
        try:
            row = dict(fn() or {})
        except Exception as exc:  # a dead owner must not kill the report
            out_owners[name] = {"error": repr(exc)}
            continue
        row.setdefault("bytes", 0)
        tracked_total += int(row["bytes"])
        for dev, nbytes in (row.get("per_device") or {}).items():
            per_device[str(dev)] = per_device.get(str(dev), 0) + int(nbytes)
        out_owners[name] = row
    devices: List[dict] = []
    # Only the process that computes has a backend. Anyone else (driver, CLI,
    # serve controller) skips the query: `jax.devices()` there would initialise
    # one, and on a TPU host take the chip from the worker that needs it.
    if backend_initialized():
        import jax

        for d in jax.devices():
            dev = {"id": d.id, "platform": d.platform,
                   "kind": getattr(d, "device_kind", "")}
            try:
                stats = d.memory_stats()  # CPU backend: raises/None
            except Exception:
                stats = None
            if stats:
                dev["memory_stats"] = {
                    k: stats[k] for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                    if k in stats
                }
            devices.append(dev)
    report = {
        "owners": out_owners,
        "tracked_bytes_total": tracked_total,
        "per_device_tracked_bytes": per_device,
        "devices": devices,
    }
    _emit_mem_metrics(out_owners, tracked_total)
    return report


def _emit_mem_metrics(owners: Dict[str, dict], total: int) -> None:
    try:
        from ray_tpu.util import metrics as m

        with _MEM_METRICS_LOCK:
            if not _MEM_METRICS:
                _MEM_METRICS["owner"] = m.Gauge(
                    "device_mem_owner_bytes",
                    "framework-attributed device bytes by owner",
                    tag_keys=("owner",))
                _MEM_METRICS["total"] = m.Gauge(
                    "device_mem_tracked_bytes",
                    "framework-attributed device bytes, all owners")
            metrics = dict(_MEM_METRICS)
        for name, row in owners.items():
            if "bytes" in row:
                metrics["owner"].set(row["bytes"], tags={"owner": name})
        metrics["total"].set(total)
        for metric in metrics.values():
            metric.flush()
    except Exception:
        pass


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when an exception looks like a device-memory exhaustion escape
    (XLA surfaces these as RESOURCE_EXHAUSTED / out-of-memory strings on
    every backend; there is no stable exception type to catch)."""
    text = f"{type(exc).__name__}: {exc}"
    low = text.lower()
    return ("resource_exhausted" in low or "resource exhausted" in low
            or "out of memory" in low or "out_of_memory" in low)


def oom_snapshot() -> dict:
    """The ledger ranked by bytes descending — what the flight recorder
    attaches to an OOM before the engine re-raises."""
    report = device_memory_report()
    ranked = sorted(
        ((name, row.get("bytes", 0)) for name, row in report["owners"].items()
         if "error" not in row),
        key=lambda kv: -kv[1])
    return {
        "ts": time.time(),
        "ranked_owners": [{"owner": n, "bytes": b} for n, b in ranked],
        "tracked_bytes_total": report["tracked_bytes_total"],
        "devices": report["devices"],
    }


# ---------------------------------------------------------------------------
# Profiler capture
# ---------------------------------------------------------------------------

_CAPTURE_LOCK = threading.Lock()
_ACTIVE_CAPTURE: Optional["ProfilerCapture"] = None

# per-file / per-capture caps when shipping trace artifacts across actors
_MAX_FILE_BYTES = 4 << 20
_MAX_CAPTURE_BYTES = 32 << 20


class ProfilerCapture:
    """A single in-flight ``jax.profiler`` trace capture.  Acquire with
    ``start_capture()``; release with ``stop_capture()`` (or ``close()``,
    the abandon path) — leaklint pairs them (RL801) and leaksan tracks the
    live handle under kind ``profiler_capture``."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.started_at = time.time()
        self.backend_trace = False
        self._stopped = False
        try:
            import jax

            jax.profiler.start_trace(log_dir)
            self.backend_trace = True
        except Exception:
            # backend without a profiler (or a capture already running
            # outside us): the manifest records the miss, artifacts still
            # round-trip so the fleet path stays testable everywhere
            self.backend_trace = False
        from ray_tpu.devtools import leaksan

        leaksan.track("profiler_capture", self, detail=log_dir)

    def stop_capture(self) -> dict:
        """Stop the trace and write ``capture_manifest.json`` into the log
        dir; idempotent.  Returns the manifest."""
        if self._stopped:
            return self._manifest()
        self._stopped = True
        if self.backend_trace:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
        manifest = self._manifest()
        try:
            os.makedirs(self.log_dir, exist_ok=True)
            path = os.path.join(self.log_dir, "capture_manifest.json")
            with open(path, "w") as f:
                json.dump(manifest, f, indent=1)
        except OSError:
            pass
        from ray_tpu.devtools import leaksan

        leaksan.untrack("profiler_capture", self)
        global _ACTIVE_CAPTURE
        with _CAPTURE_LOCK:
            if _ACTIVE_CAPTURE is self:
                _ACTIVE_CAPTURE = None
        return manifest

    def close(self) -> dict:
        return self.stop_capture()

    def _manifest(self) -> dict:
        return {
            "log_dir": self.log_dir,
            "started_at": self.started_at,
            "duration_s": time.time() - self.started_at,
            "backend_trace": self.backend_trace,
            "pid": os.getpid(),
        }


def start_capture(log_dir: Optional[str] = None) -> ProfilerCapture:
    """Start a trace capture (one per process at a time).  The returned
    handle must be released via ``stop_capture()``/``close()``."""
    global _ACTIVE_CAPTURE
    with _CAPTURE_LOCK:
        if _ACTIVE_CAPTURE is not None:
            raise RuntimeError(
                f"profiler capture already active: {_ACTIVE_CAPTURE.log_dir}")
        if log_dir is None:
            log_dir = tempfile.mkdtemp(prefix="ray_tpu_xprof_")
        cap = ProfilerCapture(log_dir)
        _ACTIVE_CAPTURE = cap
        return cap


def stop_capture() -> Optional[dict]:
    """Stop the process's active capture, if any (module-level convenience
    for operator consoles; the handle's own method is the canonical path)."""
    with _CAPTURE_LOCK:
        cap = _ACTIVE_CAPTURE
    return cap.stop_capture() if cap is not None else None


def capture(duration_s: float = 3.0, log_dir: Optional[str] = None) -> dict:
    """One-shot capture: start, run for ``duration_s``, stop, and return the
    trace artifacts inline (size-capped) so an actor caller can gather them
    to the driver without a shared filesystem."""
    cap = start_capture(log_dir)
    trace_dir = cap.log_dir
    try:
        time.sleep(duration_s)
    finally:
        manifest = cap.stop_capture()
    files: Dict[str, bytes] = {}
    truncated: List[str] = []
    budget = _MAX_CAPTURE_BYTES
    for root, _dirs, names in os.walk(trace_dir):
        for name in sorted(names):
            path = os.path.join(root, name)
            rel = os.path.relpath(path, trace_dir)
            try:
                size = os.path.getsize(path)
                if size > _MAX_FILE_BYTES or size > budget:
                    truncated.append(rel)
                    continue
                with open(path, "rb") as f:
                    files[rel] = f.read()
                budget -= size
            except OSError:
                truncated.append(rel)
    return {"log_dir": trace_dir, "manifest": manifest,
            "files": files, "truncated": truncated}
