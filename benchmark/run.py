"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from `BENCHMARK.json`: the cell names a configuration
(`benchmark/configs/<config>.json`) and a traffic mix (`benchmark/traffic/<mix>.json`);
the mix names its driver (`benchmark/drivers/<driver>.py`) and, for a train step over
several chips, its `mesh`; a driver says which kind of record it returns (`RECORD`, or
its own name); a configuration may name its block (`lib/blocks.py`); every metric has a
reader (`benchmark/metrics/<metric>.py`) that says whose records it reads. A later PR
adds files and entries and edits nothing here. See `benchmark/README.md`.

The last line of standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device`, and with `--trace 1` also `breakdown`. Without a TPU whose
published peaks are in `lib/peaks.py`, or with fewer chips than the cell asks for, it
exits non-zero and prints no result. There is no CPU size and no fallback.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")  # the CPU test points these three at its own files
TRAFFIC_DIR = os.path.join(HERE, "traffic")
DRIVERS_DIR = os.path.join(HERE, "drivers")
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_driver(name: str):
    """The driver's module and the kind of record it returns: its `RECORD`, or its own
    name. Readers bind to that kind (`DRIVERS`), so a new driver file that returns a
    record of a kind that is there reports that kind's metrics with no reader edited."""
    mod = _load_module(os.path.join(DRIVERS_DIR, name + ".py"), "benchmark_driver_" + name)
    return mod, getattr(mod, "RECORD", name)


def load_metric_readers() -> dict:
    """Every file of `benchmark/metrics/` is one metric: NAME, UNIT, DRIVERS (the kinds
    of record it can read, each named after the driver that first returned it) and
    `read(record)`."""
    out = {}
    mdir = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".py") and not fn.startswith("_"):
            mod = _load_module(os.path.join(mdir, fn), "benchmark_metric_" + fn[:-3].replace(".", "_"))
            out[mod.NAME] = mod
    return out


def metrics_of_cell(bench: dict, cell: str, kind: str) -> list:
    """The entries of `end_to_end` or `per_layer` that this cell reports."""
    return [m for m in bench[kind] if "workloads" not in m or cell in m["workloads"]]


class CompileWatch:
    """Counts what JAX builds: one `backend_compile` event per program compiled or
    read from the persistent cache, with the seconds JAX itself measured for tracing,
    lowering and compiling."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0

    def __call__(self, name: str, seconds: float, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += seconds
            if name.endswith("backend_compile_duration"):
                self.programs += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "seconds": self.seconds}


def require_chip(chips: int):
    """The devices this run measures, or no run at all. (The CPU test of this file
    replaces this function; the command has no option that does.)"""
    import jax

    from lib.peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark/run.py measures a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices, peaks_for(devices[0].device_kind)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data, the clock set-up is counted from, and the
    helpers that are the same for every driver."""

    cell: dict
    config: dict        # the configuration file, whole
    traffic: dict       # the traffic file, whole
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    compiles: CompileWatch
    t_start: float
    trace_dir: str

    @property
    def model(self) -> dict:
        return self.config["model"]

    def model_config(self, **overrides):
        """The configuration file as the program's `ModelConfig`; nothing is added to
        the program's `CONFIGS`."""
        import jax.numpy as jnp

        from ray_tpu.models.transformer import ModelConfig

        fields = dict(self.model, **overrides)
        for key in ("dtype", "param_dtype"):
            if key in fields:
                fields[key] = getattr(jnp, fields[key])
        return ModelConfig(**fields)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start


def device_block(devices, chips: int, trace_summary) -> dict:
    """`memory_peak_bytes` is the fullest chip's; `memory_peak_bytes_by_chip` every chip's
    of those the cell uses."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices[:chips]]
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": max(peaks),
           "memory_peak_bytes_by_chip": peaks}
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _read_json(BENCH_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _read_json(os.path.join(ROOT, config_entry["file"]))
    traffic = _read_json(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))

    # The compile cache sits at a fixed path inside the checkout (or where the
    # environment says); the program reads the variable when jax is imported.
    from ray_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # The engine's small programs compile in under a second each, and there are
    # many: without this they are compiled again in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    watch = CompileWatch()
    jax.monitoring.register_event_duration_secs_listener(watch)

    devices, peaks = require_chip(cell["chips"])
    import_s = time.perf_counter() - _T_START
    print(f"[bench] {args.workload} seed={args.seed} seconds={args.seconds} trace={args.trace} "
          f"device={devices[0].device_kind} x{len(devices)} cache={cache_dir}", flush=True)

    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, peaks=peaks, compiles=watch,
        t_start=_T_START,
        trace_dir=os.path.join(ROOT, ".bench_trace", args.workload),
    )
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    driver, record_kind = load_driver(traffic["driver"])
    record = driver.run(ctx)
    record["setup"]["import_s"] = import_s
    record.update(cell=cell["name"], driver=traffic["driver"], record=record_kind, model=ctx.model,
                  block=config.get("block"), chips=cell["chips"], traffic=traffic, peaks=peaks,
                  seed=args.seed)

    trace_summary = None
    if ctx.trace:
        from lib import trace_reduce

        trace_summary = trace_reduce.reduce_dir(ctx.trace_dir, chips=cell["chips"])
        record["trace"] = trace_summary
        record.setdefault("notes", []).append("programs on the device, seconds in the traced window: "
                                              + json.dumps(trace_summary["modules"]))

    readers = load_metric_readers()
    kind = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of_cell(bench, cell["name"], kind):
        reader = readers.get(entry["name"])
        applies = reader is not None and record_kind in reader.DRIVERS
        value = reader.read(record) if applies else None
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    print("[bench] setup " + json.dumps(record["setup"]), flush=True)
    line = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device_block(devices, cell["chips"], trace_summary),
    }
    if trace_summary is not None:
        line["breakdown"] = {"device_ops": trace_summary["device_ops"][:10],
                             "idle_gaps": trace_summary["idle_gaps"][:10]}
    for note in record.get("notes", []):
        print("[bench] " + note, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    # The engine's stepper is a daemon thread that may still hold the device; leave
    # without running interpreter teardown under it.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
