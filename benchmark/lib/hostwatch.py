"""What the host did to a measured window: a note for the run's log, never a metric.

A serve cell's numbers are taken on the host's clock with the host in the loop, and a
one-chip machine shares its host's cores. A run that reads far from the others has as a
rule lost a second or two somewhere; this says where to look: time stolen from the
machine (`/proc/stat`), CPU pressure (`/proc/pressure/cpu`), the collector's pauses
(`gc.callbacks`), involuntary context switches, and how late a thread that sleeps
`TICK_S` at a time woke (every thread of the process stands still in a pause, whoever
caused it). Nothing here is read by a metric's reader or by the driver's check. A traced
run has no such thread: the profiler would record its every call, and `lib/trace_reduce.py`
names idle gaps after the Python thread with the most calls.
"""

from __future__ import annotations

import gc
import json
import resource
import threading
import time

TICK_S = 0.02
LATE_S = 0.1  # a wake-up later than this counts as a stall


def _proc_stat() -> tuple:
    """(all jiffies, stolen jiffies) of the machine, or zeros where /proc has no such line."""
    try:
        with open("/proc/stat") as f:
            cpu = [int(x) for x in f.readline().split()[1:]]
        return sum(cpu[:8]), cpu[7]
    except (OSError, ValueError, IndexError):
        return 0, 0


def _pressure_us() -> int:
    try:
        with open("/proc/pressure/cpu") as f:
            return int(f.readline().split("total=")[1])
    except (OSError, ValueError, IndexError):
        return 0


class HostWatch:
    def __init__(self, ticker: bool = True):
        self._stop = threading.Event()
        self._late, self._stalls, self._worst, self._worst_at = 0.0, 0, 0.0, 0.0
        self._gc_t0, self._gc_s, self._gc_worst, self._gc_n = 0.0, 0.0, 0.0, [0, 0, 0]
        self._t0 = time.perf_counter()
        self._cpu0 = time.process_time()
        self._stat0, self._psi0 = _proc_stat(), _pressure_us()
        self._sw0 = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._tick, name="bench-hostwatch", daemon=True) if ticker else None
        if ticker:
            self._thread.start()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._gc_t0
        self._gc_s += took
        self._gc_worst = max(self._gc_worst, took)
        self._gc_n[min(int(info.get("generation", 0)), 2)] += 1

    def _tick(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(TICK_S):
            now = time.perf_counter()
            late = now - last - TICK_S
            if late > LATE_S:
                self._late += late
                self._stalls += 1
            if late > self._worst:
                self._worst, self._worst_at = late, now - self._t0
            last = now

    def stop(self) -> str:
        """Ends the watch; the note."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        gc.callbacks.remove(self._on_gc)
        wall = time.perf_counter() - self._t0
        stat1 = _proc_stat()
        jiffies = max(1, stat1[0] - self._stat0[0])
        out = {
            "wall_s": round(wall, 3),
            "process_cpu_s": round(time.process_time() - self._cpu0, 3),
            "machine_steal_share": round((stat1[1] - self._stat0[1]) / jiffies, 5),
            "cpu_pressure_s": round((_pressure_us() - self._psi0) / 1e6, 3),
            "involuntary_switches": resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw - self._sw0,
            "gc_collections": self._gc_n, "gc_s": round(self._gc_s, 4), "gc_worst_s": round(self._gc_worst, 4),
        }
        if self._thread is not None:
            out.update({"wake_worst_late_s": round(self._worst, 4), "wake_worst_at_s": round(self._worst_at, 2),
                        "stalls_over_0.1s": self._stalls,
                        "stalled_s": round(self._late, 3)})
        return "host in the window: " + json.dumps(out)


def start(ticker: bool = True) -> HostWatch:
    return HostWatch(ticker)
