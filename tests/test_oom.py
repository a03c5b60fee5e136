"""OOM defense: memory monitor + group-by-owner worker killing.

Shape parity: reference python/ray/tests/test_memory_pressure.py — a node under
memory pressure kills workers (retriable-first, newest-owner-first) and
survives; killed retriable tasks rerun once pressure drops.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private.memory_monitor import (
    MemoryMonitor,
    _read_meminfo,
    pick_worker_to_kill,
)


class _FakeHandle:
    def __init__(self, kind="worker", busy_task=None, actor_id=None,
                 task_started_at=0.0, started_at=0.0):
        self.kind = kind
        self.busy_task = busy_task
        self.actor_id = actor_id
        self.task_started_at = task_started_at
        self.started_at = started_at


def _task(owner: str, retries: int):
    return {"owner": {"worker_id": owner}, "retries_left": retries}


def test_policy_prefers_retriable_then_newest_owner():
    old_nonretriable = _FakeHandle(busy_task=_task("A", 0), task_started_at=1.0)
    retriable_old = _FakeHandle(busy_task=_task("B", 2), task_started_at=2.0)
    retriable_new = _FakeHandle(busy_task=_task("C", 2), task_started_at=9.0)
    victim = pick_worker_to_kill([old_nonretriable, retriable_old, retriable_new])
    # Retriable groups are preferred, and among them the newest task dies first.
    assert victim is retriable_new

    # Within one owner's group the newest worker dies first.
    a1 = _FakeHandle(busy_task=_task("A", 1), task_started_at=1.0)
    a2 = _FakeHandle(busy_task=_task("A", 1), task_started_at=5.0)
    assert pick_worker_to_kill([a1, a2]) is a2

    # Only non-retriable work left: still kills (the node must survive).
    assert pick_worker_to_kill([old_nonretriable]) is old_nonretriable

    # Drivers are never victims; actors are last resort (newest first).
    driver = _FakeHandle(kind="driver")
    actor_old = _FakeHandle(kind="actor", actor_id="x", started_at=1.0)
    actor_new = _FakeHandle(kind="actor", actor_id="y", started_at=2.0)
    assert pick_worker_to_kill([driver, actor_old, actor_new]) is actor_new
    assert pick_worker_to_kill([driver]) is None


def test_meminfo_parsing(tmp_path):
    p = tmp_path / "meminfo"
    p.write_text("MemTotal:       100 kB\nMemFree:         5 kB\nMemAvailable:   20 kB\n")
    total, avail = _read_meminfo(str(p))
    assert total == 100 * 1024 and avail == 20 * 1024
    assert abs(MemoryMonitor(str(p)).usage_fraction() - 0.8) < 1e-9
    assert MemoryMonitor(str(tmp_path / "missing")).usage_fraction() is None


def _write_usage(path, frac):
    total = 1000000
    path.write_text(
        f"MemTotal:       {total} kB\nMemAvailable:   {int(total * (1 - frac))} kB\n"
    )


def test_node_survives_memory_pressure(tmp_path, monkeypatch):
    """Retriable tasks under pressure: workers are killed, the node survives,
    and the task reruns to completion once pressure drops."""
    meminfo = tmp_path / "meminfo"
    _write_usage(meminfo, 0.10)
    monkeypatch.setenv("RAY_TPU_MEMINFO_PATH", str(meminfo))
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "50")
    monkeypatch.setenv("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.90")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_MIN_WAIT_S", "0.1")
    ray_tpu.init(
        num_cpus=2, num_tpus=0,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    try:
        marker = tmp_path / "attempts"

        @ray_tpu.remote(max_retries=5)
        def slow(marker_path):
            with open(marker_path, "a") as f:
                f.write("x")
            time.sleep(3.0)
            return "done"

        ref = slow.remote(str(marker))
        # Wait for the first attempt to actually start, then apply pressure.
        deadline = time.monotonic() + 60
        while not marker.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert marker.exists(), "task never started"
        _write_usage(meminfo, 0.97)
        # Pressure stays on until the worker has been killed (a new attempt
        # will re-append to the marker file after requeue).
        first_attempts = len(marker.read_text())
        deadline = time.monotonic() + 60
        while len(marker.read_text()) <= first_attempts and time.monotonic() < deadline:
            time.sleep(0.1)
        assert len(marker.read_text()) > first_attempts, "no OOM kill + retry happened"
        _write_usage(meminfo, 0.10)  # pressure gone: the retry completes
        assert ray_tpu.get(ref, timeout=120) == "done"
    finally:
        ray_tpu.shutdown()


def test_oom_error_when_retries_exhausted(tmp_path, monkeypatch):
    """A non-retriable task killed by the memory monitor surfaces
    OutOfMemoryError with the monitor's cause attached."""
    meminfo = tmp_path / "meminfo"
    _write_usage(meminfo, 0.10)
    monkeypatch.setenv("RAY_TPU_MEMINFO_PATH", str(meminfo))
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "50")
    monkeypatch.setenv("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.90")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_MIN_WAIT_S", "0.1")
    ray_tpu.init(
        num_cpus=2, num_tpus=0,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    try:
        started = tmp_path / "started"

        @ray_tpu.remote(max_retries=0)
        def hog(marker_path):
            with open(marker_path, "w") as f:
                f.write("x")
            time.sleep(30.0)

        ref = hog.remote(str(started))
        deadline = time.monotonic() + 60
        while not started.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert started.exists()
        _write_usage(meminfo, 0.97)
        with pytest.raises(ray_tpu.exceptions.OutOfMemoryError, match="memory monitor"):
            ray_tpu.get(ref, timeout=120)
    finally:
        ray_tpu.shutdown()
