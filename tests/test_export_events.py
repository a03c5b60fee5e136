"""Structured export events.

Shape parity: reference src/ray/protobuf/export_*.proto +
observability/ray_event_recorder.cc + dashboard/modules/aggregator — cluster
state transitions (nodes, actors, tasks) land as durable JSONL records an
external aggregator can consume without touching the GCS tables.
"""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import state


@pytest.fixture
def export_cluster(tmp_path, monkeypatch):
    exp = tmp_path / "exports"
    monkeypatch.setenv("RAY_TPU_EXPORT_EVENTS_DIR", str(exp))
    from ray_tpu._private.config import CONFIG

    CONFIG._reset()
    ray_tpu.init(
        num_cpus=2, num_tpus=0,
        worker_env={
            "RAY_TPU_EXPORT_EVENTS_DIR": str(exp),
            "JAX_PLATFORMS": "cpu",
        },
    )
    yield str(exp)
    ray_tpu.shutdown()
    monkeypatch.delenv("RAY_TPU_EXPORT_EVENTS_DIR")
    CONFIG._reset()


def test_export_events_recorded_and_aggregatable(export_cluster):
    exp = export_cluster

    @ray_tpu.remote
    class Recorder:
        def mark(self):
            return "done"

    @ray_tpu.remote
    def traced_task():
        return 1

    a = Recorder.remote()
    assert ray_tpu.get(a.mark.remote(), timeout=120) == "done"
    ray_tpu.kill(a)
    # Task events flush from live workers on a 5s cadence (a killed actor's
    # buffer dies with it): a plain task's worker stays alive to flush.
    assert ray_tpu.get(traced_task.remote(), timeout=120) == 1

    # Node + actor transitions and task events flush on their own timers.
    deadline = time.time() + 30
    while time.time() < deadline:
        nodes = state.list_export_events(exp, source_type="node")
        actors = state.list_export_events(exp, source_type="actor")
        tasks = state.list_export_events(exp, source_type="task")
        if nodes and actors and any(
            e["event_data"].get("name") == "traced_task" for e in tasks
        ):
            break
        time.sleep(0.5)
    assert nodes, "no node export events"
    assert any(e["event_data"].get("node", {}).get("is_head") for e in nodes)
    states = {e["event_data"].get("actor", {}).get("state") for e in actors}
    assert "ALIVE" in states and "DEAD" in states, states
    # Records carry the export schema and survive raw JSONL parsing.
    for rec in (nodes + actors)[:5]:
        assert rec["source_type"] in ("node", "actor")
        assert rec["event_id"] and rec["timestamp"] > 0
    raw = open(os.path.join(exp, "export_actor.jsonl")).read().splitlines()
    assert all(json.loads(line) for line in raw)
    # The combined aggregator view is time-ordered across source types.
    combined = state.list_export_events(exp)
    times = [r["timestamp"] for r in combined]
    assert times == sorted(times)
    assert {r["source_type"] for r in combined} >= {"node", "actor", "task"}
