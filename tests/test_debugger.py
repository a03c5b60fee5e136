"""Post-mortem debugger + pushed-down task-event queries (round 5).

Parity: reference `python/ray/util/rpdb.py` (socket pdb, sessions advertised
via GCS, `ray debug` attaches) and GcsTaskManager server-side query filters.
"""

import socket
import time

import pytest

import ray_tpu
from ray_tpu._private import debugger
from ray_tpu._private.config import CONFIG


@pytest.fixture
def pm_cluster():
    ray_tpu.init(
        num_cpus=2,
        num_tpus=0,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "RAY_TPU_POST_MORTEM": "1",
            "RAY_TPU_POST_MORTEM_WAIT_S": "60",
        },
    )
    yield
    ray_tpu.shutdown()
    CONFIG._reset()


def _read_until(sock, marker: bytes, timeout: float = 30.0) -> bytes:
    sock.settimeout(timeout)
    buf = b""
    while marker not in buf:
        chunk = sock.recv(4096)
        if not chunk:
            break
        buf += chunk
    return buf


PROMPT = b"(ray_tpu-pdb) "


def test_post_mortem_breakpoint_roundtrip(pm_cluster):
    @ray_tpu.remote
    def boom():
        secret = 12345  # noqa: F841 - inspected via the debugger
        raise ValueError("park me")

    ref = boom.remote()

    # The worker parks the failing frame and advertises a session.
    from ray_tpu._private.worker import global_worker

    deadline = time.time() + 60
    sessions = []
    while time.time() < deadline:
        sessions = debugger.list_sessions(global_worker())
        if sessions:
            break
        time.sleep(0.2)
    assert sessions, "no post-mortem session advertised"
    s = sessions[0]
    assert "park me" in s["error"]
    assert s["name"] == "boom"

    # Drive pdb over the socket: inspect the raising frame, then continue.
    with socket.create_connection((s["ip"], s["port"]), timeout=30) as conn:
        banner = _read_until(conn, PROMPT)
        assert b"post-mortem" in banner and b"park me" in banner
        conn.sendall(b"p secret\n")
        out = _read_until(conn, PROMPT)
        assert b"12345" in out, out
        conn.sendall(b"c\n")

    # Releasing the debugger lets the original error propagate to the caller.
    with pytest.raises(ValueError, match="park me"):
        ray_tpu.get(ref, timeout=60)

    # The session deregisters once released.
    deadline = time.time() + 30
    while time.time() < deadline:
        if not debugger.list_sessions(global_worker()):
            break
        time.sleep(0.2)
    assert not debugger.list_sessions(global_worker())


def test_list_tasks_filters_push_down(pm_cluster):
    from ray_tpu.util import state

    @ray_tpu.remote
    def alpha():
        return 1

    @ray_tpu.remote
    def beta():
        return 2

    ray_tpu.get([alpha.remote() for _ in range(3)] + [beta.remote()],
                timeout=120)

    # Events flush on a cadence; poll for the filtered page.
    deadline = time.time() + 60
    rows = []
    while time.time() < deadline:
        rows = state.list_tasks(filters=[("name", "=", "alpha")], limit=100)
        if len({r["task_id"] for r in rows}) >= 3 and any(
            r.get("state") == "FINISHED" for r in rows
        ):
            break
        time.sleep(0.5)
    assert rows and all(r["name"] == "alpha" for r in rows)
    assert len({r["task_id"] for r in rows}) == 3

    # Pagination pushes down too: page sizes add up to the unpaged listing.
    all_alpha = state.list_tasks(filters=[("name", "=", "alpha")], limit=1000)
    page1 = state.list_tasks(filters=[("name", "=", "alpha")], limit=2)
    page2 = state.list_tasks(filters=[("name", "=", "alpha")], limit=2,
                             offset=2)
    assert [r["task_id"] for r in page1 + page2][:len(all_alpha)] == [
        r["task_id"] for r in all_alpha[:4]
    ]

    # Per-task drill-down rides the GCS index.
    tid = rows[0]["task_id"]
    events = state.get_task(tid)
    assert events and all(e["task_id"] == tid for e in events)
    states = [e["state"] for e in events]
    assert "FINISHED" in states

    # Comparison predicates evaluate server-side.
    t0 = min(e.get("time", 0.0) for e in events)
    recent = state.list_tasks(filters=[("time", ">=", t0)], limit=1000)
    assert recent
