"""Sequenced borrow protocol: registration must never race the owner's release.

Round-1/2 carried a known race: borrow registration was a fire-and-forget
notify that could reorder against the owner's last release, freeing data a
borrower still held (reference sequences this in
`src/ray/core_worker/reference_counter.h:43`). Round 3 routes registration
through the task protocol (reply-borne, strictly ordered ahead of arg-pin
release). These tests inject a large delay into the legacy notify path to
prove the sequenced paths never depend on it, and exercise crash
reconciliation of dead borrowers.
"""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def borrow_cluster(monkeypatch):
    """Cluster with the legacy borrow notify delayed 1500ms (fault injection)
    and a fast borrower audit. Any path that still depended on the async
    notify ordering would free borrowed objects under this delay."""
    monkeypatch.setenv("RAY_TPU_TEST_DELAY_BORROW_REPORT_MS", "1500")
    monkeypatch.setenv("RAY_TPU_BORROW_AUDIT_INTERVAL_S", "1")
    from ray_tpu._private.config import CONFIG

    CONFIG._reset()
    ray_tpu.init(
        num_cpus=4, num_tpus=0,
        worker_env={
            "RAY_TPU_TEST_DELAY_BORROW_REPORT_MS": "1500",
            "RAY_TPU_BORROW_AUDIT_INTERVAL_S": "1",
            "JAX_PLATFORMS": "cpu",
        },
    )
    yield
    ray_tpu.shutdown()
    monkeypatch.delenv("RAY_TPU_TEST_DELAY_BORROW_REPORT_MS")
    monkeypatch.delenv("RAY_TPU_BORROW_AUDIT_INTERVAL_S")
    CONFIG._reset()


@ray_tpu.remote
class Holder:
    def __init__(self):
        self.ref = None

    def hold(self, box):
        self.ref = box[0]
        return True

    def read(self):
        return float(ray_tpu.get(self.ref).sum())

    def drop(self):
        self.ref = None
        return True


def test_borrowed_arg_survives_owner_drop(borrow_cluster):
    """Actor keeps a borrowed arg ref past the call; the owner drops its own
    ref immediately after. The reply-borne registration must already have
    counted the actor, so the put object survives without reconstruction
    (put objects have NO lineage — a premature free here is unrecoverable)."""
    h = Holder.remote()
    ref = ray_tpu.put(np.ones(200_000))
    assert ray_tpu.get(h.hold.remote([ref]), timeout=120)
    del ref  # owner's local count -> 0 while the (delayed) legacy notify path idles
    time.sleep(2.0)  # any mis-ordered free would land in this window
    assert ray_tpu.get(h.read.remote(), timeout=120) == 200_000.0
    assert ray_tpu.get(h.drop.remote(), timeout=60)


def test_actor_task_result_ref_survives_executor_release(borrow_cluster):
    """Actor returns a ref it owns inside its result (the VERDICT actor-task
    case): the executor's task-local refs die at completion, but the caller was
    pre-counted as sub-borrower before the reply left, so materializing the
    ref later still works. Actor-task results are not reconstructible."""

    @ray_tpu.remote
    class Maker:
        def make(self):
            return [ray_tpu.put(np.full(150_000, 3.0))]

    m = Maker.remote()
    box = ray_tpu.get(m.make.remote(), timeout=120)
    time.sleep(2.0)  # executor's locals are long dead; delayed notify path idles
    assert float(ray_tpu.get(box[0], timeout=120).sum()) == 450_000.0
    del box


def test_borrow_chain_through_two_actors(borrow_cluster):
    """Driver ref -> actor A -> actor B: the sub-borrow tree keeps the object
    alive after the driver and A both drop their refs."""
    a, b = Holder.remote(), Holder.remote()
    ref = ray_tpu.put(np.ones(120_000))
    assert ray_tpu.get(a.hold.remote([ref]), timeout=120)

    @ray_tpu.remote
    def forward(src, dst):
        # Runs inside a worker: the received ref is itself a borrow; handing
        # it to B extends the chain.
        return ray_tpu.get(dst.hold.remote([src[0]]))

    assert ray_tpu.get(forward.remote([ref], b), timeout=120)
    del ref
    assert ray_tpu.get(a.drop.remote(), timeout=60)
    time.sleep(2.0)
    assert ray_tpu.get(b.read.remote(), timeout=120) == 120_000.0


def test_intermediate_borrower_crash_grandchild_survives(borrow_cluster):
    """The VERDICT transitive hole: driver ref -> actor A -> grandchild actor
    C; A is SIGKILLed while C still borrows. Sub-borrower registrations are
    mirrored to the TRUE owner, so the audit dropping A must NOT free the
    object (put objects have no lineage — a premature free is unrecoverable,
    so a successful read proves no free and no reconstruction happened)."""
    from ray_tpu._private.worker import _global_worker

    @ray_tpu.remote
    class Middle:
        def __init__(self):
            self.ref = None

        def hold(self, box):
            self.ref = box[0]
            return True

        def forward(self, child):
            # Runs inside A: handing the borrowed ref onward makes C a
            # grandchild registered with A (and, mirrored, with the owner).
            return ray_tpu.get(child.hold.remote([self.ref]), timeout=60)

    a = Middle.remote()
    c = Holder.remote()
    ref = ray_tpu.put(np.ones(130_000))
    oid = ref.id
    rc = _global_worker.reference_counter
    assert ray_tpu.get(a.hold.remote([ref]), timeout=120)
    assert ray_tpu.get(a.forward.remote(c), timeout=120)
    # The mirror is async: wait until the owner's table lists BOTH A and C.
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        keys = {k for k, oids in rc.borrower_snapshot().items() if oid in oids}
        if len(keys) >= 2:
            break
        time.sleep(0.2)
    assert len(keys) >= 2, f"grandchild never mirrored to the owner: {keys}"
    ray_tpu.kill(a)  # intermediate dies WITHOUT releasing
    del ref  # owner's local count -> 0: only borrower counts protect the data
    time.sleep(4.0)  # audit (1s) reconciles A; C's mirrored count must hold
    assert ray_tpu.get(c.read.remote(), timeout=120) == 130_000.0
    assert ray_tpu.get(c.drop.remote(), timeout=60)
    # After the grandchild releases, nothing holds the object: the owner's
    # table must fully drain (C's release lands at the owner even though its
    # borrow parent A is dead — the audit's holdings check reconciles it).
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and rc.num_borrows(oid) > 0:
        time.sleep(0.5)
    assert rc.num_borrows(oid) == 0, "borrower table leaked after release"


def test_put_embedded_ref_protected(borrow_cluster):
    """Refs embedded in put() payloads (not task args/results): the put object
    pins them for its lifetime (contained-in protection), so a reader can
    materialize the inner ref long after the owner dropped its own handle —
    even with the legacy notify path delayed 1500ms."""
    from ray_tpu._private.worker import _global_worker

    inner = ray_tpu.put(np.full(110_000, 2.0))
    inner_oid = inner.id
    outer = ray_tpu.put({"box": inner})
    del inner  # owner's only DIRECT handle dies; the put pin must hold
    time.sleep(2.0)  # any unprotected window would free inner here

    @ray_tpu.remote
    def read_inner(box):
        payload = ray_tpu.get(box[0])
        return float(ray_tpu.get(payload["box"]).sum())

    assert ray_tpu.get(read_inner.remote([outer]), timeout=120) == 220_000.0
    # Freeing the outer object releases the pin: inner must actually die
    # (protection, not a leak).
    del outer
    store = _global_worker.memory_store
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and store.get(inner_oid) is not None:
        _global_worker.reference_counter.drain_deferred()
        time.sleep(0.5)
    assert store.get(inner_oid) is None, "put-embedded pin leaked"


def test_crashed_borrower_reconciles(borrow_cluster):
    """A borrower killed without releasing must not pin the object forever:
    the owner's audit loop drops dead borrowers (reference: worker-failure
    interception in the reference counter)."""
    from ray_tpu._private.worker import _global_worker

    h = Holder.remote()
    ref = ray_tpu.put(np.ones(100_000))
    assert ray_tpu.get(h.hold.remote([ref]), timeout=120)
    oid = ref.id
    rc = _global_worker.reference_counter
    # the reply-borne registration has landed by now
    assert rc.num_borrows(oid) >= 1
    ray_tpu.kill(h)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and rc.num_borrows(oid) > 0:
        time.sleep(0.5)
    assert rc.num_borrows(oid) == 0, "dead borrower's count was never reconciled"
    # owner still holds its own ref: the object must still be readable
    assert float(ray_tpu.get(ref, timeout=60).sum()) == 100_000.0
