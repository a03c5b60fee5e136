"""Test fixtures.

Parity with the reference test strategy (SURVEY.md §4): ray_start_regular boots a real
single-node cluster; ray_start_cluster yields a Cluster for multi-node tests with real
raylet processes. JAX tests run on a virtual 8-device CPU mesh (the reference pattern of
faking TPU resources on CPU nodes, python/ray/train/v2/tests/test_jax_trainer.py:16-55).
"""

import os

# Tests run on a virtual 8-device CPU mesh, even on a host that has a TPU: jax backends
# initialize lazily, so overriding the platform in-process before first use wins.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/stress variants excluded from the tier-1 "
        "wall-clock budget (tier-1 runs -m 'not slow')",
    )


# -- leaksan guard (docs/raylint.md §leaksan) ---------------------------------
# The suites whose tests exercise the acquire/release-paired resource planes
# (slot-view leases, KV prefix leases, arena pins, device-object stream
# pumps): each test in them runs under the runtime leak sanitizer and FAILS
# if it grows the live-handle registry.
LEAKSAN_SUITES = {
    "test_tensor_channel.py",
    "test_llm_kvcache.py",
    "test_llm_kvtier.py",
    "test_llm_multitenant.py",
    "test_device_objects.py",
    "test_llm_tp.py",
    "test_flight_recorder.py",
    "test_xprof.py",
    "test_autopilot.py",
    "test_llm_generate.py",
    "test_llm_stream.py",
    "test_llm_batch.py",
}


@pytest.fixture(autouse=True)
def leaksan_guard(request):
    fspath = getattr(request.node, "fspath", None)
    name = os.path.basename(str(fspath)) if fspath is not None else ""
    if name not in LEAKSAN_SUITES:
        yield
        return
    from ray_tpu.devtools import leaksan

    leaksan.enable()
    before = leaksan.snapshot()
    yield
    # rpc conns are cached per (process, peer) for the process lifetime by
    # design, so they are reported but not failed on; pump threads and every
    # lease/pin/view/stream kind must return to the baseline (gc-collected-
    # without-release counts as a leak too — see leaksan.check_growth).
    growth = leaksan.check_growth(before, settle_s=5.0)
    if growth:
        report = growth.pop("report", {})
        pytest.fail(
            f"leaksan: resource handles leaked by this test: {growth}\n"
            f"live handles: {report}", pytrace=False,
        )

# -- distsan guard (docs/raylint.md §distsan) ---------------------------------
# The suites that drive the tagged hot-path/report-path/finalizer contexts
# (the llm decode loop, scheduler stats export, stream finalizers): each test
# runs under the runtime distributed-contract sanitizer and FAILS if a metric
# mutation or GCS call landed inside a hot/finalizer context.
DISTSAN_SUITES = {
    "test_llm_engine_hotpath.py",
    "test_llm_scheduler.py",
    "test_llm_multitenant.py",
    "test_serve_observability.py",
    "test_autopilot.py",
    "test_llm_generate.py",
    "test_llm_stream.py",
    "test_llm_batch.py",
}


@pytest.fixture(autouse=True)
def distsan_guard(request):
    fspath = getattr(request.node, "fspath", None)
    name = os.path.basename(str(fspath)) if fspath is not None else ""
    if name not in DISTSAN_SUITES:
        yield
        return
    from ray_tpu.devtools import distsan

    distsan.enable()
    distsan.reset()
    yield
    found = distsan.violations()
    distsan.disable()
    distsan.reset()
    if found:
        pytest.fail(
            "distsan: control-plane traffic recorded inside a hot/finalizer "
            f"context during this test: {found}", pytrace=False,
        )


_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


@pytest.fixture(scope="module")
def ray_start_regular():
    """A single-node cluster shared by the tests in one module (fast on 1-core CI)."""
    ray_tpu.init(num_cpus=4, num_tpus=0, worker_env=_WORKER_ENV)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_isolated():
    """A fresh single-node cluster per test (for tests that mutate cluster state)."""
    ray_tpu.init(num_cpus=4, num_tpus=0, worker_env=_WORKER_ENV)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1, "env_vars": _WORKER_ENV})
    yield cluster
    cluster.shutdown()


# -- multi-device-on-CPU harness (docs/serving_tp.md) -------------------------
# Mesh/TP tests need several XLA devices, which only exist if XLA_FLAGS was
# set BEFORE jax initialized. This conftest forces it for in-process tests;
# the subprocess harness below makes mesh tests robust even when the parent
# interpreter's jax initialized under different flags (a bare
# `pytest tests/test_llm_tp.py -p no:conftest`, an embedding harness),
# so the tier-1 command exercises real meshes on any CPU-only CI box.

def run_multi_device_subprocess(code: str, *, timeout: float = 600,
                                env_extra: dict | None = None) -> dict:
    """Run `code` in a fresh interpreter with the 8-virtual-device CPU env
    forced. The snippet reports by printing one line `RESULT <json>`;
    the parsed object is returned. Failure surfaces stdout+stderr."""
    import json
    import subprocess
    import sys

    env = dict(os.environ)
    env.update(_WORKER_ENV)
    if env_extra:
        env.update(env_extra)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=repo_root,
    )
    assert proc.returncode == 0, (
        f"multi-device subprocess failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}"
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(
        f"multi-device subprocess printed no RESULT line:\n{proc.stdout[-2000:]}"
    )


@pytest.fixture(scope="session")
def multi_device_run():
    """The subprocess-spawned multi-device test group runner (TP mesh tests
    ride it so CI without TPUs — or with a parent jax initialized under
    different XLA flags — still runs them against a real 8-device mesh)."""
    return run_multi_device_subprocess
