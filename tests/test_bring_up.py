"""Repairs the chip run needed, checked where they can be without the chip: the
accelerator demand reaches the scheduler, the driver stays off JAX, workers are
pinned to the chips they were granted, the compile cache has one fixed home, and an
unknown model is an error."""

import os
import subprocess
import sys
import time
import types

import pytest

import ray_tpu
from ray_tpu.remote_function import _build_resources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _replica_options(app, found=None) -> dict:
    """{deployment name: ray_actor_options} over a bound application graph."""
    from ray_tpu.serve import Application

    found = {} if found is None else found
    found[app.deployment.name] = app.deployment.config.ray_actor_options

    def walk(v):
        if isinstance(v, Application):
            _replica_options(v, found)
        elif isinstance(v, dict):
            for x in v.values():
                walk(x)
        elif isinstance(v, (list, tuple)):
            for x in v:
                walk(x)

    walk(app.init_args)
    walk(app.init_kwargs)
    return found


def _openai(config):
    from ray_tpu.llm import build_openai_app

    return build_openai_app([config])


def _dp(config):
    from ray_tpu.llm.dp_serve import build_dp_openai_app

    return build_dp_openai_app(config, dp_size=2)


def _pd(config):
    from ray_tpu.llm.pd_disagg import build_pd_openai_app

    return build_pd_openai_app(config)


@pytest.mark.parametrize("tp,want", [(1, 1.0), (2, 2.0)])
@pytest.mark.parametrize("build,replicas", [
    (_openai, {"LLMServer-test-tiny"}),
    (_dp, {"DPLLMServer-test-tiny"}),
    (_pd, {"Prefill-test-tiny", "Decode-test-tiny"}),
])
def test_builders_reserve_the_accelerator(monkeypatch, build, replicas, tp, want):
    """Every replica deployment's actor options resolve to {"TPU": n}: the key the
    scheduler reads, scaled by the TP degree. No cluster: the DP builder's rank
    assigner is the one actor a builder starts, and it is stubbed."""
    from ray_tpu.actor import ActorClass
    from ray_tpu.llm import LLMConfig

    # the one call a builder makes on it: the DP builder sizes the assigner it finds
    assigner = types.SimpleNamespace(ensure_size=types.SimpleNamespace(remote=lambda dp_size: dp_size))
    monkeypatch.setattr(ActorClass, "remote", lambda self, *a, **k: assigner)
    monkeypatch.setattr(ray_tpu, "get", lambda ref: ref)
    config = LLMConfig(model_id="test-tiny", accelerator_resources={"TPU": 1}, tp=tp)
    options = _replica_options(build(config))
    assert replicas <= set(options)
    for name in replicas:
        assert _build_resources(options[name]) == {"TPU": want}, name
        # and what the controller does with them is accepted, not dropped
        ray_tpu.remote(**options[name])(type("Replica", (), {}))


@pytest.mark.parametrize("target", [type("C", (), {}), lambda: None], ids=["actor", "task"])
def test_bare_resource_name_among_options_is_an_error(target):
    with pytest.raises(ValueError, match="resources="):
        ray_tpu.remote(num_cpus=0, TPU=1.0)(target)


def test_unknown_model_id_raises():
    from ray_tpu.llm import LLMConfig, load_model

    with pytest.raises(ValueError, match="no-such-model"):
        load_model(LLMConfig(model_id="no-such-model"))


@pytest.mark.parametrize("env,want", [
    ("/somewhere/else", "/somewhere/else"),
    (None, os.path.join(REPO, ".jax_cache")),
])
def test_compile_cache_has_one_home(monkeypatch, env, want):
    from ray_tpu.util import compile_cache

    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    assert compile_cache.compile_cache_dir() == want
    # twice, a process apart in time: no pid, temp name or clock in the path
    assert compile_cache.compile_cache_dir() == want


@pytest.mark.parametrize("chips,on_host,want", [
    ([2], 4, {"TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1", "TPU_HOST_BOUNDS": "1,1,1"}),
    ([0, 1], 4, {"TPU_VISIBLE_CHIPS": "0,1", "TPU_CHIPS_PER_HOST_BOUNDS": "1,2,1", "TPU_HOST_BOUNDS": "1,1,1"}),
    ([0, 1, 2, 3], 4, {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}),  # all of them: the host's own
    ([0], 1, {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}),
])
def test_set_visible_chips(chips, on_host, want):
    from ray_tpu.accelerators import TPUAcceleratorManager

    env = {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "OTHER": "kept"}
    TPUAcceleratorManager.set_visible_chips(chips, env, chips_on_host=on_host)
    assert env == {"OTHER": "kept", **want}


def test_three_of_four_chips_is_refused():
    from ray_tpu.accelerators import TPUAcceleratorManager

    with pytest.raises(ValueError, match="3 of 4"):
        TPUAcceleratorManager.set_visible_chips([0, 1, 2], {}, chips_on_host=4)


_DRIVER = """
import sys
import ray_tpu
from ray_tpu.util import xprof

ray_tpu.init(num_cpus=1)  # num_tpus=None: the detection path runs
assert not xprof.backend_initialized(), "ray_tpu.init() initialised a JAX backend"
report = xprof.device_memory_report()  # what `ray_tpu status` calls in the driver
assert report["devices"] == [], report
assert not xprof.backend_initialized(), "the report path initialised a JAX backend"
ray_tpu.shutdown()
print("DRIVER-OK")
"""


def test_init_and_report_leave_the_driver_off_jax():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env, capture_output=True,
                          text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0 and "DRIVER-OK" in proc.stdout, proc.stdout + proc.stderr


def test_actor_is_pinned_to_its_chips_and_others_to_the_cpu():
    """On a host that advertises chips, an actor that asks for one sees that chip and
    no other; a worker that asked for none is held to the CPU backend."""
    pin = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS", "JAX_PLATFORMS")

    class Probe:
        def env(self):
            return {k: os.environ.get(k) for k in pin}

    # no JAX_PLATFORMS in worker_env: the raylet decides
    ray_tpu.init(num_cpus=2, num_tpus=2,
                 worker_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    try:
        on_chip = [ray_tpu.remote(num_cpus=0, num_tpus=1)(Probe).remote() for _ in range(2)]
        plain = ray_tpu.remote(num_cpus=0)(Probe).remote()
        envs = ray_tpu.get([a.env.remote() for a in on_chip], timeout=120)
        assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == ["0", "1"]
        assert all(e["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1" and e["TPU_HOST_BOUNDS"] == "1,1,1"
                   for e in envs)
        off_chip = ray_tpu.get(plain.env.remote(), timeout=120)
        assert off_chip["JAX_PLATFORMS"] == "cpu" and off_chip["TPU_VISIBLE_CHIPS"] is None
    finally:
        ray_tpu.shutdown()


def test_node_survives_a_memory_read_that_blocks(tmp_path, monkeypatch):
    """Seen on the v5e host: while a worker starts the TPU runtime, reading
    /proc/meminfo blocks for about 8 s. The raylet read it on its event loop, missed
    `node_death_timeout_s` (then 5 s) of heartbeats, and the GCS declared the only node dead.
    A FIFO nobody writes to stands in for the blocked read."""
    fifo = tmp_path / "meminfo"
    os.mkfifo(fifo)
    monkeypatch.setenv("RAY_TPU_MEMINFO_PATH", str(fifo))
    monkeypatch.setenv("RAY_TPU_NODE_DEATH_TIMEOUT_S", "3")  # the default is 30: too long to wait
    ray_tpu.init(num_cpus=1, num_tpus=0)
    try:
        time.sleep(3 + 2)  # the timeout and two heartbeats
        assert [n["alive"] for n in ray_tpu.nodes()] == [True]
        assert ray_tpu.get(ray_tpu.remote(lambda: 7).remote(), timeout=60) == 7
    finally:
        fd = os.open(fifo, os.O_WRONLY | os.O_NONBLOCK)  # let the blocked reader go
        os.close(fd)
        ray_tpu.shutdown()
