"""Autoscaler tests: demand-driven upscale, idle downscale, request_resources.

Shape parity: reference python/ray/tests/test_autoscaler_e2e.py +
autoscaler/v2/tests (reconciler logic against a local provider).
"""

import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import (
    Autoscaler,
    AutoscalingConfig,
    LocalNodeProvider,
    request_resources,
)
from ray_tpu.cluster_utils import Cluster

_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


@pytest.fixture
def cluster():
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 1, "env_vars": _WORKER_ENV})
    c.connect()
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_upscale_on_pending_tasks(cluster):
    autoscaler = Autoscaler(
        LocalNodeProvider(cluster),
        AutoscalingConfig(max_workers=2, worker_resources={"CPU": 2},
                          idle_timeout_s=300),
    )

    @ray_tpu.remote(num_cpus=2)  # can never fit on the 1-CPU head
    def big(x):
        return x * 2

    refs = [big.remote(i) for i in range(4)]
    # demand reaches the GCS via heartbeats; reconcile until nodes appear
    deadline = time.time() + 60
    while time.time() < deadline:
        autoscaler.reconcile_once()
        if autoscaler.num_scale_ups >= 1:
            break
        time.sleep(0.5)
    assert autoscaler.num_scale_ups >= 1
    assert ray_tpu.get(refs, timeout=120) == [0, 2, 4, 6]


def test_downscale_idle_nodes(cluster):
    provider = LocalNodeProvider(cluster)
    autoscaler = Autoscaler(
        provider,
        AutoscalingConfig(min_workers=0, max_workers=2,
                          worker_resources={"CPU": 1}, idle_timeout_s=1.0),
    )
    provider.create_node({"CPU": 1})
    deadline = time.time() + 20
    while time.time() < deadline and len(ray_tpu.nodes()) < 2:
        time.sleep(0.2)
    deadline = time.time() + 60
    while time.time() < deadline:
        autoscaler.reconcile_once()
        if autoscaler.num_scale_downs >= 1:
            break
        time.sleep(0.5)
    assert autoscaler.num_scale_downs >= 1
    assert provider.non_terminated_nodes() == []


def test_request_resources_floor(cluster):
    autoscaler = Autoscaler(
        LocalNodeProvider(cluster),
        AutoscalingConfig(max_workers=3, worker_resources={"CPU": 2},
                          idle_timeout_s=300),
    )
    request_resources(num_cpus=4)  # head has 1; needs 2 worker nodes of 2
    deadline = time.time() + 60
    while time.time() < deadline:
        autoscaler.reconcile_once()
        total = ray_tpu.cluster_resources().get("CPU", 0)
        if total >= 4:
            break
        time.sleep(0.5)
    assert ray_tpu.cluster_resources().get("CPU", 0) >= 4


def test_upscale_on_pending_actor(cluster):
    autoscaler = Autoscaler(
        LocalNodeProvider(cluster),
        AutoscalingConfig(max_workers=1, worker_resources={"CPU": 2},
                          idle_timeout_s=300),
    )

    @ray_tpu.remote(num_cpus=2)
    class Heavy:
        def ping(self):
            return "up"

    a = Heavy.remote()  # unplaceable on the 1-CPU head
    deadline = time.time() + 60
    while time.time() < deadline:
        autoscaler.reconcile_once()
        if autoscaler.num_scale_ups >= 1:
            break
        time.sleep(0.5)
    assert autoscaler.num_scale_ups >= 1
    assert ray_tpu.get(a.ping.remote(), timeout=120) == "up"


def test_gce_tpu_provider_dryrun():
    """GCETPUNodeProvider against recorded GCE responses (VERDICT #9;
    reference: python/ray/autoscaler/_private/gcp/node_provider.py)."""
    from ray_tpu.autoscaler.gcp import GCETPUNodeProvider, RecordedTransport

    transport = RecordedTransport()
    provider = GCETPUNodeProvider(
        project="proj", zone="us-central2-b", accelerator_type="v5litepod-16",
        head_address="10.0.0.1:6379", cluster_name="testcl",
        transport=transport,
    )
    nid = provider.create_node({
        "CPU": 1, "TPU": 4, "TPU-v5litepod-16": 1, "TPU-v5litepod-16-head": 1,
        "TPU-testslice": 1, "my_custom": 2, "very_custom": 1,
    })
    method, url, body = transport.requests[-1]
    assert method == "POST" and f"nodeId={nid}" in url
    assert body["acceleratorType"] == "v5litepod-16"
    script = body["metadata"]["startup-script"]
    assert "ray_tpu start --address=10.0.0.1:6379" in script
    # TPU/pod/head resources must NOT be baked into the startup script: it runs
    # on every host of the slice, and only TPU_WORKER_ID==0 may advertise the
    # gang-scheduling head resource (per-host discovery derives all of these).
    assert "head" not in script and "TPU" not in script and "v5litepod" not in script
    assert "my_custom" in script and "very_custom" in script
    assert body["labels"]["ray-tpu-cluster"] == "testcl"

    assert provider.non_terminated_nodes() == [nid]
    addr = provider.cluster_address(nid)
    assert addr is not None and addr[0].startswith("10.0.0.")
    provider.terminate_node(nid)
    assert provider.non_terminated_nodes() == []
    # Foreign/deleting slices are excluded from the cluster's node view.
    transport._nodes["other"] = {"name": "nodes/other", "state": "READY",
                                "labels": {"ray-tpu-cluster": "another"}}
    transport._nodes["dying"] = {"name": "nodes/dying", "state": "DELETING",
                                 "labels": {"ray-tpu-cluster": "testcl"}}
    assert provider.non_terminated_nodes() == []


def test_upscale_on_slice_head_gated_demand(cluster):
    """An actor gang-gated on a TPU slice-head resource drives the autoscaler
    to provision a slice-shaped node, and the gang then schedules (the
    FakeMultiNode-style e2e of VERDICT #9)."""
    slice_resources = {"CPU": 1, "TPU": 4.0, "TPU-v5e-16": 1.0,
                      "TPU-v5e-16-head": 1.0}
    autoscaler = Autoscaler(
        LocalNodeProvider(cluster),
        AutoscalingConfig(max_workers=2, worker_resources=slice_resources,
                          idle_timeout_s=300),
    )

    @ray_tpu.remote(resources={"TPU-v5e-16-head": 1.0}, num_cpus=0)
    class SliceHead:
        def where(self):
            return "on-slice"

    a = SliceHead.remote()
    ref = a.where.remote()
    deadline = time.time() + 90
    added = 0
    while time.time() < deadline:
        added += autoscaler.reconcile_once()["added"]
        if added:
            break
        time.sleep(1.0)
    assert added >= 1, "autoscaler never provisioned a slice for the gated actor"
    assert ray_tpu.get(ref, timeout=120) == "on-slice"


def test_yaml_cluster_config_roundtrip(tmp_path):
    """`ray_tpu up/down` config parsing + provider construction."""
    from ray_tpu.scripts.scripts import _build_provider, _load_cluster_yaml

    cfg_file = tmp_path / "cluster.yaml"
    cfg_file.write_text("""
cluster_name: mypod
provider:
  type: gcp_tpu
  project: proj
  zone: us-central2-b
  accelerator_type: v5litepod-16
head:
  num_cpus: 4
workers:
  min_workers: 0
  max_workers: 8
  resources: {TPU: 4, TPU-v5litepod-16: 1}
""")
    cfg = _load_cluster_yaml(str(cfg_file))
    assert cfg["cluster_name"] == "mypod"
    assert cfg["workers"]["max_workers"] == 8
    from ray_tpu.autoscaler.gcp import GCETPUNodeProvider, RecordedTransport

    provider = _build_provider(cfg, head_address="10.0.0.1:6379")
    assert isinstance(provider, GCETPUNodeProvider)
    provider._transport = RecordedTransport()
    nid = provider.create_node(dict(cfg["workers"]["resources"]))
    assert provider.non_terminated_nodes() == [nid]
