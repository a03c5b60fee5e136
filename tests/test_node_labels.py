"""Node-label scheduling + composite strategies (VERDICT r2 #10).

Reference: `src/ray/raylet/scheduling/policy/node_label_scheduling_policy.cc`
(hard/soft selectors) + `python/ray/util/scheduling_strategies.py:123-148`
(NodeLabelSchedulingStrategy with In/NotIn/Exists/DoesNotExist operators).
"""

import pytest

import ray_tpu
from ray_tpu.util.scheduling_strategies import (
    CompositeSchedulingStrategy,
    DoesNotExist,
    Exists,
    In,
    NodeLabelSchedulingStrategy,
    NotIn,
    match_labels,
)


def test_match_labels_operators():
    labels = {"zone": "us-east", "tier": "gpu"}

    def sel(**kw):
        from ray_tpu.util.scheduling_strategies import _selector_spec

        return _selector_spec(kw)

    assert match_labels(labels, sel(zone="us-east"))
    assert not match_labels(labels, sel(zone="eu"))
    assert match_labels(labels, sel(zone=In("us-east", "us-west")))
    assert not match_labels(labels, sel(zone=NotIn("us-east")))
    assert match_labels(labels, sel(tier=Exists()))
    assert not match_labels(labels, sel(missing=Exists()))
    assert match_labels(labels, sel(missing=DoesNotExist()))
    assert not match_labels(labels, sel(tier=DoesNotExist()))


def test_actor_and_task_schedule_by_label(ray_start_cluster):
    cluster = ray_start_cluster
    labeled = cluster.add_node(num_cpus=2, labels={"zone": "east", "disk": "ssd"})
    cluster.connect()
    assert cluster.wait_for_nodes()

    strategy = NodeLabelSchedulingStrategy(hard={"zone": "east"})

    @ray_tpu.remote(num_cpus=1, scheduling_strategy=strategy)
    class Pinned:
        def where(self):
            return ray_tpu.get_runtime_context().get_node_id().hex()

    a = Pinned.remote()
    assert ray_tpu.get(a.where.remote(), timeout=240) == labeled.node_id_hex

    @ray_tpu.remote(num_cpus=1, scheduling_strategy=NodeLabelSchedulingStrategy(
        hard={"disk": In("ssd", "nvme")}
    ))
    def where_task():
        return ray_tpu.get_runtime_context().get_node_id().hex()

    assert ray_tpu.get(where_task.remote(), timeout=240) == labeled.node_id_hex


def test_composite_label_or_resource_fallback(ray_start_cluster):
    """Label-OR-resource composite: with no node carrying the label, the
    second sub-strategy (plain resource scheduling) places the work."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1, resources={"fallback": 1})
    cluster.connect()
    assert cluster.wait_for_nodes()

    composite = CompositeSchedulingStrategy(any_of=[
        NodeLabelSchedulingStrategy(hard={"accelerator": "tpu-v9"}),  # nobody
        None,  # plain resource scheduling
    ])

    @ray_tpu.remote(num_cpus=0, resources={"fallback": 1},
                    scheduling_strategy=composite)
    def run():
        return "placed"

    assert ray_tpu.get(run.remote(), timeout=240) == "placed"

    @ray_tpu.remote(num_cpus=1, scheduling_strategy=composite)
    class Svc:
        def ping(self):
            return "ok"

    assert ray_tpu.get(Svc.remote().ping.remote(), timeout=240) == "ok"


def test_composite_prefers_matching_label(ray_start_cluster):
    """When the labeled node EXISTS, the first sub-strategy wins."""
    cluster = ray_start_cluster
    labeled = cluster.add_node(num_cpus=1, labels={"accelerator": "tpu-v9"})
    cluster.connect()
    assert cluster.wait_for_nodes()

    composite = CompositeSchedulingStrategy(any_of=[
        NodeLabelSchedulingStrategy(hard={"accelerator": "tpu-v9"}),
        None,
    ])

    @ray_tpu.remote(num_cpus=1, scheduling_strategy=composite)
    def where():
        return ray_tpu.get_runtime_context().get_node_id().hex()

    assert ray_tpu.get(where.remote(), timeout=240) == labeled.node_id_hex


def test_composite_lands_on_a_labeled_node_the_subscribed_view_lacks():
    """A head raylet's subscribed view may lag a node the GCS already lists. A
    composite that settles on a later sub-strategy (or on none) is resolved again
    on the GCS's own list before the task goes anywhere, and the GCS is asked at
    most once a second however many tasks come."""
    import asyncio

    from ray_tpu._private.raylet import Raylet, ResourceManager

    labeled = {"node_id": b"labeled", "alive": True, "labels": {"accelerator": "tpu-v9"},
               "resources_total": {"CPU": 1}, "resources_available": {"CPU": 1}}
    asked, forwarded = [], []

    class Gcs:
        async def call(self, verb):
            asked.append(verb)
            return [labeled]

    async def forward(spec, node_id, method="submit_task"):
        forwarded.append(node_id)
        return True

    raylet = Raylet.__new__(Raylet)  # the dispatch decision alone: no sockets, no workers
    raylet.node_id, raylet.labels = b"head", {}
    raylet.resources = ResourceManager({"CPU": 1})
    raylet.node_view = {}  # the labeled node's registration has not arrived here
    raylet.gcs, raylet._authoritative = Gcs(), (float("-inf"), {})
    raylet._forward_to_peer = forward
    composite = CompositeSchedulingStrategy(any_of=[
        NodeLabelSchedulingStrategy(hard={"accelerator": "tpu-v9"}), None]).to_spec()
    spec = {"task_id": b"t", "resources": {"CPU": 1}, "scheduling_strategy": composite}

    async def dispatch_twice():
        return [await raylet._try_dispatch(dict(spec)) for _ in range(2)]

    assert asyncio.run(dispatch_twice()) == [True, True]
    assert forwarded == [b"labeled", b"labeled"] and asked == ["get_nodes"]
    assert spec["scheduling_strategy"] == composite  # a forwarded peer resolves it again
