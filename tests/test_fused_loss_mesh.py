"""`fused_cross_entropy_loss` on a mesh: the same numbers as `cross_entropy_loss` over
whole logits, and no traffic of the table's size inside the chunk loop.

On the CPU's virtual devices (conftest.py gives eight). Under a split batch the loss
runs its chunk loop on each device's own sequences and moves the head once a step
(PERF.md §6, PR 31); where no mesh axis splits the batch it is the scan it was. The
compile of the same structure for a described v5e:2x2 at InternLM2's head is in
tests/test_chip_compile.py.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from ray_tpu.models.transformer import cross_entropy_loss, fused_cross_entropy_loss
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.spmd import _rules_list

B, S, E, V = 4, 64, 32, 96
MESHES = [{"dp": 1}, {"fsdp": 4}, {"dp": 2, "fsdp": 2}, {"dp": 4}, {"fsdp": 2, "tp": 2}]


def _mesh_id(axes):
    return "-".join(f"{k}{v}" for k, v in axes.items())


def _operands(tied: bool, masked: bool):
    k = jax.random.split(jax.random.PRNGKey(31), 4)
    hidden = jax.random.normal(k[0], (B, S, E), jnp.bfloat16)
    table = 0.2 * jax.random.normal(k[1], (V, E) if tied else (E, V), jnp.float32)
    targets = jax.random.randint(k[2], (B, S), 0, V)
    mask = (jax.random.uniform(k[3], (B, S)) > 0.3).astype(jnp.float32) if masked else None
    return hidden, table, targets, mask


def _plain(hidden, table, targets, mask, contract_dim, operands=jnp.bfloat16):
    """The model's head over the whole sequence, then `cross_entropy_loss`."""
    logits = jax.lax.dot_general(
        hidden.astype(operands), table.astype(operands),
        (((2,), (contract_dim,)), ((), ())), preferred_element_type=jnp.float32)
    return cross_entropy_loss(logits, targets, mask)


def _on_mesh(axes, hidden, table, tied):
    """The mesh, and hidden and table placed on it as the default rules place them."""
    mesh = mesh_lib.create_mesh(axes, devices=jax.devices()[:int(np.prod(list(axes.values())))])
    names = ("vocab", "embed") if tied else ("embed", "vocab")
    return mesh, (
        jax.device_put(hidden, NamedSharding(mesh, mesh_lib.logical_to_spec(("batch", "seq", None)))),
        jax.device_put(table, NamedSharding(mesh, mesh_lib.logical_to_spec(names))),
    )


def _fused(targets, mask, contract_dim, chunk):
    def loss(hidden, table):
        with nn.logical_axis_rules(_rules_list(None)):  # as build_train_step calls it
            return fused_cross_entropy_loss(hidden, table, targets, mask, chunk=chunk,
                                            contract_dim=contract_dim)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


@pytest.mark.parametrize("masked", [False, True], ids=["all_tokens", "mask"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("axes", MESHES, ids=_mesh_id)
def test_fused_loss_is_the_plain_loss_on_every_mesh(axes, tied, masked):
    hidden, table, targets, mask = _operands(tied, masked)
    contract_dim = 1 if tied else 0
    plain = jax.value_and_grad(_plain, argnums=(0, 1))
    want, (want_h, want_t) = plain(hidden, table, targets, mask, contract_dim)
    # What the plain path itself gives at bf16: its distance from the same loss with
    # float32 operands. The fused loss lies that close to the plain one, and its
    # gradients within four times that (2.3 times is the most any case reads: a
    # chunk's share of the table's gradient is rounded to bf16 before it is summed).
    exact, (exact_h, exact_t) = plain(hidden, table, targets, mask, contract_dim, jnp.float32)

    def dist(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))

    mesh, placed = _on_mesh(axes, hidden, table, tied)
    with mesh:
        got, (got_h, got_t) = _fused(targets, mask, contract_dim, chunk=16)(*placed)
    assert got_h.dtype == hidden.dtype and got_t.dtype == table.dtype
    assert abs(float(got) - float(want)) <= max(abs(float(exact) - float(want)), 1e-5)
    assert dist(got_h, want_h) <= 4 * dist(exact_h, want_h)
    assert dist(got_t, want_t) <= 4 * dist(exact_t, want_t)


def _collectives(text: str):
    """[(kind, elements of the result, inside a while body)] of a compiled module's text."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    found, inside = [], False
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            inside = head.group(1) in bodies
            continue
        op = re.search(r"= (\(?[a-z]+\d+\[[\d,]*\]).* (all-gather|all-reduce|reduce-scatter|all-to-all|"
                       r"collective-permute)(?:-start)?\(", line)
        if op:
            dims = re.search(r"\[([\d,]*)\]", op.group(1)).group(1)
            found.append((op.group(2), int(np.prod([int(d) for d in dims.split(",") if d] or [1])), inside))
    return found


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_no_collective_of_the_tables_size_in_the_chunk_loop(tied):
    """fsdp=4, the benchmark's mesh: the compiled value_and_grad holds the table's
    gather and its gradient's reduction outside both loops, and as many collectives
    at 4 chunks as at 16."""
    hidden, table, targets, _ = _operands(tied, False)
    mesh, placed = _on_mesh({"fsdp": 4}, hidden, table, tied)
    by_chunks = {}
    with mesh:
        for chunk in (16, 4):  # 4 and 16 chunks of S = 64
            text = _fused(targets, None, 1 if tied else 0, chunk).lower(*placed).compile().as_text()
            assert len(re.findall(r"body=", text)) >= 2, "a forward and a backward loop"
            by_chunks[S // chunk] = _collectives(text)
    for found in by_chunks.values():
        assert not any(inside for _, _, inside in found), found
        # of the table's size: its gather and its gradient's sum, once each
        big = sorted(kind for kind, size, _ in found if size == table.size)
        assert big in (["all-gather", "all-reduce"], ["all-gather", "reduce-scatter"]), found
    assert sorted(by_chunks[4]) == sorted(by_chunks[16])


def test_the_scan_is_what_runs_where_no_axis_splits_the_batch():
    """`tp` alone, no rules, no mesh: the loss is the scan it was, with no shard_map."""
    hidden, table, targets, _ = _operands(False, False)

    def jaxpr(**ctx):
        def loss(hidden, table):
            return fused_cross_entropy_loss(hidden, table, targets, chunk=16, contract_dim=0)
        if "rules" in ctx:
            with nn.logical_axis_rules(ctx["rules"]):
                return str(jax.make_jaxpr(loss)(hidden, table))
        return str(jax.make_jaxpr(loss)(hidden, table))

    bare = jaxpr()
    assert "shard_map" not in bare and "custom_vjp" not in bare
    with mesh_lib.create_mesh({"tp": 4}, devices=jax.devices()[:4]):
        assert jaxpr(rules=_rules_list(None)) == bare
    with mesh_lib.create_mesh({"fsdp": 4}, devices=jax.devices()[:4]):
        assert jaxpr() == bare  # a mesh and no rules: nothing says what splits the batch
        assert "shard_map" in jaxpr(rules=_rules_list(None))
        # three sequences over four devices: the axes do not divide the batch
        odd = jax.make_jaxpr(lambda h, t: fused_cross_entropy_loss(
            h, t, targets[:3], chunk=16, contract_dim=0))
        with nn.logical_axis_rules(_rules_list(None)):
            assert "shard_map" not in str(odd(hidden[:3], table))
