"""Pipeline parallelism: GPipe schedule over the pp mesh axis.

The key property: the pipelined loss AND its gradients match the unpipelined
sequential reference exactly (same layer order, same microbatch-averaged loss),
with autodiff generating the backward pipeline through reversed ppermutes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.pipeline import (
    PipelineState,
    bubble_fraction,
    build_pipeline_loss,
    build_pipeline_train_step,
    init_pipeline_state,
    sequential_reference_loss,
)

V, E, H, T = 31, 16, 32, 12
L = 8  # layers, divisible by pp


def _embed_fn(p, tokens):
    return p["table"][tokens]


def _layer_fn(p, x):
    h = jax.nn.gelu(x @ p["w1"])
    return x + h @ p["w2"]


def _head_loss_fn(p, x, targets):
    logits = x @ p["w"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def _make_params(rng):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    scale = 0.1
    return {
        "embed": {"table": scale * jax.random.normal(k1, (V, E))},
        "layers": {
            "w1": scale * jax.random.normal(k2, (L, E, H)),
            "w2": scale * jax.random.normal(k3, (L, H, E)),
        },
        "head": {"w": scale * jax.random.normal(k4, (E, V))},
    }


def _data(rng, batch):
    kt, kl = jax.random.split(rng)
    tokens = jax.random.randint(kt, (batch, T), 0, V)
    targets = jax.random.randint(kl, (batch, T), 0, V)
    return tokens, targets


@pytest.mark.parametrize("axes,batch,microbatches", [
    ({"pp": 4}, 8, 4),
    ({"pp": 2, "dp": 2}, 8, 2),
    ({"pp": 8}, 16, 8),
])
def test_pipeline_matches_sequential(axes, batch, microbatches):
    mesh = mesh_lib.create_mesh(axes)
    params = _make_params(jax.random.PRNGKey(0))
    tokens, targets = _data(jax.random.PRNGKey(1), batch)

    pipe_loss = build_pipeline_loss(
        _embed_fn, _layer_fn, _head_loss_fn, mesh, microbatches
    )
    ref_loss = sequential_reference_loss(_embed_fn, _layer_fn, _head_loss_fn)

    with mesh:
        lp, gp = jax.jit(jax.value_and_grad(pipe_loss))(params, tokens, targets)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss))(params, tokens, targets)

    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-5)
    flat_p, _ = jax.tree_util.tree_flatten(gp)
    flat_r, _ = jax.tree_util.tree_flatten(gr)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6)


def test_pipeline_train_step_learns():
    mesh = mesh_lib.create_mesh({"pp": 4})
    params = _make_params(jax.random.PRNGKey(0))
    optimizer = optax.adam(1e-2)
    state = init_pipeline_state(params, optimizer, mesh)
    step_fn, shardings = build_pipeline_train_step(
        _embed_fn, _layer_fn, _head_loss_fn, optimizer, mesh, num_microbatches=4
    )
    tokens, _ = _data(jax.random.PRNGKey(1), 8)
    targets = tokens  # learn the identity mapping: loss must drop fast
    batch = {
        "tokens": jax.device_put(tokens, shardings["tokens"]),
        "targets": jax.device_put(targets, shardings["targets"]),
    }
    with mesh:
        state, first = step_fn(state, batch)
        for _ in range(30):
            state, metrics = step_fn(state, batch)
    assert float(metrics["loss"]) < 0.5 * float(first["loss"])
    assert int(state.step) == 31


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(2, 14) == pytest.approx(1 / 15)


def test_pipeline_rejects_bad_shapes():
    mesh = mesh_lib.create_mesh({"pp": 2})
    loss = build_pipeline_loss(_embed_fn, _layer_fn, _head_loss_fn, mesh, 3)
    params = _make_params(jax.random.PRNGKey(0))
    tokens, targets = _data(jax.random.PRNGKey(1), 8)  # 8 % 3 != 0
    with pytest.raises(ValueError, match="divisible"):
        with mesh:
            jax.jit(loss)(params, tokens, targets)

    with pytest.raises(ValueError, match="pp axis"):
        build_pipeline_loss(
            _embed_fn, _layer_fn, _head_loss_fn, mesh_lib.create_mesh({"dp": 2}), 2
        )


@pytest.mark.parametrize("axes,specs", [
    # tp shards the layer matmuls' hidden dim and the head's vocab dim;
    # XLA inserts the tensor-parallel collectives INSIDE the pipeline
    # (manual pp + auto tp — pipeline.py round-5 composition).
    ({"pp": 2, "tp": 2}, True),
    ({"pp": 2, "dp": 2, "tp": 2}, True),
])
def test_pipeline_composes_with_tp(axes, specs):
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.pipeline import place_pipeline_params

    mesh = mesh_lib.create_mesh(axes)
    params = _make_params(jax.random.PRNGKey(0))
    batch = 8
    tokens, targets = _data(jax.random.PRNGKey(1), batch)
    param_specs = {
        "layers": {"w1": P(None, "tp"), "w2": P("tp", None)},
        "head": {"w": P("tp", None)},  # contraction-dim sharding: V=31 is odd
    } if specs else None

    pipe_loss = build_pipeline_loss(
        _embed_fn, _layer_fn, _head_loss_fn, mesh, 4, param_specs=param_specs
    )
    ref_loss = sequential_reference_loss(_embed_fn, _layer_fn, _head_loss_fn)

    with mesh:
        placed = place_pipeline_params(params, mesh, param_specs=param_specs)
        # Placement really is tp-sharded (not a silent replicate).
        w1_sharding = placed["layers"]["w1"].sharding
        assert "tp" in (w1_sharding.spec[2] or ()), w1_sharding.spec
        lp, gp = jax.jit(jax.value_and_grad(pipe_loss))(placed, tokens, targets)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss))(params, tokens, targets)

    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-5)
    flat_p, _ = jax.tree_util.tree_flatten(gp)
    flat_r, _ = jax.tree_util.tree_flatten(gr)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_pipeline_train_step_learns_with_tp():
    from jax.sharding import PartitionSpec as P

    mesh = mesh_lib.create_mesh({"pp": 2, "tp": 2})
    params = _make_params(jax.random.PRNGKey(0))
    optimizer = optax.adam(1e-2)
    param_specs = {
        "layers": {"w1": P(None, "tp"), "w2": P("tp", None)},
        "head": {"w": P("tp", None)},
    }
    state = init_pipeline_state(params, optimizer, mesh, param_specs=param_specs)
    step_fn, shardings = build_pipeline_train_step(
        _embed_fn, _layer_fn, _head_loss_fn, optimizer, mesh,
        num_microbatches=4, param_specs=param_specs,
    )
    tokens, _ = _data(jax.random.PRNGKey(1), 8)
    batch = {
        "tokens": jax.device_put(tokens, shardings["tokens"]),
        "targets": jax.device_put(tokens, shardings["targets"]),
    }
    with mesh:
        state, first = step_fn(state, batch)
        for _ in range(30):
            state, metrics = step_fn(state, batch)
    assert float(metrics["loss"]) < 0.5 * float(first["loss"])
