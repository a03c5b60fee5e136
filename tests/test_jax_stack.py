"""TPU compute stack tests on the virtual 8-device CPU mesh.

Covers mesh construction, flash-attention kernel (interpret mode) vs reference, ring /
ulysses attention equivalence under shard_map, and a sharded FSDP+TP train step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import mesh as mesh_lib


def test_create_mesh_shapes():
    m = mesh_lib.create_mesh({"dp": 2, "tp": 4})
    assert m.shape["dp"] == 2 and m.shape["tp"] == 4
    m2 = mesh_lib.create_mesh({"fsdp": -1})
    assert m2.shape["fsdp"] == 8


def test_logical_to_spec():
    spec = mesh_lib.logical_to_spec(("batch", "seq", "embed"))
    assert spec[0] == ("dp", "fsdp") or spec[0] in ("dp", ("dp", "fsdp"))
    # embed must not reuse axes already consumed by batch
    assert spec[2] is None or spec[2] not in ("dp",)


def test_flash_attention_matches_reference_interpret():
    from ray_tpu.ops.attention import _flash_forward, reference_attention

    B, S, H, D = 2, 256, 4, 64
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
        for i in range(3)
    )
    out, lse = _flash_forward(
        q, k, v, causal=True, scale=D**-0.5, block_q=128, block_k=128, interpret=True
    )
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_backward_matches_reference_interpret():
    """Pallas backward kernel parity, run in interpret mode on CPU.

    The dq accumulator block is revisited across the outer k-block grid axis
    (see _flash_backward), so this guards the refetch-on-revisit semantics the
    kernel relies on — a Pallas semantics change would corrupt gradients
    silently, TPU-only, without this check (round-2 advisor, medium)."""
    from ray_tpu.ops.attention import _flash_backward, _flash_forward, reference_attention

    B, S, H, D = 2, 256, 4, 64
    key = jax.random.PRNGKey(7)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
        for i in range(3)
    )
    scale = D**-0.5
    out, lse = _flash_forward(
        q, k, v, causal=True, scale=scale, block_q=128, block_k=128, interpret=True
    )
    g = jax.random.normal(jax.random.fold_in(key, 9), out.shape, jnp.float32)
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, g, causal=True, scale=scale,
        block_q=128, block_k=128, interpret=True,
    )

    def loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) * g)

    rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=5e-4, rtol=5e-4)


def test_flash_backward_gqa_reduction_interpret():
    """GQA rep>1: full-head kernel grads reduced over the repeat axis must match
    reference grads w.r.t. the un-repeated k/v (round-2 advisor, medium)."""
    from ray_tpu.ops.attention import _flash_backward, _flash_forward, reference_attention

    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    rep = H // Hkv
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D), jnp.float32)
    scale = D**-0.5
    k_full = jnp.repeat(k, rep, axis=2)
    v_full = jnp.repeat(v, rep, axis=2)
    out, lse = _flash_forward(
        q, k_full, v_full, causal=True, scale=scale, block_q=64, block_k=64,
        interpret=True,
    )
    g = jax.random.normal(jax.random.fold_in(key, 3), out.shape, jnp.float32)
    dq, dkf, dvf = _flash_backward(
        q, k_full, v_full, out, lse, g, causal=True, scale=scale,
        block_q=64, block_k=64, interpret=True,
    )
    dk = dkf.reshape(B, S, Hkv, rep, D).sum(axis=3)
    dv = dvf.reshape(B, S, Hkv, rep, D).sum(axis=3)

    def loss(q, k, v):
        kf = jnp.repeat(k, rep, axis=2)
        vf = jnp.repeat(v, rep, axis=2)
        return jnp.sum(reference_attention(q, kf, vf, causal=True) * g)

    rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=5e-4, rtol=5e-4)


def test_flash_attention_grad_path():
    from ray_tpu.ops.attention import flash_attention, reference_attention

    B, S, H, D = 1, 64, 2, 32
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
        for i in range(3)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_gqa_flash_matches_reference():
    from ray_tpu.ops.attention import flash_attention, reference_attention

    B, S, H, Hkv, D = 1, 32, 4, 2, 16
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(reference_attention(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )


def test_ring_attention_matches_full():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.ring_attention import ring_attention

    mesh = mesh_lib.create_mesh({"sp": 4})
    B, S, H, D = 2, 128, 4, 32
    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
        for i in range(3)
    )

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = ring(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_ulysses_attention_matches_full():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.ring_attention import ulysses_attention

    mesh = mesh_lib.create_mesh({"sp": 4})
    B, S, H, D = 1, 128, 4, 32
    key = jax.random.PRNGKey(4)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
        for i in range(3)
    )
    uly = shard_map(
        lambda q, k, v: ulysses_attention(
            q, k, v, "sp", attn_fn=lambda a, b, c: reference_attention(a, b, c, causal=True)
        ),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = uly(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_sharded_train_step_fsdp_tp():
    import optax

    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel.spmd import build_train_step, init_state

    cfg = get_config("test-tiny")
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"fsdp": 2, "tp": 2, "dp": 2})
    optimizer = optax.adamw(1e-3)
    state, shardings = init_state(model, cfg, optimizer, mesh, sample_shape=(2, 32))

    # embedding [vocab, embed] should be sharded over fsdp on dim 1
    emb_sharding = state.params["embedding"].sharding
    assert "fsdp" in str(emb_sharding.spec)

    step_fn, batch_shardings = build_train_step(model, optimizer, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, cfg.vocab_size)
    batch = {
        "tokens": jax.device_put(tokens, batch_shardings["tokens"]),
        "targets": jax.device_put(tokens, batch_shardings["targets"]),
    }
    with mesh:
        state2, metrics = step_fn(state, batch)
        loss1 = float(metrics["loss"])
        for _ in range(3):
            state2, metrics = step_fn(state2, batch)
    assert float(metrics["loss"]) < loss1  # loss decreases on a repeated batch
    assert int(metrics["step"]) == 4


def test_ulysses_attention_gqa_with_small_kv_heads():
    """GQA where kv-heads (2) < sp axis (4): the repeat fallback must kick in."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.ring_attention import ulysses_attention
    from jax import shard_map

    sp = 4
    mesh = mesh_lib.create_mesh({"sp": sp}, devices=jax.devices()[:sp])
    B, S, H, Hkv, D = 2, 16 * sp, 8, 2, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D), jnp.float32)
    fn = jax.jit(
        shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, "sp",
                attn_fn=lambda a, b, c: reference_attention(a, b, c, causal=True),
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
        )
    )
    out = fn(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_moe_routing_capacity_and_balance():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.moe import top_k_routing

    T, E, k, C = 64, 4, 2, 40
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    dispatch, combine, aux = top_k_routing(logits, k, C)
    assert dispatch.shape == (T, E, C)
    # each expert's slots hold at most one token each
    per_slot = np.asarray(dispatch).sum(axis=0)  # [E, C]
    assert per_slot.max() <= 1.0 + 1e-6
    # each kept token's combine weights sum to ~1
    kept = np.asarray(dispatch).sum(axis=(1, 2)) > 0
    combine_sums = np.asarray(combine).sum(axis=(1, 2))[kept]
    np.testing.assert_allclose(combine_sums, 1.0, atol=1e-5)
    assert float(aux) > 0


def test_moe_transformer_train_step_on_ep_mesh():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    cfg = get_config(
        "test-tiny", moe_experts=4, moe_top_k=2, scan_layers=True, remat=False,
    )
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 2, "ep": 4}, devices=jax.devices()[:8])
    optimizer = optax.adamw(1e-3)
    state, _ = init_state(model, cfg, optimizer, mesh, sample_shape=(4, 32))
    step_fn, shardings = build_train_step(model, optimizer, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, cfg.vocab_size)
    batch = {
        "tokens": jax.device_put(tokens, shardings["tokens"]),
        "targets": jax.device_put(tokens, shardings["targets"]),
    }
    with mesh:
        state, metrics = step_fn(state, batch)
        state, metrics2 = step_fn(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert metrics2["loss"] < metrics["loss"] + 1.0  # sane optimization step
