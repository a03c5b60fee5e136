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


@pytest.mark.parametrize("S, T, D, block_q, block_k, causal", [
    (256, 256, 64, 128, 128, True),  # equal blocks: a crossing strip a step and nothing else to skip
    # a key block wider than the query block: in one head steps the diagonal hides (their
    # index map repeats a block), unmasked strips in pairs and alone, and a crossing block
    # whose last strips are dead
    (2048, 2048, 64, 256, 1024, True),
    (2048, 2048, 128, 512, 2048, True),  # a head's whole K and V as one block, as the chip runs it
    (512, 512, 32, 256, 128, True),  # block_k < block_q: two crossing blocks a query block
    (384, 384, 64, 128, 256, True),  # S no multiple of the key block: the last one hangs over T
    (256, 512, 64, 128, 256, True),  # T != S (a ring shard's): keys past the last query are never read
    (512, 256, 64, 128, 256, True),  # and queries past the last key see all of T
    (512, 512, 64, 128, 256, False),  # no mask: the unmasked body everywhere
    (256, 384, 64, 128, 256, False),  # but for the strip T ends in
])
def test_flash_attention_matches_reference_interpret(S, T, D, block_q, block_k, causal):
    from ray_tpu.ops.attention import _attention_with_lse, _flash_forward

    B, H = (2, 4) if S * T <= 256 * 256 else (1, 2)
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, T if i else S, H, D), jnp.float32)
        for i in range(3)
    )
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=D**-0.5, block_q=block_q, block_k=block_k, interpret=True
    )
    ref, ref_lse = _attention_with_lse(q, k, v, causal=causal, scale=D**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


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


def _kernels_in(program, *args) -> set:
    """The Pallas kernels a program would dispatch on a TPU, by name, read off its jaxpr: traced
    here with `_use_pallas` true, nothing lowered."""
    import re

    text = str(jax.make_jaxpr(program)(*args))
    kernels = set(re.findall(r"name=(flash_fwd|flash_bwd)\b", text))
    assert ("pallas_call" in text) == bool(kernels)
    return kernels


def _qkv(layout: str, S: int, T: int, H=4, Hkv=2, D=64):
    """bfloat16 q of S rows and H heads, k and v of T rows and Hkv heads, in `layout`."""
    def one(i, rows, heads):
        shape = (1, heads, rows, D) if layout == "bhsd" else (1, rows, heads, D)
        return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), i), shape, jnp.bfloat16)

    return one(0, S, H), one(1, T, Hkv), one(2, T, Hkv)


@pytest.mark.parametrize("case, layout, S, T, kernels", [
    ("init", "bhsd", 8, 8, set()),  # the dense block's tree: the flax forward at 8 tokens, layer by layer
    ("jaxpr", "bhsd", 8, 8, set()),
    ("jaxpr", "bhsd", 64, 64, set()),
    ("jaxpr", "bshd", 64, 64, set()),
    ("jaxpr", "bhsd", 256, 64, set()),  # a shard's keys under 128 rows
    ("jaxpr", "bhsd", 127, 127, set()),
    ("jaxpr", "bhsd", 128, 128, {"flash_fwd", "flash_bwd"}),
    ("jaxpr", "bshd", 128, 256, {"flash_fwd", "flash_bwd"}),
    ("jaxpr", "bhsd", 4096, 4096, {"flash_fwd", "flash_bwd"}),  # the train cells' call
    ("agrees", "bhsd", 8, 8, set()),
    ("agrees", "bshd", 8, 8, set()),
])
def test_a_call_under_128_rows_is_not_the_flash_kernels(case, layout, S, T, kernels, monkeypatch):
    """On a TPU (`_use_pallas` true) `flash_takes` hands a call whose S or T is under 128 rows to
    `_attention_with_lse`, forward and backward, by the shapes alone: `llama.init_params` then
    dispatches no Mosaic kernel (PERF.md §6, PR 49), and both layouts give what the plain body gives."""
    import dataclasses

    from ray_tpu.llm import LLMConfig, engine_config
    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    assert attention.flash_takes(S, T) == bool(kernels)
    if case == "init":
        cfg = engine_config(LLMConfig(model_id="tiny", model_config=dataclasses.replace(get_config("test-tiny"), attention="flash")))
        assert cfg.attention == "flash" and not cfg.scan_layers and not cfg.remat
        assert _kernels_in(lambda key: Transformer(cfg).init(key, jnp.zeros((1, S), jnp.int32)), jax.random.PRNGKey(0)) == kernels
        return
    entry = attention.flash_attention_bhsd if layout == "bhsd" else attention.flash_attention
    loss = lambda q, k, v: jnp.sum(entry(q, k, v).astype(jnp.float32) ** 2)  # noqa: E731
    q, k, v = _qkv(layout, S, T)
    if case == "agrees":
        # evaluated on the CPU with the switch on: a `pallas_call` would not even lower here
        to_bshd = (lambda x: jnp.transpose(x, (0, 2, 1, 3))) if layout == "bhsd" else (lambda x: x)
        ref = lambda q, k, v: attention._attention_with_lse(  # noqa: E731
            to_bshd(q), *(jnp.repeat(to_bshd(x), 2, axis=2) for x in (k, v)), causal=True, scale=None)[0]
        np.testing.assert_array_equal(np.asarray(to_bshd(entry(q, k, v)), np.float32), np.asarray(ref(q, k, v), np.float32))
        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=0.1, rtol=0.05)
        return
    assert _kernels_in(entry, q, k, v) == kernels - {"flash_bwd"}
    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), q, k, v) == kernels


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
