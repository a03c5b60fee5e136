"""A prefill chunk's latent attention (`ops/latent_attention.py:latent_chunk_attention`): the
Pallas kernel `latent_chunk`, interpreted on the CPU, against the same loop as XLA's products
(`latent_chunk_attention_xla`), in float32, over heads, query tiles, key blocks, the place of the
diagonal, a selection mask and a score scale; rows past the chunk's last are never read; the
carry goes through a block's call in place; a bucket too small for a tile takes the XLA body."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la

DIMS = dict(kv_rank=32, nope=16, rope=8, v=16)
T, W = 512, 128  # a slab's rows and its width: c_kv | k_r | zeros, as `pangu_moe` keeps it
ATOL = 3e-6      # float32 both ways; the tiles only add in another order (outputs are of order 0.1 to 1)


def _inputs(H, S, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (S, H, DIMS["nope"] + DIMS["rope"]))
    rows = jax.random.normal(keys[1], (T, DIMS["kv_rank"] + DIMS["rope"]))
    slab = jnp.pad(rows, ((0, 0), (0, W - rows.shape[1])))
    kv_b = jax.random.normal(keys[2], (DIMS["kv_rank"], H, DIMS["nope"] + DIMS["v"])) / math.sqrt(DIMS["kv_rank"])
    return q, slab, kv_b


def _selection(S, offset, seed=0, share=0.03):
    """[S, T] bool, causal already: a few keys a query, its own among them, so that most queries
    have none in some block (the carry's maximum is still -1e30 there)."""
    chosen = jax.random.uniform(jax.random.PRNGKey(100 + seed), (S, T)) < share
    q_pos = offset + jnp.arange(S)[:, None]
    return (chosen | (jnp.arange(T)[None, :] == q_pos)) & (jnp.arange(T)[None, :] <= q_pos)


SHAPES = {
    # heads, queries, first position, keys a block
    "32-heads-one-tile-one-block": (32, 128, 0, 128),
    "32-heads-diagonal-inside-the-second-and-third-of-three-blocks": (32, 128, 200, 128),
    "32-heads-three-query-tiles-four-blocks": (32, 384, 70, 128),
    "128-heads-diagonal-inside-a-block-of-three": (128, 128, 200, 128),
    "128-heads-a-tile-of-256-by-256": (128, 256, 130, 256),
}


@pytest.mark.parametrize("score_scale", [1.0, 1.7], ids=["plain-scale", "score-scale"])
@pytest.mark.parametrize("selected", [False, True], ids=["causal", "selection"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_interpreted_is_the_loop_of_products(shape, selected, score_scale):
    H, S, offset, kb = SHAPES[shape]
    q, slab, kv_b = _inputs(H, S)
    mask = _selection(S, offset) if selected else None
    scale = score_scale / math.sqrt(DIMS["nope"] + DIMS["rope"])
    assert la.chunk_tiles(S, kb) is not None
    want = la.latent_chunk_attention_xla(q, slab, kv_b, jnp.int32(offset), kb, DIMS, scale, mask)
    got = la.latent_chunk_attention(q, slab, kv_b, jnp.int32(offset), kb, DIMS, scale, mask, interpret=True)
    assert got.shape == (S, H, DIMS["v"]) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    assert np.abs(np.asarray(want)).max() > 0.1  # not a comparison of zeros


@pytest.mark.parametrize("selected", [False, True], ids=["causal", "selection"])
def test_rows_past_the_chunks_last_are_never_read(selected):
    """The slab holds what a longer request left past the chunk: with other numbers there in the
    chunk's last block (read, and weighted 0) and NaN in the blocks after it (never read), the
    output is the same bit for bit."""
    H, S, offset, kb = 32, 128, 200, 128
    q, slab, kv_b = _inputs(H, S, seed=1)
    mask = _selection(S, offset, seed=1) if selected else None
    got = la.latent_chunk_attention(q, slab, kv_b, jnp.int32(offset), kb, DIMS, 0.2, mask, interpret=True)
    at = jnp.arange(T)[:, None]
    poisoned = jnp.where(at < offset + S, slab, jnp.where(at < -(-(offset + S) // kb) * kb, 1e3 * slab[::-1], jnp.nan))
    again = la.latent_chunk_attention(q, poisoned, kv_b, jnp.int32(offset), kb, DIMS, 0.2, mask, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_a_blocks_call_takes_the_carry_and_returns_it_in_place():
    """Two calls of the block's kernel, the second on the first's carry, are two blocks of the
    loop; and the call aliases the carry's three arrays to its three results, so that on the chip
    67 MB of accumulator are written where they were read."""
    H, S, offset, kb = 32, 128, 128, 128
    q, slab, kv_b = _inputs(H, S, seed=2)
    want = la.latent_chunk_attention_xla(q, slab, kv_b, jnp.int32(offset), kb, DIMS, 0.2)
    carry = (jnp.full((H, 1, S), -1e30, jnp.float32), jnp.zeros((H, 1, S), jnp.float32), jnp.zeros((H, DIMS["v"], S), jnp.float32))
    qt = q.transpose(1, 2, 0)
    for j in range(2):
        kv, k_rope = la._expand(slab, j, kb, kv_b, DIMS, "kc,chd->hkd")
        args = (jnp.asarray([j * kb, offset], jnp.int32), qt, kv, k_rope, None) + tuple(carry)
        carry = la._latent_chunk_block(*args, scale=0.2, interpret=True)
    np.testing.assert_allclose(np.asarray((carry[2] / carry[1]).transpose(2, 0, 1)), np.asarray(want), atol=ATOL)
    (call,) = [e for e in jax.make_jaxpr(lambda *a: la._latent_chunk_block.__wrapped__(*a, scale=0.2))(*args).eqns
               if e.primitive.name == "pallas_call"]
    assert dict(call.params["input_output_aliases"]) == {5: 0, 6: 1, 7: 2}
    assert [v.aval.shape for v in call.outvars] == [c.shape for c in carry]


@pytest.mark.parametrize("S,kb", [(16, 128), (64, 128), (128, 16), (32, 64)])
def test_a_bucket_or_a_block_too_small_for_a_tile_takes_the_products_by_its_shape(S, kb, monkeypatch):
    """The 16- to 64-token tail buckets, and the small caches of the blocks' own tests: no tile,
    so the function is the XLA body whatever the backend (here: asked to interpret)."""
    assert la.chunk_tiles(S, kb) is None
    monkeypatch.setattr(la, "_latent_chunk_block", None)  # would fail if called
    q, slab, kv_b = _inputs(4, S, seed=3)
    want = la.latent_chunk_attention_xla(q, slab, kv_b, jnp.int32(40), kb, DIMS, 0.2)
    got = la.latent_chunk_attention(q, slab, kv_b, jnp.int32(40), kb, DIMS, 0.2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiles_follow_the_shapes_and_off_the_tpu_the_function_is_the_products():
    assert la.chunk_tiles(1024, 1024) == (1024, 1024) and la.chunk_tiles(2048, 512) == (1024, 512)
    assert la.chunk_tiles(256, 1024) == (256, 1024) and la.chunk_tiles(128, 128) == (128, 128)
    assert la.chunk_tiles(384, 128) == (128, 128)
    q, slab, kv_b = _inputs(4, 128, seed=4)
    assert jax.default_backend() == "cpu"
    text = jax.jit(lambda *a: la.latent_chunk_attention(*a, jnp.int32(0), 128, DIMS, 0.2)).lower(q, slab, kv_b).as_text()
    assert "latent_chunk" not in text and "custom_call" not in text
