"""Dense latent attention of a decode step against the latent slab as it lies.

With W_kvb folded into the query and the output (`models/dots3.py:_absorbed`), a head's
key and its value at a cached position are the same latent row: the score is q . row and
the output the scores' softmax over the rows themselves. So one slab is read once, 128
heads at a time: per cached row 1152 bytes and 128 x (576 + 512) x 2 FLOP, which on a v5e
is as much time in the matrix unit as in the memory. `latent_attention` is the Pallas
kernel that reads of each slot only the row blocks up to its length; `latent_attention_xla`
is the same function as two products over every row of the slab and a mask, which every
other backend runs (the CPU tests hold the kernel, interpreted, to it).

The slab is `[slots, rows, width]` with `width` a multiple of 128 lanes (`slab_width`:
c_kv | k_r | zeros, 576 kept as 640): an array whose last axis is not whole rows of 128
lanes is padded to them by the TPU anyway, and addressed a row at a time only after a copy
of all of it into another layout (PERF.md §6, PR 35, and §7 on `dots3`'s 576-wide slab).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
LANES = 128
BLOCK_ROWS = 512  # cache rows a block of the kernel reads: 640 KB of a 640-wide bfloat16 slab


def slab_width(width: int) -> int:
    """Lanes a latent row is kept in: `width` rounded up to whole rows of 128."""
    return -(-width // LANES) * LANES


def block_rows(T: int) -> int:
    """The largest power of two up to BLOCK_ROWS that divides the slab's rows."""
    block = BLOCK_ROWS
    while block > 8 and T % block:
        block //= 2
    return block if T % block == 0 else T


def rows_read(lens, T: int):
    """Rows of each slot's slab the kernel copies in for `lens` ([B], the last visible row):
    whole blocks up to it."""
    block = block_rows(T)
    return (jnp.minimum(lens, T - 1) // block + 1) * block


def latent_attention_xla(q, slab, lens, *, scale):
    """q: [B, H, W]; slab: [B, T, W]; lens: [B], slot b sees rows 0 .. lens[b].
    softmax_t(q . slab[t] * scale) over the visible rows, times the rows: [B, H, W] in q's
    type. Scores in float32, the weights cast to q's type before the second product."""
    rows = slab.astype(q.dtype)
    s = jnp.einsum("bhw,btw->bht", q, rows, preferred_element_type=jnp.float32) * scale
    visible = jnp.arange(slab.shape[1])[None, :] <= lens[:, None]
    s = jnp.where(visible[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,btw->bhw", p, rows, preferred_element_type=jnp.float32).astype(q.dtype)


def _latent_attn_kernel(lens_ref, q_ref, slab_hbm, o_ref, buf, sems, *, scale: float, block: int, T: int):
    """Grid (slot,): online softmax over the slot's live row blocks, which the kernel copies in
    itself, two buffers deep, so that a slot costs no step for a block it does not hold
    (`ops/attention.py:_cached_attn_kernel`'s scheme, one slab in place of two)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    last = jnp.minimum(lens_ref[b], T - 1)
    live_blocks = last // block + 1

    def copy(j, slot):
        return pltpu.make_async_copy(slab_hbm.at[b, pl.ds(j * block, block)], buf.at[slot], sems.at[slot])

    copy(0, 0).start()
    q = q_ref[0]

    def one_block(j, carry):
        m_prev, l_prev, acc = carry
        slot = j % 2

        @pl.when(j + 1 < live_blocks)
        def _next():
            copy(j + 1, 1 - slot).start()

        copy(j, slot).wait()
        rows = buf[slot].astype(q.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block
        # every query sees row 0, so no row of the first block is all masked and m is finite from there on
        s = jnp.where(col <= last, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), rows, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    H = q.shape[0]
    _, l, acc = jax.lax.fori_loop(0, live_blocks, one_block, (
        jnp.full((H, 1), _NEG_INF, jnp.float32), jnp.zeros((H, 1), jnp.float32), jnp.zeros(q.shape, jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_attention(q, slab, lens, *, scale, interpret: bool = False):
    """The length-aware kernel: as `latent_attention_xla`, reading of each slot only the row
    blocks up to its last visible row (`rows_read`). The slab goes in as it lies. Jitted, so
    that a program of several layers traces the kernel and lowers it to Mosaic once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    T = slab.shape[1]
    assert slab.shape == (B, T, W) and W % LANES == 0, (q.shape, slab.shape)
    block = block_rows(T)
    kernel = functools.partial(_latent_attn_kernel, scale=float(scale), block=block, T=T)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, lens: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, W), lambda b, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, W), slab.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        interpret=interpret,
        name="latent_attn",
    )(lens.astype(jnp.int32), q, slab)
