"""Dense latent attention against the latent slab as it lies: a decode step's, and a prefill chunk's.

With W_kvb folded into the query and the output (`models/pangu_moe.py:attn_decode`), a head's
key and its value at a cached position are the same latent row: the score is q . row and
the output the scores' softmax over the rows themselves. So one slab is read once, 128
heads at a time: per cached row 1152 bytes and 128 x (576 + 512) x 2 FLOP, which on a v5e
is as much time in the matrix unit as in the memory. `latent_attention` is the Pallas
kernel that reads of each slot only the row blocks up to its length; `latent_attention_xla`
is the same function as two products over every row of the slab and a mask, which every
other backend runs (the CPU tests hold the kernel, interpreted, to it).

The slab is `[slots, rows, width]` with `width` a multiple of 128 lanes (`slab_width`: the row
`models/latent.py` computes, c_kv | k_r, then zeros: 576 kept as 640): an array whose last axis is
not whole rows of 128 lanes is padded to them by the TPU anyway, and addressed a row at a time only
after a copy of all of it into another layout (PERF.md §6, PR 35, and §7 on `dots3`'s 576-wide slab).

A prefill chunk expands keys and values from the latent rows instead, a block of keys at a time
(`latent_chunk_attention`): per block a layer 86 GFLOP of products over [heads, queries, keys]
scores, which as XLA's fusions are written and read three times in float32 (537 MB a pass at 128
heads x 1024 x 1024). The Pallas kernel `latent_chunk` runs a block's scores, softmax and second
product a tile at a time, the scores never leaving VMEM; `latent_chunk_attention_xla` is the same
loop as XLA's fusions, which every other backend and every bucket too small for a tile runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention

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


# -- a prefill chunk ---------------------------------------------------------------------


def _expand(lat_rows, j, kb: int, kv_b, dims: dict, eq: str):
    """Block j's `kb` rows of the slab [T, W] under W_kvb [kv_rank, H, nope + v]: the keys' nope
    part and the values, one array laid out as `eq` says, and the rows' rope part, which every
    head shares: [kb, rope]. Both in W_kvb's type."""
    rows = jax.lax.dynamic_slice(lat_rows, (j * kb, 0), (kb, lat_rows.shape[-1])).astype(kv_b.dtype)
    kv = jnp.einsum(eq, rows[:, :dims["kv_rank"]], kv_b, preferred_element_type=jnp.float32).astype(rows.dtype)
    return kv, rows[:, dims["kv_rank"]:dims["kv_rank"] + dims["rope"]]


def latent_chunk_attention_xla(q_full, lat_rows, kv_b, offset, kb: int, dims: dict, scale: float, mask=None):
    """q_full: [S, H, nope + rope], the chunk's queries at positions offset + [0, S); lat_rows:
    [T, W], the slot's slab with the chunk's rows written; kv_b: [kv_rank, H, nope + v]; mask: None
    for the causal mask alone, else [S, T] bool, the keys each query may see (causal already).
    Attends over rows [0, offset + S) in blocks of `kb` keys expanded from the latent rows, an
    online softmax across the blocks: scores and statistics in float32, the weights cast to q's
    type before the second product, accumulated in float32. Returns [S, H, v] in q's type."""
    S, H, _ = q_full.shape
    dt = q_full.dtype
    q_pos = offset + jnp.arange(S)[:, None]

    def attend_block(j, carry):
        m, l, acc = carry
        kv, k_rope = _expand(lat_rows, j, kb, kv_b, dims, "kc,chd->khd")
        # one product over [nope | rope]: a second one and their sum would each be a
        # pass over the block's float32 scores, which are what this loop is bound by
        keys = jnp.concatenate([kv[..., :dims["nope"]], jnp.broadcast_to(k_rope[:, None], (kb, H, dims["rope"]))], axis=-1)
        s = jnp.einsum("shd,khd->hsk", q_full, keys, preferred_element_type=jnp.float32)
        if mask is None:
            seen = (j * kb + jnp.arange(kb)[None, :] <= q_pos)[None]
        else:
            seen = jax.lax.dynamic_slice(mask, (0, j * kb), (S, kb))[None]
        s = jnp.where(seen, s * scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        pr = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "hsk,khd->hsd", pr.astype(dt), kv[..., dims["nope"]:], preferred_element_type=jnp.float32)
        return m_new, l * fade + jnp.sum(pr, axis=-1), acc

    init = (jnp.full((H, S), _NEG_INF, jnp.float32), jnp.zeros((H, S), jnp.float32),
            jnp.zeros((H, S, dims["v"]), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, (offset + S + kb - 1) // kb, attend_block, init)
    return (acc / l[..., None]).astype(dt).transpose(1, 0, 2)


def chunk_tiles(S: int, kb: int):
    """(queries, keys) of a tile of the kernel `latent_chunk` for a chunk of S queries against
    blocks of kb keys, or None where either is no whole number of 128 lanes (the 16- to 64-token
    tail buckets, the tests' small caches): those run `latent_chunk_attention_xla`."""
    if S % LANES or kb % LANES:
        return None
    return tuple(next(t for t in (1024, 512, 256, 128) if n % t == 0) for n in (S, kb))


def _latent_chunk_kernel(pos_ref, qt_ref, kn_ref, kr_ref, v_ref, *rest, scale: float, tq: int, tk: int, nope: int,
                         selected: bool):
    """Grid (head, query tile, key tile of the block): scores of the tile's keys (sublanes) by its
    queries (lanes), so that the softmax's statistics are rows and its reductions run down the
    sublanes; the carry's tile stays in VMEM across the key tiles. pos_ref: (the block's first
    key's position, the chunk's first query's)."""
    from jax.experimental import pallas as pl

    mask_ref, (m_in, l_in, acc_in, m_ref, l_ref, acc_ref) = (rest[0] if selected else None), rest[-6:]
    ki = pl.program_id(2)
    key0 = pos_ref[0] + ki * tk
    q0 = pos_ref[1] + pl.program_id(1) * tq

    @pl.when(ki == 0)
    def _take_carry():
        m_ref[...] = m_in[...]
        l_ref[...] = l_in[...]
        acc_ref[...] = acc_in[...]

    def tile(on_diagonal: bool):
        qt = qt_ref[...]
        s = jnp.dot(kn_ref[...], qt[:nope], preferred_element_type=jnp.float32)
        s = (s + jnp.dot(kr_ref[...], qt[nope:], preferred_element_type=jnp.float32)) * scale
        seen = None
        if selected:
            seen = mask_ref[...].astype(jnp.int32) != 0
        elif on_diagonal:
            seen = (key0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    <= q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        if seen is not None:
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        if selected:  # a query may have no key chosen in the blocks so far: m is still _NEG_INF there
            p = jnp.where(seen, p, 0.0)
        fade = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * fade + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * fade + jax.lax.dot_general(  # over the keys of both: [v, queries]
            v_ref[...], p.astype(v_ref.dtype), (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # a tile wholly past the tile's last query is skipped: it adds nothing and moves no maximum
    if selected:
        pl.when(key0 <= q0 + tq - 1)(lambda: tile(False))
    else:
        # every query sees key 0, so m is finite from the first tile on and a masked score's weight is exp(-1e30 - m) = 0
        pl.when(key0 + tk - 1 <= q0)(lambda: tile(False))  # wholly under the tile's first query: no mask
        pl.when((key0 + tk - 1 > q0) & (key0 <= q0 + tq - 1))(lambda: tile(True))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_chunk_block(pos, qt, kv, k_rope, mask_t, m, l, acc, *, scale: float, interpret: bool = False):
    """One block of keys into the carry, in place. qt: [H, nope + rope, S]; kv: [H, kb, nope + v]
    with v == nope, which the kernel reads as two blocks of one array; k_rope: [kb, rope]; mask_t:
    None or [kb, S] int8; m, l: [H, 1, S]; acc: [H, v, S] (queries along the lanes throughout).
    Jitted, so that a program of several layers lowers the kernel to Mosaic once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, D, S = qt.shape
    kb, nope = kv.shape[1], kv.shape[2] // 2
    tq, tk = chunk_tiles(S, kb)
    carry = [pl.BlockSpec((None, 1, tq), lambda h, qi, ki, pos: (h, 0, qi)),
             pl.BlockSpec((None, 1, tq), lambda h, qi, ki, pos: (h, 0, qi)),
             pl.BlockSpec((None, nope, tq), lambda h, qi, ki, pos: (h, 0, qi))]
    operands = [qt, kv, k_rope, kv]
    in_specs = [pl.BlockSpec((None, D, tq), lambda h, qi, ki, pos: (h, 0, qi)),
                pl.BlockSpec((None, tk, nope), lambda h, qi, ki, pos: (h, ki, 0)),
                pl.BlockSpec((tk, k_rope.shape[1]), lambda h, qi, ki, pos: (ki, 0)),
                pl.BlockSpec((None, tk, nope), lambda h, qi, ki, pos: (h, ki, 1))]
    if mask_t is not None:
        operands.append(mask_t)
        in_specs.append(pl.BlockSpec((tk, tq), lambda h, qi, ki, pos: (ki, qi)))
    first = 1 + len(operands)  # the carry's place among the operands, the scalars counted
    kernel = functools.partial(_latent_chunk_kernel, scale=float(scale), tq=tq, tk=tk, nope=nope,
                               selected=mask_t is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, S // tq, kb // tk), in_specs=in_specs + carry, out_specs=carry),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (m, l, acc)],
        input_output_aliases={first: 0, first + 1: 1, first + 2: 2},
        # a 1024 x 1024 tile's scores, weights and mask are some 15 MB beside the buffered operands: over the default 16 MB
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_chunk",
    )(pos, *operands, m, l, acc)


def latent_chunk_attention(q_full, lat_rows, kv_b, offset, kb: int, dims: dict, scale: float, mask=None,
                           interpret: bool = False):
    """`latent_chunk_attention_xla`'s function with a block's scores, softmax and second product
    in the kernel `latent_chunk`, where the backend is a TPU (or `interpret`), the chunk and the
    block are whole tiles (`chunk_tiles`) and a head's values are as wide as its keys' nope part
    (the kernel reads both out of one expansion); else that function itself. The loop slices the
    block's rows out of the slab and expands them in XLA, so the kernel never takes a slab."""
    S, H, _ = q_full.shape
    if chunk_tiles(S, kb) is None or dims["nope"] != dims["v"] or not (interpret or attention._use_pallas()):
        return latent_chunk_attention_xla(q_full, lat_rows, kv_b, offset, kb, dims, scale, mask)
    dt = q_full.dtype
    qt = q_full.transpose(1, 2, 0)
    offset = jnp.asarray(offset, jnp.int32)

    def attend_block(j, carry):
        kv, k_rope = _expand(lat_rows, j, kb, kv_b, dims, "kc,chd->hkd")
        mask_t = None if mask is None else jax.lax.dynamic_slice(mask, (0, j * kb), (S, kb)).T.astype(jnp.int8)
        pos = jnp.stack([j * kb, offset]).astype(jnp.int32)
        return tuple(_latent_chunk_block(pos, qt, kv, k_rope, mask_t, *carry, scale=scale, interpret=interpret))

    init = (jnp.full((H, 1, S), _NEG_INF, jnp.float32), jnp.zeros((H, 1, S), jnp.float32),
            jnp.zeros((H, dims["v"], S), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, (offset + S + kb - 1) // kb, attend_block, init)
    return (acc / l).astype(dt).transpose(2, 0, 1)
