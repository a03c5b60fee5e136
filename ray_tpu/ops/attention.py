"""Attention ops: Pallas flash-attention TPU kernel + reference JAX path.

This is where the reference framework leans on CUDA (vLLM/torch SDPA under Ray's LLM and
Train libraries); the TPU rebuild owns the kernel. Forward is an online-softmax flash
kernel tiled for the MXU (q blocked over the grid, a head's k/v walked in strips up to the
diagonal); backward is a custom VJP over a one-pass Pallas kernel. On non-TPU backends, and
for a call under 128 rows on any (`flash_takes`), the reference JAX implementation runs
instead, so the same model code tests on the virtual CPU mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _use_pallas() -> bool:
    # No except: a backend that fails to start must fail the caller, not hand it
    # the reference path in silence.
    return jax.default_backend() == "tpu"


def reference_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                        positions_q=None, positions_kv=None):
    """Plain XLA attention. q:[B,S,H,D] k/v:[B,T,Hkv,D] -> [B,S,H,D]."""
    out, _ = _attention_with_lse(q, k, v, causal=causal, scale=scale,
                                 positions_q=positions_q, positions_kv=positions_kv)
    return out


def _attention_with_lse(q, k, v, *, causal, scale, positions_q=None, positions_kv=None):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        pos_q = positions_q if positions_q is not None else jnp.arange(S)
        pos_k = positions_kv if positions_kv is not None else jnp.arange(T)
        mask = pos_q[:, None] >= pos_k[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)  # [B,H,S]
    probs = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out, lse


# ------------------------------------------------------------------ pallas kernel

def _lanes(x, n: int):
    """A statistic kept the same in every lane, [rows, L], as [rows, n]: whole copies side by
    side, cut to n."""
    return jnp.tile(x, (1, -(-n // x.shape[1])))[:, :n]


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, causal: bool, T: int, strip: int):
    """Grid (BH, nq, nk), nk innermost+sequential: online softmax state lives in VMEM
    scratch across k-steps. A step does work only where the mask leaves any: it goes
    through its key block in strips of `strip` columns, first those every row of the query
    block sees (`full` columns), with no iota, compare or select; then under the mask those
    only some rows see (up to `live`: the strip the diagonal crosses, and the one T ends in
    where the last key block hangs over it); the rest in no strip at all. A key block
    wholly above the diagonal has `live` 0 and costs its grid step alone: its index map
    repeats the last visible block's, so nothing is copied for it (`_flash_forward`).

    Refs are the raw (1, x, y) blocks. Scratch: acc [BQ, D] f32; m and l [BQ, L] f32,
    L = 128 lanes on the chip: m the same in every lane, so that a strip's scores meet it
    with no broadcast, l a partial sum a lane (column c's weight in lane c mod L), summed
    over the lanes once, at the end.
    """
    from jax.experimental import pallas as pl

    block_q, D = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    L = m_ref.shape[1]
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # the last column every row of the query block sees, and the last any row sees
    if causal:
        sees_all, sees_any = jnp.minimum(q_start, T - 1), jnp.minimum(q_start + block_q - 1, T - 1)
    else:
        sees_all = sees_any = T - 1
    full = jnp.clip(sees_all + 1 - k_start, 0, block_k)
    live = jnp.clip(sees_any + 1 - k_start, 0, block_k)

    def one_strip(masked: bool):
        def body(c, carry):
            at = pl.multiple_of(c * strip, strip)
            # Inputs stay in their native (bf16) dtype: the MXU takes them directly and
            # accumulates in f32; the scale is folded into the f32 scores.
            k_blk = k_ref[0, pl.ds(at, strip), :]
            v_blk = v_ref[0, pl.ds(at, strip), :]
            s = jax.lax.dot_general(
                q_ref[0], k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [BQ, strip] f32
            if masked:
                cols = k_start + at + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                seen = cols < T
                if causal:
                    seen &= cols <= q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, strip))
            alpha = jnp.exp(m_prev - m_new)
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * alpha + sum(p[:, lane:lane + L] for lane in range(0, strip, L))
            acc_ref[:] = acc_ref[:] * _lanes(alpha, D) + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return carry
        return body

    # Two unmasked strips an iteration: the second's scores come off the MXU while the first's
    # weights are still being computed (5% of the kernel at 512 rows: PERF.md §6, PR 48).
    unmasked, n_full = one_strip(masked=False), full // strip
    jax.lax.fori_loop(0, n_full // 2, lambda c, carry: unmasked(2 * c + 1, unmasked(2 * c, carry)), None)
    pl.when(n_full % 2 == 1)(lambda: unmasked(n_full - 1, None))
    jax.lax.fori_loop(n_full, (live + strip - 1) // strip, one_strip(masked=True), None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_ref[:], axis=-1, keepdims=True), 1e-30)
        o_ref[:] = (acc_ref[:] / l)[None].astype(o_ref.dtype)
        lse_ref[:] = (m_ref[:, :1] + jnp.log(l))[None]


def _flash_blocks(S: int, T: int, D: int, dtype) -> tuple[int, int]:
    """(block_q, block_k) of the forward kernel for q [.., S, D] against k, v [.., T, D]: 512
    query rows a grid step where they divide S (256 rows run a quarter slower, 1024 leave
    more of the diagonal's strip masked: the chip's sweep at S = T = 1024 to 8192, D = 64
    and 128, PERF.md §6, PR 48), and a head's whole K and V as one block, copied once a head
    and walked in strips inside the step, while both, two buffers each, fit 8 MB of VMEM
    (8192 rows of bfloat16 at D <= 128); past that the largest power of two that does and
    divides T. A size no such block divides gets one block that covers it."""
    block_q = next((b for b in (512, 256, 128) if S % b == 0), S)
    fits = (8 << 20) // (4 * max(D, 128) * jnp.dtype(dtype).itemsize)
    if T <= fits:
        return block_q, T
    return block_q, next((b for b in (8192, 4096, 2048, 1024, 512, 256, 128) if b <= fits and T % b == 0), T)


def flash_takes(S: int, T: int) -> bool:
    """Whether a call of S query rows against T keys is the kernels', forward and backward: 128
    rows of each and more. Under that a head's one block of scores is less work than a kernel's
    start, and the call is a flax `init` at a few tokens or a short ring shard, dispatched eagerly,
    where every dispatch traces and lowers the kernel's body afresh (48 a dense serve set-up:
    PERF.md §6, PR 49): it runs `_attention_with_lse`, the body every other backend runs."""
    return min(S, T) >= 128


def _flash_forward(q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int,
                   interpret: bool, layout: str = "bshd"):
    """q:[B,S,H,D] k/v:[B,T,H,D] (kv heads already expanded) -> (out, lse [B,H,S]).
    Causal, query i sees keys j <= i, whatever S and T.

    layout="bhsd": operands arrive [B,H,S,D] (the kernel's native layout) and
    the output returns [B,H,S,D] — no transposes touch HBM. The model's train
    path produces this layout straight out of its projection einsums."""
    from jax.experimental import pallas as pl

    from jax.experimental.pallas import tpu as pltpu

    if layout == "bhsd":
        B, H, S, D = q.shape
        T = k.shape[2]
        qt = q.reshape(B * H, S, D)
        kt = k.reshape(B * H, T, D)
        vt = v.reshape(B * H, T, D)
    else:
        B, S, H, D = q.shape
        T = k.shape[1]
        # Flatten (batch, head) into the leading grid dim; blocks squeeze it away.
        qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
        kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, T, D)
        vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, T, D)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    grid = (B * H, pl.cdiv(S, block_q), pl.cdiv(T, block_k))  # nk innermost
    # a strip is as wide as the diagonal's step through a key block, so that one strip a
    # query block crosses it
    strip = math.gcd(block_q, block_k)
    lanes = math.gcd(strip, 128)

    def kv_block(bh, i, j):
        if causal:  # a block the diagonal hides repeats the last visible one: no copy
            j = jnp.minimum(j, jnp.minimum(i * block_q + block_q - 1, T - 1) // block_k)
        return bh, j, 0

    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal, T=T, strip=strip)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_block),
            pl.BlockSpec((1, block_k, D), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            # lse as [BH, S, 1], a column the chip keeps in tiles of 128 lanes (134 MB at
            # [64, 4096]): the form the backward kernel reads it in, so that in a train step
            # it goes from kernel to kernel as it lies; as a row of lanes, [BH, 1, S], the
            # step pays two relayouts a layer more (PERF.md §6, PR 48)
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    if layout == "bhsd":
        return out.reshape(B, H, S, D), lse.reshape(B, H, S)
    out = jnp.transpose(out.reshape(B, H, S, D), (0, 2, 1, 3))
    return out, lse.reshape(B, H, S)


def _flash_bwd_fused_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            scale: float, causal: bool):
    """Single-pass flash backward. Grid (BH, nk, nq), nq innermost.

    For a fixed k/v block, stream q blocks: recompute p once and produce ALL
    THREE gradients from it — dk/dv accumulate in VMEM scratch (emitted at the
    last q step), dq accumulates in its HBM-backed output block, which Pallas
    refetches on each revisit (j outer); a [BQ,D] f32 block per visit is noise
    next to recomputing s/p/dp/ds in a second pass."""
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    j = pl.program_id(1)
    i = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_dq():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    q_start = i * block_q
    k_start = j * block_k
    visible = (q_start + block_q - 1 >= k_start) if causal else (i >= 0)

    @pl.when(visible)
    def _compute():
        q = q_ref[:][0]
        g = g_ref[:][0]
        k_blk = k_ref[:][0]
        v_blk = v_ref[:][0]
        lse = lse_ref[:][0]  # [BQ, 1] f32
        delta = delta_ref[:][0]  # [BQ, 1] f32
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [BQ, BK] f32 (rows with -inf lse rows exp to 0)
        pb = p.astype(k_blk.dtype)
        # dv += p^T g   ([BK,BQ]@[BQ,D])
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            pb, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dp = g v^T    ([BQ,D]@[D,BK])
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [BQ, BK]
        # dk += ds^T q  ([BK,BQ]@[BQ,D])
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dq += ds k    ([BQ,BK]@[BK,D]) — accumulated in the f32 output block
        dq_ref[:] = dq_ref[:] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )[None]

    @pl.when(i == num_q - 1)
    def _emit():
        dk_ref[:] = dk_acc[:][None].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:][None].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    layout: str = "bshd"):
    """Pallas flash backward: no [S,T] tensor ever touches HBM, one pass.

    q/g:[B,S,H,D], k/v:[B,T,H,D] (kv already expanded), lse:[B,H,S] f32.
    layout="bhsd": q/g/k/v/out arrive (and dq/dk/dv return) as [B,H,*,D] —
    zero transposes. Returns (dq, dk, dv) in the inputs' dtypes.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layout == "bhsd":
        B, H, S, D = q.shape
        T = k.shape[2]
        qt = q.reshape(B * H, S, D)
        kt = k.reshape(B * H, T, D)
        vt = v.reshape(B * H, T, D)
        gt = g.reshape(B * H, S, D).astype(q.dtype)
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # [B,H,S]
        deltat = delta.reshape(B * H, S, 1)
    else:
        B, S, H, D = q.shape
        T = k.shape[1]
        qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
        kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, T, D)
        vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, T, D)
        gt = jnp.transpose(g, (0, 2, 1, 3)).reshape(B * H, S, D).astype(q.dtype)
        # delta = sum(g * out, -1): cheap rowwise reduction, precomputed in XLA.
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # [B,S,H]
        deltat = jnp.transpose(delta, (0, 2, 1)).reshape(B * H, S, 1)
    lset = lse.reshape(B * H, S, 1)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(T, block_k)

    kernel = functools.partial(_flash_bwd_fused_kernel, scale=scale, causal=causal)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, j, i: (bh, i, 0)),  # q
            pl.BlockSpec((1, block_q, D), lambda bh, j, i: (bh, i, 0)),  # g
            pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0)),  # lse
            pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0)),  # delta
            pl.BlockSpec((1, block_k, D), lambda bh, j, i: (bh, j, 0)),  # k
            pl.BlockSpec((1, block_k, D), lambda bh, j, i: (bh, j, 0)),  # v
        ],
        out_specs=[
            # dq revisited across j (outer grid dim): accumulated f32 in HBM.
            pl.BlockSpec((1, block_q, D), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd",
    )(qt, gt, lset, deltat, kt, vt)

    if layout == "bhsd":
        return (dq.reshape(B, H, S, D).astype(q.dtype),
                dk.reshape(B, H, T, D), dv.reshape(B, H, T, D))
    dq = jnp.transpose(dq.reshape(B, H, S, D), (0, 2, 1, 3)).astype(q.dtype)
    dk = jnp.transpose(dk.reshape(B, H, T, D), (0, 2, 1, 3))
    dv = jnp.transpose(dv.reshape(B, H, T, D), (0, 2, 1, 3))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None):
    """Flash attention. q:[B,S,H,D], k/v:[B,T,Hkv,D] (GQA: Hkv divides H)."""
    out, _ = _flash_attention_fwd_impl(q, k, v, causal, scale)
    return out


def _flash_attention_fwd_impl(q, k, v, causal, scale):
    D = q.shape[-1]
    eff_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    H, Hkv = q.shape[2], k.shape[2]
    k_full, v_full = k, v
    if Hkv != H:
        rep = H // Hkv
        k_full = jnp.repeat(k, rep, axis=2)
        v_full = jnp.repeat(v, rep, axis=2)
    if _use_pallas() and flash_takes(q.shape[1], k.shape[1]):
        block_q, block_k = _flash_blocks(q.shape[1], k.shape[1], D, q.dtype)
        out, lse = _flash_forward(
            q, k_full, v_full, causal=causal, scale=eff_scale,
            block_q=block_q, block_k=block_k, interpret=False,
        )
    else:
        out, lse = _attention_with_lse(q, k_full, v_full, causal=causal, scale=eff_scale)
    return out, lse


def _flash_fwd_rule(q, k, v, causal, scale):
    out, lse = _flash_attention_fwd_impl(q, k, v, causal, scale)
    # Under a named-save remat policy ("selective"), the residuals the flash
    # backward needs must be nameable or the whole forward kernel re-runs in
    # the backward pass; checkpoint_name is an identity otherwise.
    from jax.ad_checkpoint import checkpoint_name

    return out, (q, k, v, checkpoint_name(out, "flash_residuals"),
                 checkpoint_name(lse, "flash_residuals"))


def _flash_bwd_rule(causal, scale, residuals, g):
    """Flash backward: the one-pass Pallas kernel on TPU for the calls the kernels take
    (`flash_takes`; p recomputed blockwise, no [S,T] tensor reaches HBM); recompute-based
    XLA einsums elsewhere."""
    q, k, v, out, lse = residuals
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    eff_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    rep = H // Hkv
    k_full = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    v_full = jnp.repeat(v, rep, axis=2) if rep > 1 else v

    if _use_pallas() and flash_takes(S, T):
        dq, dk, dv = _flash_backward(
            q, k_full, v_full, out, lse, g, causal=causal, scale=eff_scale,
            block_q=512, block_k=1024,
            interpret=False,
        )
        if rep > 1:
            dk = dk.reshape(B, T, Hkv, rep, D).sum(axis=3).astype(k.dtype)
            dv = dv.reshape(B, T, Hkv, rep, D).sum(axis=3).astype(v.dtype)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    return _xla_flash_bwd(q, k_full, v_full, out, lse, g, causal, eff_scale,
                          rep, Hkv, k.dtype, v.dtype)


def _xla_flash_bwd(q, k_full, v_full, out, lse, g, causal, eff_scale, rep,
                   Hkv, k_dtype, v_dtype):
    """Recompute-based XLA flash backward in bshd layout — the SINGLE
    implementation behind both layout entry points (the bhsd rule transposes
    into here on its non-pallas path; those transposes only run on CPU/test
    backends where they're free of consequence).

    The big einsums run in the inputs' compute dtype with f32 accumulation
    (an f32 matmul costs ~8x MXU throughput on v5e) and the [B,H,S,T]
    intermediates are held in that dtype, halving the dominant HBM traffic of
    this backward for bf16 models. Softmax math (exp, lse subtraction, ds
    recentering) stays f32. Full-precision inputs keep f32 end to end."""
    B, S, _H, D = q.shape
    T = k_full.shape[1]
    bf = q.dtype if q.dtype in (jnp.bfloat16, jnp.float16) else jnp.float32
    logits = jnp.einsum(
        "bshd,bthd->bhst", q.astype(bf), k_full.astype(bf),
        preferred_element_type=jnp.float32,
    ) * eff_scale
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jnp.exp(logits - lse[..., None]).astype(bf)  # [B,H,S,T]

    gb = g.astype(bf)
    dv = jnp.einsum("bhst,bshd->bthd", p, gb, preferred_element_type=jnp.float32)
    dp = jnp.einsum("bshd,bthd->bhst", gb, v_full.astype(bf),
                    preferred_element_type=jnp.float32)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,S,H]
    ds = (p.astype(jnp.float32)
          * (dp - jnp.transpose(delta, (0, 2, 1))[..., None]) * eff_scale).astype(bf)
    dq = jnp.einsum("bhst,bthd->bshd", ds, k_full.astype(bf),
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhst,bshd->bthd", ds, q.astype(bf),
                    preferred_element_type=jnp.float32)
    if rep > 1:
        dk = dk.reshape(B, T, Hkv, rep, D).sum(axis=3)
        dv = dv.reshape(B, T, Hkv, rep, D).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k_dtype), dv.astype(v_dtype)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------------- bhsd (transpose-free)

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_bhsd(q, k, v, causal: bool = True, scale: float | None = None):
    """Flash attention in the kernel's NATIVE layout: q:[B,H,S,D],
    k/v:[B,Hkv,T,D] -> [B,H,S,D].

    The bshd entry point pays 4 HBM transposes in forward and 7 in backward
    per call (measured ~1/3 of the in-graph attention cost at the flagship
    shape); a model whose projections emit [B,H,S,D] directly (einsum
    'bse,ehd->bhsd' — the transpose folds into the matmul) skips all of them.
    """
    out, _ = _flash_bhsd_fwd_impl(q, k, v, causal, scale)
    return out


def _expand_kv_bhsd(k, v, H):
    Hkv = k.shape[1]
    if Hkv == H:
        return k, v
    rep = H // Hkv
    return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


def _flash_bhsd_fwd_impl(q, k, v, causal, scale):
    D = q.shape[-1]
    eff_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k_full, v_full = _expand_kv_bhsd(k, v, q.shape[1])
    if _use_pallas() and flash_takes(q.shape[2], k.shape[2]):
        block_q, block_k = _flash_blocks(q.shape[2], k.shape[2], D, q.dtype)
        return _flash_forward(
            q, k_full, v_full, causal=causal, scale=eff_scale,
            block_q=block_q, block_k=block_k, interpret=False, layout="bhsd",
        )
    out, lse = _attention_with_lse(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(k_full, (0, 2, 1, 3)),
        jnp.transpose(v_full, (0, 2, 1, 3)), causal=causal, scale=eff_scale,
    )
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _flash_bhsd_fwd_rule(q, k, v, causal, scale):
    out, lse = _flash_bhsd_fwd_impl(q, k, v, causal, scale)
    from jax.ad_checkpoint import checkpoint_name

    return out, (q, k, v, checkpoint_name(out, "flash_residuals"),
                 checkpoint_name(lse, "flash_residuals"))


def _flash_bhsd_bwd_rule(causal, scale, residuals, g):
    q, k, v, out, lse = residuals
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    eff_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    rep = H // Hkv
    k_full, v_full = _expand_kv_bhsd(k, v, H)

    if _use_pallas() and flash_takes(S, T):
        dq, dk, dv = _flash_backward(
            q, k_full, v_full, out, lse, g, causal=causal, scale=eff_scale,
            block_q=512, block_k=1024,
            interpret=False, layout="bhsd",
        )
        if rep > 1:
            dk = dk.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(k.dtype)
            dv = dv.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(v.dtype)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    # XLA fallback (CPU tests, a call under 128 rows): normalize into the shared bshd backward
    # — the extra transposes only exist on backends where they cost nothing,
    # and the numerically sensitive math stays in ONE place.
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    dq, dk, dv = _xla_flash_bwd(
        tr(q), tr(k_full), tr(v_full), tr(out), lse, tr(g), causal, eff_scale,
        rep, Hkv, k.dtype, v.dtype,
    )
    return tr(dq), tr(dk), tr(dv)


flash_attention_bhsd.defvjp(_flash_bhsd_fwd_rule, _flash_bhsd_bwd_rule)


# ------------------------------------------------ cached attention (the serve path)
#
# One query block a slot against the slot's rows of the KV slab, as the engine holds it:
# `[B, T, *minor]`, where `minor` is `(Hkv, D)` or any row-major regrouping of it whose
# last axis holds whole heads (`(Hkv * D // 128, 128)` for heads of 64: the blocks with
# narrow heads keep their slabs so, because a TPU array whose last axis is under 128
# lanes is not stored row-major). Query i of slot b sees rows j <= lens[b] + i.


def cached_attention_xla(q, cache_k, cache_v, lens, *, scale):
    """The plain form: two products over every row of the slab and a mask. q: [B, S, Hkv,
    G, D]; cache_k/v: [B, T, *minor]; lens: [B] -> [B, S, Hkv, G, D] in q's type. Scores in
    float32, the weights cast to q's type before the values' product."""
    B, S, Hkv, _, D = q.shape
    T = cache_k.shape[1]
    k = cache_k.reshape(B, T, Hkv, D).astype(q.dtype)
    v = cache_v.reshape(B, T, Hkv, D).astype(q.dtype)
    # Grouped queries: head h reads KV head h // G, so both products run against the slab
    # as it lies: a copy of K or V repeated to H heads costs a third of a decode step
    # (PERF.md §6, PR 29).
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k) * scale
    visible = jnp.arange(T)[None, None, :] <= lens[:, None, None] + jnp.arange(S)[None, :, None]
    logits = jnp.where(visible[:, None, None], logits.astype(jnp.float32), _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def cached_attention_takes(lanes: int) -> bool:
    """Whether the kernel can read a slab whose last axis is `lanes` wide: its rows must be
    whole rows of 128 lanes. A narrower last axis (a dense model's heads of 64 kept a head a
    row) is not stored row-major on the TPU, so the kernel's copies cannot address it and the
    compiler would copy the whole slab for it first, as it does for the products."""
    return lanes % 128 == 0


def _cached_block_rows(T: int, groups: int) -> int:
    """Cache rows a block of the kernel reads: about 1024 rows of the flattened slab (256 KB
    each of K and V in bfloat16: the chip's fastest of 512 to 8192, PERF.md §6, PR 35), a
    divisor of T."""
    want = max(8, 1024 // groups)
    while want > 8 and T % want:
        want //= 2
    return want if T % want == 0 else T


def put_gated(cache, new, at, gate):
    """The plain form of a step's write: cache [B, T, *minor]; new [B, S, *minor] in the cache's
    type; slot b's S rows land at rows at[b] + [0, S) where gate[b], and nothing moves where not.
    The current rows are read and written back where the gate is off; the read and the write
    clamp alike at the cache's end (at T - S), so a gated-off slot is untouched there too."""
    origin = (0,) * (cache.ndim - 2)

    def put(slot_cache, slot_new, a, g):
        cur = jax.lax.dynamic_slice(slot_cache, (a,) + origin, slot_new.shape)
        return jax.lax.dynamic_update_slice(slot_cache, jnp.where(g, slot_new, cur), (a,) + origin)

    return jax.vmap(put)(cache, new, at, gate)


_TILE = 16  # flattened slab rows: what a window starts at and spans (a bfloat16 tile in VMEM; a float32 one is 8)


def _write_window(at, S: int, groups: int, T: int):
    """Where slot rows at + [0, S) lie in the flattened slab, for a slab whose cache row is not
    whole tiles (`groups` no multiple of 8: two or four heads of 64 a row, a TP device's two
    heads, three or six of any width): the copies of the chip address whole tiles only, so
    those rows go as a window of `size` flattened rows from `start` (both multiples of `_TILE`,
    the window inside the slab) that holds them from row `off` on -> (start, off, size). A cache
    row starts at a multiple of `gcd(groups, _TILE)` inside its tile, so `off` is at most `_TILE`
    less that. `at` is clamped already; a scalar or one a slot. None where no such window lies
    inside the slab: its flattened rows are not whole tiles, or fewer than `size`."""
    size = -(-(_TILE - math.gcd(groups, _TILE) + S * groups) // _TILE) * _TILE
    if (T * groups) % _TILE or size > T * groups:
        return None
    start = jnp.minimum(at * groups // _TILE * _TILE, T * groups - size)
    return start, at * groups - start, size


def _cached_attn_kernel(*refs, scale: float, S: int, G: int, groups: int, per_group: int, block: int, T: int,
                        writes: bool):
    """Grid (slot,): online softmax over the slot's live row blocks, which the kernel copies in
    itself, two buffers deep, so that a slot costs no step for a block it does not hold.

    q_ref: [1, R, W], R = Hkv * S * G rows ordered (lane group, head in the group, position,
    query of the head), a row's head in its own D of the W lanes and zeros in the others.
    k_hbm, v_hbm: the whole flattened slabs [B, T * groups, W], left where they are; a block is
    `block` cache rows, each as `groups` rows of W lanes. A score is live where the column's
    lane group is the row's and its cache row is visible; the zeros of q make a group's other
    heads add nothing.

    Where it `writes`, the slabs are the call's outputs too, aliased to its inputs, and the step's
    new rows (new_k, new_v: the slot's, a block in VMEM) go into them first: where the slot's gate
    is on, one copy a slab to rows at + [0, S), `at` clamped to T - S as a `dynamic_update_slice`
    clamps it (a copy out of bounds is not clamped). A cache row of whole tiles (groups a multiple
    of 8) goes as it is, [B, S * groups, W]. Any other comes placed in its window of whole tiles
    (`_write_window`: [B, size, W], zeros round it): the window is copied in, takes the new rows,
    and is copied back. The write is issued at the top of the slot's grid step and waited for
    before the copy-in of the first block that holds one of its rows is started, so that it runs
    behind the earlier blocks' copies and products. Every read of a slab goes through the output's
    reference: on the chip the two are one buffer, interpreted they are two arrays and the rows
    are in the output's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if writes:
        (lens_ref, at_ref, gate_ref, q_ref, new_k, new_v, _, _, o_ref, k_hbm, v_hbm,
         k_buf, v_buf, sems, put_sems, *windows) = refs
    else:
        lens_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
    b = pl.program_id(0)
    lens = lens_ref[b]
    live_blocks = jnp.minimum(lens + S - 1, T - 1) // block + 1
    width = block * groups

    def copies(j, buf):
        rows = pl.ds(j * width, width)
        return (pltpu.make_async_copy(k_hbm.at[b, rows], k_buf.at[buf], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[b, rows], v_buf.at[buf], sems.at[1, buf]))

    if writes:
        at = jnp.minimum(at_ref[b], T - S)
        gate = gate_ref[b] != 0
        if windows:
            start, off, size = _write_window(at, S, groups, T)
            rows = pl.ds(pl.multiple_of(start, _TILE), size)
            sources = windows
        else:
            start, rows, sources = at * groups, pl.ds(at * groups, S * groups), (new_k.at[0], new_v.at[0])
        first_written = start // width
        puts = (pltpu.make_async_copy(sources[0], k_hbm.at[b, rows], put_sems.at[0]),
                pltpu.make_async_copy(sources[1], v_hbm.at[b, rows], put_sems.at[1]))

        @pl.when(gate)
        def _put():
            if windows:
                gets = (pltpu.make_async_copy(k_hbm.at[b, rows], windows[0], put_sems.at[0]),
                        pltpu.make_async_copy(v_hbm.at[b, rows], windows[1], put_sems.at[1]))
                for get in gets:
                    get.start()
                for get in gets:
                    get.wait()
                row = jax.lax.broadcasted_iota(jnp.int32, windows[0].shape, 0)
                new = (row >= off) & (row < off + S * groups)
                for window, placed in zip(windows, (new_k, new_v)):
                    window[...] = jnp.where(new, placed[0], window[...])
            for put in puts:
                put.start()

        def landed():
            for put in puts:
                put.wait()

    def before_copy_in(j):
        """The new rows have landed before the first block that holds one of them is copied in."""
        if writes:
            pl.when(gate & (first_written == j))(landed)

    before_copy_in(0)
    for copy in copies(0, 0):
        copy.start()
    q = q_ref[0]
    sg = S * G

    def one_block(j, carry):
        m_prev, l_prev, acc = carry
        buf = j % 2

        @pl.when(j + 1 < live_blocks)
        def _next():
            before_copy_in(j + 1)
            for copy in copies(j + 1, 1 - buf):
                copy.start()

        for copy in copies(j, buf):
            copy.wait()
        k = k_buf[buf].astype(q.dtype)
        v = v_buf[buf].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [R, block * groups]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * width
        # column c is lane group c % groups of cache row c // groups; every row sees cache
        # row 0, so no row of the first block is all masked and m is finite from there on
        live = (col % groups == row // (per_group * sg)) & (col < (lens + 1 + (row % sg) // G) * groups)
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    rows = q.shape[0]
    _, l, acc = jax.lax.fori_loop(0, live_blocks, one_block, (
        jnp.full((rows, 1), _NEG_INF, jnp.float32), jnp.zeros((rows, 1), jnp.float32), jnp.zeros(q.shape, jnp.float32)))
    if writes:  # rows past the slot's live blocks (a length told under the write row) are read by no block
        pl.when(gate & (first_written >= live_blocks))(landed)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def cached_attention(q, cache_k, cache_v, lens, *, scale, new_k=None, new_v=None, write_at=None, gate=None,
                     interpret: bool = False):
    """The length-aware kernel: as `cached_attention_xla`, reading of each slot only the row
    blocks up to its last visible row -> (out, cache_k, cache_v). The slab goes in as it lies:
    its rows and the axes between them and the last are flattened, which moves nothing.

    Handed a step's new rows (new_k, new_v: [B, S, *minor] in the slabs' types), the row each
    slot writes at (write_at: [B], its own operand: a slab writes at `lens`, a ring at `lens` mod
    its window and is told a shorter length) and the gate ([B] bool), the kernel writes them
    into the slabs itself before it reads them, as `put_gated` would have, and the slabs it
    returns are the ones it was given, in place (`input_output_aliases`); handed none, it writes
    nothing and returns the slabs as they came. (A slab whose cache row is no whole tile and that
    holds no window of whole tiles, `_write_window`, is written by `put_gated` first: an odd
    number of flattened rows, a ring shorter than a window.)

    Jitted, so that a program of 24 layers traces the kernel and lowers it to Mosaic once and
    calls that 24 times: traced in line, each layer's call costs 0.09 s of every start, warm or
    cold (PERF.md §6, PR 35)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hkv, G, D = q.shape
    T, W = cache_k.shape[1], cache_k.shape[-1]
    groups, per_group = Hkv * D // W, W // D
    assert per_group * D == W and groups * per_group == Hkv, (q.shape, cache_k.shape)
    rows = Hkv * S * G
    qf = jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(B, groups, per_group, S * G, D)
    if per_group > 1:
        # a head's D values into its own lanes of the group, zeros in the other heads'
        own = jnp.eye(per_group, dtype=q.dtype)[None, None, :, None, :, None]
        qf = qf[:, :, :, :, None, :] * own
    qf = qf.reshape(B, rows, W)
    block = _cached_block_rows(T, groups)
    flat = (B, T * groups, W)
    writes = new_k is not None
    if writes and groups % 8 and _write_window(0, S, groups, T) is None:
        cache_k, cache_v = put_gated(cache_k, new_k, write_at, gate), put_gated(cache_v, new_v, write_at, gate)
        writes = False
    kernel = functools.partial(_cached_attn_kernel, scale=float(scale), S=S, G=G, groups=groups,
                               per_group=per_group, block=block, T=T, writes=writes)
    scalars = [lens.astype(jnp.int32)]
    per_slot = pl.BlockSpec((1, rows, W), lambda b, *scalars: (b, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    operands, in_specs = [qf], [per_slot]
    out_specs, out_shape = [per_slot], [jax.ShapeDtypeStruct((B, rows, W), q.dtype)]
    scratch = [pltpu.VMEM((2, block * groups, W), cache_k.dtype), pltpu.VMEM((2, block * groups, W), cache_v.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    aliases = {}
    if writes:
        assert new_k.shape == (B, S) + cache_k.shape[2:] and new_k.dtype == cache_k.dtype, (new_k.shape, new_k.dtype)
        assert new_v.shape == (B, S) + cache_v.shape[2:] and new_v.dtype == cache_v.dtype, (new_v.shape, new_v.dtype)
        scalars += [write_at.astype(jnp.int32), gate.astype(jnp.int32)]
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
        new_k, new_v = new_k.reshape(B, S * groups, W), new_v.reshape(B, S * groups, W)
        if groups % 8:  # a cache row is no whole tile: each slot's rows placed in their window of whole tiles
            _, off, size = _write_window(jnp.minimum(scalars[1], T - S), S, groups, T)
            row = jnp.arange(size)[None, :, None] - off[:, None, None]

            def placed(new):  # row off + i of a slot's window is its new row i: selects, which no slot serialises
                return functools.reduce(lambda out, i: jnp.where(row == i, new[:, i:i + 1], out), range(S * groups),
                                        jnp.zeros((B, size, W), new.dtype))

            new_k, new_v = placed(new_k), placed(new_v)
            scratch += [pltpu.VMEM((size, W), cache_k.dtype), pltpu.VMEM((size, W), cache_v.dtype)]
        new_rows = pl.BlockSpec((1, new_k.shape[1], W), lambda b, *scalars: (b, 0, 0))
        operands += [new_k, new_v]
        in_specs += [new_rows, new_rows]
        out_specs += [whole, whole]
        out_shape += [jax.ShapeDtypeStruct(flat, cache_k.dtype), jax.ShapeDtypeStruct(flat, cache_v.dtype)]
        slabs_at = len(scalars) + len(operands)  # the slabs' places among the operands, the scalars counted
        aliases = {slabs_at: 1, slabs_at + 1: 2}
    out, *slabs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=in_specs + [whole, whole],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="cached_attn",
    )(*scalars, *operands, cache_k.reshape(flat), cache_v.reshape(flat))
    if writes:
        cache_k, cache_v = slabs[0].reshape(cache_k.shape), slabs[1].reshape(cache_v.shape)
    out = out.reshape(B, groups, per_group, S * G, per_group, D)
    if per_group > 1:
        out = jnp.stack([out[:, :, u, :, u] for u in range(per_group)], axis=2)
    return jnp.transpose(out.reshape(B, Hkv, S, G, D), (0, 2, 1, 3, 4)), cache_k, cache_v
