"""Ring attention: exact causal attention over a sequence-parallel ("sp") mesh axis.

The reference framework has no native sequence/context parallelism (SURVEY.md §2.3: absent,
only vLLM pass-through); this is a first-class TPU capability here. Each device holds a
contiguous sequence chunk of q/k/v; k/v chunks rotate around the sp ring via
`jax.lax.ppermute` (XLA lowers to ICI neighbor exchange) while every device accumulates its
q-chunk's attention with an online log-sum-exp merge. Communication overlaps compute under
XLA's async collective scheduling; a Pallas RDMA double-buffered variant is the follow-on
optimization.

Causal structure: with chunk index c_q fixed per device and c_kv rotating, a step is
  - fully visible  (c_kv < c_q): unmasked block attention
  - diagonal       (c_kv == c_q): causal mask within the chunk
  - invisible      (c_kv > c_q): skipped via -inf lse contribution
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _ensure_varying(x, axis_name):
    """Mark x varying over the manual axis if it isn't already (jax vma typing)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


def _chunk_attention(q, k, v, mode, scale):
    """Block attention with lse. q:[B,S,H,D], k/v:[B,T,H,D]; mode 0=full,1=diag,2=skip."""
    S, T = q.shape[1], k.shape[1]
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    causal_mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
    logits = jnp.where(
        (mode == 0) | ((mode == 1) & causal_mask[None, None]), logits, _NEG_INF
    )
    lse = jax.nn.logsumexp(logits, axis=-1)  # [B,H,S]
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(q.dtype), v)
    return out, lse


def _merge(out1, lse1, out2, lse2):
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None].transpose(0, 2, 1, 3)  # [B,S,H,1]
    w2 = jnp.exp(lse2 - lse)[..., None].transpose(0, 2, 1, 3)
    return out1 * w1.astype(out1.dtype) + out2 * w2.astype(out2.dtype), lse


def ring_attention(q, k, v, axis_name: str = "sp", *, causal: bool = True,
                   scale: float | None = None):
    """Call inside shard_map with sequence sharded over `axis_name`.

    q:[B,Sc,H,D] local chunk; k/v:[B,Sc,Hkv,D] local chunks. Returns local out chunk.
    """
    D, H, Hkv = q.shape[-1], q.shape[2], k.shape[2]
    eff_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]  # send kv to the right

    def step(carry, step_idx):
        out_acc, lse_acc, k_cur, v_cur = carry
        # kv chunk currently held came from (my_idx - step_idx) mod n
        kv_idx = (my_idx - step_idx) % axis_size
        if causal:
            mode = jnp.where(kv_idx < my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2))
        else:
            mode = jnp.zeros((), jnp.int32)
        out_p, lse_p = _chunk_attention(q, k_cur, v_cur, mode, eff_scale)
        out_new, lse_new = _merge(out_acc, lse_acc, out_p, lse_p)
        # Rotate k/v around the ring (skipped result ignored on the final step).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (out_new, lse_new, k_nxt, v_nxt), None

    B, Sc, _, _ = q.shape
    out0 = jnp.zeros_like(q)
    lse0 = jnp.full((B, H, Sc), _NEG_INF, jnp.float32)
    # Freshly-created carries must be marked varying over the manual axis for scan's
    # carry typing under shard_map (vma rules).
    out0 = _ensure_varying(out0, axis_name)
    lse0 = _ensure_varying(lse0, axis_name)
    (out, _lse, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(axis_size)
    )
    return out


def ulysses_attention(q, k, v, axis_name: str = "sp", *, causal: bool = True,
                      scale: float | None = None, attn_fn=None):
    """DeepSpeed-Ulysses style context parallelism: all-to-all head<->sequence reshuffle.

    Inside shard_map with sequence sharded over `axis_name`: trade the sequence shard for
    a head shard (all_to_all), run full-sequence attention per head group, trade back.
    Requires num heads divisible by the axis size.
    """
    n = jax.lax.psum(1, axis_name)
    if k.shape[2] != q.shape[2] and k.shape[2] % n != 0:
        # GQA with fewer kv-head groups than the sp axis: materialize full kv heads
        # before the exchange (costs bandwidth; correctness over elegance).
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, Sc, H, D] -> gather sequence, scatter heads -> [B, S, H/n, D]
    q_g = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k_g = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v_g = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    if attn_fn is None:
        from ray_tpu.ops.attention import flash_attention

        attn_fn = lambda a, b, c: flash_attention(a, b, c, causal, scale)  # noqa: E731
    out = attn_fn(q_g, k_g, v_g)
    # [B, S, H/n, D] -> back to [B, Sc, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)
