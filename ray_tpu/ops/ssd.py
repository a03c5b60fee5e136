"""The selective state-space recurrence of a Mamba-2 layer (state-space duality), in two
forms that must agree (`tests/test_granite_hybrid.py`): a chunked scan for a run of
positions and a one-token update for a decode step. Plain `jax.numpy`; decays and
cumulative sums in float32, the quadratic products in the inputs' dtype with float32
accumulation.

Per head (H heads of P channels, a state of N a channel; B and C are shared by all heads:
one group), with a_t = dt_t * A <= 0:

    h_t = exp(a_t) h_{t-1} + dt_t x_t (outer) B_t          h: [H, P, N]
    y_t = h_t . C_t + D x_t

A position with dt_t = 0 is no step: the state passes through it unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, B, C, D, h0, valid, chunk: int = 256):
    """S positions of one sequence, `chunk` at a time: inside a chunk the outputs are a
    masked quadratic form (as attention's), between chunks the state is passed on.

    x: [S, H, P]; dt: [S, H] float32, after its softplus; A, D: [H] float32; B, C: [S, N];
    h0: [H, P, N] float32, the state before the first position; valid: [S] bool, False at
    padding, which takes no step. Returns (y [S, H, P] in x's dtype, h after the last valid
    position [H, P, N] float32)."""
    S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in (x, dt, B, C))
        valid = jnp.pad(valid, (0, pad))
    n = (S + pad) // Q
    dt = jnp.where(valid[:, None], dt, 0.0)
    cs = jnp.cumsum((dt * A[None, :]).reshape(n, Q, H), axis=1)     # log decay from the chunk's start
    cs_h = cs.transpose(0, 2, 1)                                    # [n, H, Q]
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(n, Q, H, P)
    Bc, Cc = B.reshape(n, Q, N), C.reshape(n, Q, N)

    # inside a chunk: y_t = sum_{s <= t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    scores = jnp.einsum("ctn,csn->cts", Cc, Bc, preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    fade = jnp.exp(jnp.where(causal, cs_h[:, :, :, None] - cs_h[:, :, None, :], -jnp.inf))
    mixed = (scores[:, None] * fade).astype(x.dtype)                # [n, H, t, s]
    y = jnp.einsum("chts,cshp->cthp", mixed, xdt.astype(x.dtype), preferred_element_type=jnp.float32)

    # what each chunk adds to the state at its own end, then the states passed between chunks
    to_end = jnp.exp(cs[:, -1:, :] - cs)                            # [n, Q, H]
    own = jnp.einsum("cshp,csn->chpn", (xdt * to_end[..., None]).astype(x.dtype), Bc,
                     preferred_element_type=jnp.float32)
    whole = jnp.exp(cs[:, -1, :])                                   # [n, H]

    def pass_on(h, c):
        grown, kept = c
        return kept[:, None, None] * h + grown, h

    h_last, h_in = jax.lax.scan(pass_on, h0.astype(jnp.float32), (own, whole))
    # what a chunk inherits: y_t += exp(cs_t) C_t . h_in
    inherited = jnp.einsum("ctn,chpn->cthp", Cc, h_in.astype(x.dtype), preferred_element_type=jnp.float32)
    y = y + inherited * jnp.exp(cs)[..., None]
    y = y.reshape(n * Q, H, P)[:S] + D[None, :, None] * x[:S].astype(jnp.float32)
    return y.astype(x.dtype), h_last


def ssd_step(x, dt, A, B, C, D, h, gate):
    """One position for every slot. x: [B, H, P]; dt: [B, H] float32; A, D: [H]; B, C: [B, N];
    h: [B, H, P, N] float32; gate: [B] bool, and a slot whose gate is off keeps its state bit
    for bit. Returns (y [B, H, P] in x's dtype, h)."""
    x32 = x.astype(jnp.float32)
    keep = jnp.exp(dt * A[None, :])[:, :, None, None]
    stepped = keep * h + (x32 * dt[..., None])[..., None] * B.astype(jnp.float32)[:, None, None, :]
    # a product and a sum on the vector unit, in one pass over the state with its update: as a
    # matrix product the chip would round the state to bfloat16 on the way in
    y = jnp.sum(stepped * C.astype(jnp.float32)[:, None, None, :], axis=-1) + D[None, :, None] * x32
    return y.astype(x.dtype), jnp.where(gate[:, None, None, None], stepped, h)
