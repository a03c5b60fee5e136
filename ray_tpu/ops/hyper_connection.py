"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880) on the serve path: a residual
of `n` streams in place of one (`models/xing4.py`).

Per token, with X the n streams of width D (here one row of n * D values, stream after stream:
vec(X)), a sub-layer F reads a learned, input-dependent mixture of the streams and writes back
through a learned n-vector while the streams are mixed among themselves by an n x n matrix made
doubly stochastic by `iters` Sinkhorn steps:

    u = vec(X) / rms(vec(X))                       over all n * D values, no learned scale
    [a | c | r] = u Phi                            n | n | n * n
    H_pre  = sigmoid(alpha_pre a + b_pre)
    H_post = 2 sigmoid(alpha_post c + b_post)
    M      = exp(clip(alpha_res mat(r) + b_res, lo, hi)), then `iters` times: every column over
             its sum, every row over its sum (`eps` in each divisor): H_res
    h_in   = H_pre X                               `mix_in`: what the sub-layer's norm reads
    X'     = H_res X + H_post^T F(norm(h_in))      `mix_out`

Laid out for the chip. The statistic is folded into the projection (vec(X) Phi scaled by
1 / rms afterwards: u is never written). The coefficients are computed with the tokens on the
lane axis, `[2n + n^2, T]` float32 (Phi is kept `[2n + n^2, n * D]`), and the Sinkhorn steps are
sums and products of the mixing matrix's n * n rows, each a row of lanes, no reduction over an
axis of `n`: a last axis of n would fill n of 128 lanes, and 2 * `iters` reductions a sub-layer
would be as many operations on the device. XLA still cuts the unrolled steps into some fifty
fusions a sub-layer, so on the TPU they are one Pallas kernel, `hc_map`. The mixes read the coefficients
token-major (`[T, 2n + n^2]`, one small transpose a sub-layer) and the streams as lane-aligned
slices of `[T, n * D]` rows. Every function is of a token's own row alone: what padding or a
gated-off slot holds moves no other row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention


def coefficients(n: int) -> int:
    """Rows of Phi: n for H_pre, n for H_post, n * n for H_res."""
    return 2 * n + n * n


def sinkhorn(m: list, iters: int, eps: float) -> list:
    """m: n rows of n arrays of one shape, positive, m[j][i] the weight of stream i in new stream
    j. `iters` times: every column (over j) divided by its sum, then every row (over i) by its sum,
    `eps` in each divisor. Sums and products of arrays of one shape alone: elementwise over the tokens."""
    n = len(m)
    for _ in range(iters):
        inv = [1.0 / (sum(m[j][i] for j in range(n)) + eps) for i in range(n)]
        m = [[m[j][i] * inv[i] for i in range(n)] for j in range(n)]
        inv = [1.0 / (sum(m[j]) + eps) for j in range(n)]
        m = [[m[j][i] * inv[j] for i in range(n)] for j in range(n)]
    return m


def _coefficients(scaled, n: int, iters: int, eps: float, clamp: tuple) -> list:
    """scaled(k, s): row k of u Phi times alpha[s] plus b[k], any one shape (s: 0 pre, 1 post, 2 res).
    The 2n + n^2 rows of H_pre | H_post | H_res."""
    z = lambda k: scaled(k, min(k // n, 2))  # noqa: E731
    pre = [1.0 / (1.0 + jnp.exp(-z(k))) for k in range(n)]
    post = [2.0 / (1.0 + jnp.exp(-z(n + k))) for k in range(n)]
    m = [[jnp.exp(jnp.clip(z(2 * n + j * n + i), clamp[0], clamp[1])) for i in range(n)] for j in range(n)]
    return pre + post + [w for row in sinkhorn(m, iters, eps) for w in row]


def _hc_map_kernel(affine_ref, proj_ref, inv_ref, out_ref, *, n: int, iters: int, eps: float, clamp: tuple):
    """One block of tokens (the lane axis): every row of the coefficients from the projection's
    rows, elementwise, the Sinkhorn steps unrolled over the n * n rows of the mixing matrix."""
    inv = inv_ref[...]
    rows = _coefficients(lambda k, s: proj_ref[k:k + 1, :] * inv * affine_ref[s] + affine_ref[3 + k], n, iters, eps, clamp)
    for k, row in enumerate(rows):
        out_ref[k:k + 1, :] = row


LANE_BLOCK = 512  # tokens a block of the kernel takes: 16 rows of the mixing matrix in 64 registers of 8 x 128


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp", "interpret"))
def hc_map(proj, inv_rms, affine, *, n: int, iters: int, eps: float, clamp: tuple, interpret: bool = False):
    """The Pallas kernel `hc_map`: proj [2n + n^2, T] float32 (vec(X) Phi, tokens on the lane axis),
    inv_rms [1, T], affine [3 + 2n + n^2] float32 (the gains, then the biases: scalars the kernel
    reads one at a time) -> the coefficients [2n + n^2, T] float32. One
    operation on the device where the same arithmetic as XLA's fusions is some fifty (PERF.md §6, PR 42)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, T = proj.shape
    block = LANE_BLOCK if T % LANE_BLOCK == 0 else T
    kernel = functools.partial(_hc_map_kernel, n=n, iters=iters, eps=eps, clamp=clamp)
    return pl.pallas_call(
        kernel,
        grid=(T // block,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((C, block), lambda t: (0, t)), pl.BlockSpec((1, block), lambda t: (0, t))],
        out_specs=pl.BlockSpec((C, block), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((C, T), jnp.float32),
        interpret=interpret,
        name="hc_map",
    )(affine, proj, inv_rms)


def mapping(x, phi, alpha, bias, *, n: int, iters: int, eps: float, clamp: tuple):
    """x: [T, n * D] the streams; phi: [2n + n^2, n * D]; alpha: [3] (pre, post, res); bias:
    [2n + n^2]. Returns the coefficients token-major, float32 [T, 2n + n^2]: H_pre | H_post |
    H_res (row-major: column 2n + j * n + i weighs stream i in new stream j). On the TPU the
    coefficients' arithmetic is the kernel `hc_map`; elsewhere the same rows as XLA's own."""
    proj = jax.lax.dot_general(phi.astype(x.dtype), x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    x32 = x.astype(jnp.float32)
    inv_rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + eps)[None, :]
    affine = jnp.concatenate([alpha, bias]).astype(jnp.float32)
    if attention._use_pallas():
        return hc_map(proj, inv_rms, affine, n=n, iters=iters, eps=eps, clamp=clamp).T
    return jnp.concatenate(_coefficients(lambda k, s: proj[k:k + 1] * inv_rms * affine[s] + affine[3 + k], n, iters, eps, clamp)).T


def _stream(x, i: int, n: int):
    D = x.shape[-1] // n
    return x[:, i * D:(i + 1) * D].astype(jnp.float32)


def mix_in(x, coef, *, n: int):
    """h_in = H_pre X. x: [T, n * D]; coef: `mapping`'s. Returns [T, D] in x's type."""
    return sum(coef[:, i:i + 1] * _stream(x, i, n) for i in range(n)).astype(x.dtype)


def mix_out(x, y, coef, *, n: int):
    """X' = H_res X + H_post^T y. x: [T, n * D]; y: [T, D] the sub-layer's output; coef:
    `mapping`'s. Returns [T, n * D] in x's type."""
    streams, y32 = [_stream(x, i, n) for i in range(n)], y.astype(jnp.float32)

    def new(j):
        mixed = sum(coef[:, 2 * n + j * n + i:2 * n + j * n + i + 1] * streams[i] for i in range(n))
        return mixed + coef[:, n + j:n + j + 1] * y32

    return jnp.concatenate([new(j) for j in range(n)], axis=-1).astype(x.dtype)
