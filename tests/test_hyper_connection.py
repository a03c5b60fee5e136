"""`ops/hyper_connection.py` on the CPU: the mapping and the two mixes against the formula written
out literally (a loop over tokens, a `[4, 4]` matrix a token, divisions), the mixing matrix's rows and
columns summing to 1, the clamp where the logits pass it, the kernel `hc_map` interpreted against the
same rows as XLA's own, and rows that are a token's own alone: what padding or a gated-off slot
holds moves no other row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, hyper_connection as hc

N, D, ITERS, EPS, CLAMP = 4, 32, 20, 1e-6, (-30.0, 30.0)
KW = dict(n=N, iters=ITERS, eps=EPS, clamp=CLAMP)
# rows and columns of H_res: every row is divided by its sum + eps last (1 - 1e-6, at most a rounding
# away); a column's sum is where 20 steps have brought it, as the literal formula's is: within 3e-3 of
# 1 for the slowest token of these draws (a near-permutation converges slowest), 1e-6 for most
ROW_TOL, COL_TOL = 3e-6, 1e-2


def _draw(T, seed=0, gain=(1.0, 1.0, 1.0), scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (T, N * D)) * scale
    phi = jax.random.normal(k[1], (hc.coefficients(N), N * D)) / np.sqrt(N * D)
    return x, phi, jnp.asarray(gain, jnp.float32), jax.random.normal(k[2], (hc.coefficients(N),))


def _literal(x, phi, alpha, bias, iters=ITERS, clamp=CLAMP):
    """The paper's formula a token at a time in float64: (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    x, phi, alpha, bias = (np.asarray(a, np.float64) for a in (x, phi, alpha, bias))
    pre, post, res = [], [], []
    for v in x:
        u = v / np.sqrt(np.mean(v * v) + EPS)
        z = phi @ u
        pre.append(1 / (1 + np.exp(-(alpha[0] * z[:N] + bias[:N]))))
        post.append(2 / (1 + np.exp(-(alpha[1] * z[N:2 * N] + bias[N:2 * N]))))
        m = np.exp(np.clip(alpha[2] * z[2 * N:].reshape(N, N) + bias[2 * N:].reshape(N, N), *clamp))
        for _ in range(iters):
            m = m / (m.sum(axis=0, keepdims=True) + EPS)
            m = m / (m.sum(axis=1, keepdims=True) + EPS)
        res.append(m)
    return np.stack(pre), np.stack(post), np.stack(res)


def _parts(coef):
    coef = np.asarray(coef)
    return coef[:, :N], coef[:, N:2 * N], coef[:, 2 * N:].reshape(-1, N, N)


def test_the_mapping_is_the_literal_formula_and_its_mixing_matrix_is_doubly_stochastic():
    x, phi, alpha, bias = _draw(37)
    pre, post, res = _parts(hc.mapping(x, phi, alpha, bias, **KW))
    want = _literal(x, phi, alpha, bias)
    for got, lit in zip((pre, post, res), want):
        np.testing.assert_allclose(got, lit, atol=5e-6)
    assert np.abs(res.sum(axis=2) - 1).max() < ROW_TOL and np.abs(res.sum(axis=1) - 1).max() < COL_TOL
    assert (0 < pre).all() and (pre < 1).all() and (0 < post).all() and (post < 2).all() and (res > 0).all()
    # the draws make the mechanism matter: far from the identity and from the uniform matrix, and the
    # part of a coefficient that depends on the token as large as the part that does not
    assert np.abs(res - np.eye(N)).max(axis=(1, 2)).min() > 0.3 and np.abs(res - 0.25).max(axis=(1, 2)).min() > 0.1
    assert res.std(axis=0).mean() > 0.05 and pre.std(axis=0).mean() > 0.1


def test_one_sinkhorn_step_is_not_yet_doubly_stochastic_and_twenty_are():
    x, phi, alpha, bias = _draw(64, seed=3)
    one = _parts(hc.mapping(x, phi, alpha, bias, n=N, iters=1, eps=EPS, clamp=CLAMP))[2]
    assert np.abs(one.sum(axis=2) - 1).max() < 1e-5 and np.abs(one.sum(axis=1) - 1).max() > 0.1  # rows just divided, columns far
    np.testing.assert_allclose(one, _literal(x, phi, alpha, bias, iters=1)[2], atol=5e-6)


def test_the_clamp_holds_logits_drawn_past_thirty():
    """A gain of 60 on a unit-variance projection and biases of standard deviation 1 draw R past 30 in
    most tokens: the mapping is the literal formula with the clamp, finite and doubly stochastic, and
    not the formula without it (exp(88) is float32's last finite one)."""
    x, phi, alpha, bias = _draw(50, seed=5, gain=(1.0, 1.0, 60.0))
    z = np.asarray(phi) @ (np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, axis=-1, keepdims=True) + EPS)).T
    assert (np.abs(60.0 * z[2 * N:] + np.asarray(bias)[2 * N:, None]).max(axis=0) > 30).mean() > 0.9
    res = _parts(hc.mapping(x, phi, alpha, bias, **KW))[2]
    assert np.isfinite(res).all() and np.abs(res.sum(axis=2) - 1).max() < ROW_TOL
    np.testing.assert_allclose(res, _literal(x, phi, alpha, bias)[2], atol=2e-5)
    loose = _parts(hc.mapping(x, phi, alpha, bias, n=N, iters=ITERS, eps=EPS, clamp=(-1e9, 1e9)))[2]
    assert not np.isfinite(loose).all() or np.abs(loose - res).max() > 1e-3


def test_the_two_mixes_are_the_products_with_the_coefficients():
    x, phi, alpha, bias = _draw(29, seed=7)
    y = jax.random.normal(jax.random.PRNGKey(9), (29, D))
    coef = hc.mapping(x, phi, alpha, bias, **KW)
    pre, post, res = _parts(coef)
    X = np.asarray(x).reshape(29, N, D)
    np.testing.assert_allclose(np.asarray(hc.mix_in(x, coef, n=N)), np.einsum("ti,tid->td", pre, X), atol=2e-6)
    want = np.einsum("tji,tid->tjd", res, X) + post[:, :, None] * np.asarray(y)[:, None, :]
    np.testing.assert_allclose(np.asarray(hc.mix_out(x, y, coef, n=N)).reshape(29, N, D), want, atol=2e-6)


@pytest.mark.parametrize("T", [16, 48, 1024], ids=["a-bucket", "the-slots", "two-blocks-of-lanes"])
def test_the_kernel_interpreted_is_the_rows_xla_computes(T, monkeypatch):
    x, phi, alpha, bias = _draw(T, seed=T)
    want = hc.mapping(x, phi, alpha, bias, **KW)
    kernel = hc.hc_map
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(hc, "hc_map", lambda *a, **kw: kernel(*a, interpret=True, **kw))
    got = hc.mapping(x, phi, alpha, bias, **KW)
    assert got.shape == (T, hc.coefficients(N)) and (T % hc.LANE_BLOCK == 0) == (T == 1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_a_rows_coefficients_and_mixes_are_its_own_whatever_padding_holds():
    """Rows past a chunk's valid tokens, and gated-off slots, hold whatever they hold: not a number,
    zeros, another request's values. Every valid row comes out bit for bit as it does alone."""
    x, phi, alpha, bias = _draw(24, seed=11)
    y = jax.random.normal(jax.random.PRNGKey(2), (24, D))

    def run(x, y):
        coef = hc.mapping(x, phi, alpha, bias, **KW)
        return np.asarray(coef), np.asarray(hc.mix_in(x, coef, n=N)), np.asarray(hc.mix_out(x, y, coef, n=N))

    clean = run(x, y)
    pad = jnp.arange(24) % 3 == 2
    for filler in (jnp.nan, 0.0, 1e30):
        dirty = run(jnp.where(pad[:, None], filler, x), jnp.where(pad[:, None], filler, y))
        for a, b in zip(clean, dirty):
            np.testing.assert_array_equal(a[~np.asarray(pad)], b[~np.asarray(pad)])
    zeros = run(jnp.zeros_like(x), jnp.zeros_like(y))  # an all-zero row (a fresh slot) divides by eps, not by zero
    assert all(np.isfinite(a).all() for a in zeros) and not zeros[2].any()


def test_bfloat16_streams_are_mixed_in_float32_and_kept_in_bfloat16():
    x, phi, alpha, bias = _draw(33, seed=13)
    xb, y = x.astype(jnp.bfloat16), jax.random.normal(jax.random.PRNGKey(4), (33, D)).astype(jnp.bfloat16)
    coef = hc.mapping(xb, phi.astype(jnp.bfloat16), alpha, bias, **KW)
    assert coef.dtype == jnp.float32 and hc.mix_in(xb, coef, n=N).dtype == jnp.bfloat16
    out = hc.mix_out(xb, y, coef, n=N)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    exact = hc.mix_out(xb.astype(jnp.float32), y.astype(jnp.float32), coef, n=N)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exact), rtol=2 ** -8, atol=1e-3)
