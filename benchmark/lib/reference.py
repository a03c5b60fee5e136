"""The plain reference: the block's forward pass in `jax.numpy`, float32, true float32
matmuls. No kernel, no cache, no batching, and nothing imported from
`ray_tpu.models` or `ray_tpu.llm`. It reads the program's parameter tree and nothing
else of it. It decides `correct`.

The block (both configurations are this block; Mistral-7B-v0.3 and InternLM2 as their
`config.json` and modelling code describe them):

    h  = embedding[tokens]
    for each layer:
        a  = rmsnorm(h, attn_norm)
        q, k, v = a @ Wq, a @ Wk, a @ Wv                (heads of head_dim; fewer k/v heads)
        q, k = rope(q), rope(k)                          (rotate-half form, theta from the config)
        o  = softmax(q k^T / sqrt(head_dim) + causal) v  (each k/v head serves n_heads/n_kv_heads q heads)
        h  = h + o @ Wo
        m  = rmsnorm(h, mlp_norm)
        h  = h + (silu(m @ Wgate) * (m @ Wup)) @ Wdown
    logits = rmsnorm(h, final_norm) @ lm_head            (untied head)

Departures from the published models: InternLM2 stores q, k, v as one fused `wqkv`;
that is a layout of the same three matmuls, and the program's tree keeps them apart.
On a TPU a float32 matmul multiplies in bfloat16 unless the precision is raised, so
every entry point here runs under `jax.default_matmul_precision("highest")`.

Tolerances are at the bottom, with their reasons.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax.core import meta


def plain_tree(params):
    """The program's tree without flax's partitioning boxes."""
    return meta.unbox(params)


def _layer(params, i: int):
    """Layer i of either layout: `layer_<i>` (unrolled) or `layers` (stacked for scan)."""
    if "layers" in params:
        return jax.tree_util.tree_map(lambda x: x[i], params["layers"])
    return params[f"layer_{i}"]


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x: [S, H, D]. Rotate-half: pairs are (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, q_block: int, op):
    """q: [S, H, D]; k, v: [S, Hkv, D]. Causal, grouped-query, by einsum. Queries are
    taken `q_block` at a time only so that a 4096-token sequence's scores fit the chip
    beside a train state; the mathematics is the same for any block size."""
    S, H, D = q.shape
    g = H // k.shape[1]
    k = op(jnp.repeat(k, g, axis=1))
    v = op(jnp.repeat(v, g, axis=1))
    pos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, q_block):
        qb = op(q[s0:s0 + q_block])
        scores = jnp.einsum("shd,thd->hst", qb, k) / math.sqrt(D)
        mask = pos[None, :] <= pos[s0:s0 + q_block, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hst,thd->shd", op(jax.nn.softmax(scores, axis=-1)), v))
    return jnp.concatenate(outs, axis=0)


def forward(params, cfg: dict, tokens, q_block: int = 1024, operand=None):
    """tokens: [S] int32 -> logits [S, V] float32. `operand`, where given, is applied to
    both operands of every matrix product: the control of `tests/test_control.py` rounds
    them to a narrower type there, to show that the comparison fails on it. The reference
    gives none, and is then the same program as without the argument."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    op = operand or (lambda a: a)
    S = tokens.shape[0]
    positions = jnp.arange(S)
    h = f32(params["embedding"])[tokens]
    for i in range(cfg["n_layers"]):
        lp = _layer(params, i)
        a = op(_rmsnorm(h, f32(lp["attn_norm"]["scale"]), cfg["norm_eps"]))
        q = jnp.einsum("se,ehd->shd", a, op(f32(lp["attn"]["q"]["kernel"])))
        k = jnp.einsum("se,ehd->shd", a, op(f32(lp["attn"]["k"]["kernel"])))
        v = jnp.einsum("se,ehd->shd", a, op(f32(lp["attn"]["v"]["kernel"])))
        q = _rope(q, positions, cfg["rope_theta"])
        k = _rope(k, positions, cfg["rope_theta"])
        o = _attention(q, k, v, q_block, op)
        h = h + jnp.einsum("shd,hde->se", op(o), op(f32(lp["attn"]["o"]["kernel"])))
        m = op(_rmsnorm(h, f32(lp["mlp_norm"]["scale"]), cfg["norm_eps"]))
        gate = m @ op(f32(lp["mlp"]["gate"]["kernel"]))
        up = m @ op(f32(lp["mlp"]["up"]["kernel"]))
        h = h + op(jax.nn.silu(gate) * up) @ op(f32(lp["mlp"]["down"]["kernel"]))
    h = _rmsnorm(h, f32(params["final_norm"]["scale"]), cfg["norm_eps"])
    return op(h) @ op(f32(params["lm_head"]["kernel"]))


def token_losses(params, cfg: dict, tokens, targets, q_block: int = 1024, operand=None):
    """Next-token cross-entropy at every position of one sequence. tokens, targets: [S] -> [S]."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, tokens, q_block, operand)
        gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold


def loss(params, cfg: dict, tokens, targets, q_block: int = 1024, operand=None):
    """Mean next-token cross-entropy of one sequence. tokens, targets: [S]."""
    return jnp.mean(token_losses(params, cfg, tokens, targets, q_block, operand))


def greedy(params, cfg: dict, prompt, n_new: int):
    """Greedy-decode n_new tokens after `prompt` ([P] int32) by full forward passes over
    the whole sequence so far (padded to P + n_new: a causal model's logits at a
    position do not depend on what follows it). Returns (ids [n_new], margins
    [n_new]): the chosen id and the gap between the two largest logits at each step."""
    P = prompt.shape[0]
    buf = jnp.concatenate([prompt.astype(jnp.int32), jnp.zeros((n_new,), jnp.int32)])

    def step(j, carry):
        buf, ids, margins = carry
        with jax.default_matmul_precision("highest"):
            logits = forward(params, cfg, buf)[P + j - 1]
        top2 = jax.lax.top_k(logits, 2)[0]
        nxt = jnp.argmax(logits).astype(jnp.int32)
        return (buf.at[P + j].set(nxt), ids.at[j].set(nxt),
                margins.at[j].set(top2[0] - top2[1]))

    init = (buf, jnp.zeros((n_new,), jnp.int32), jnp.zeros((n_new,), jnp.float32))
    _, ids, margins = jax.lax.fori_loop(0, n_new, step, init)
    return ids, margins


# -- tolerances ---------------------------------------------------------------------

# Cell 1. The train step computes in bfloat16 with float32 accumulation; the reference
# in float32. With seeded random weights the logits have a spread near 1, a token's
# loss moves by about 1e-2 under bfloat16 rounding, with either sign, and the mean over
# thousands of tokens by about 1e-4 (PR 21 measured 2e-4 between two bfloat16 layouts
# of one step). A dropped or changed term (no rope, no norm scale, a mask off by one,
# a missing residual) gives other logits altogether: the mean loss over the same random
# targets then differs by the sampling noise of the mean, 1 / sqrt(tokens), which is
# 1.1e-2 at 8192 tokens. So 1.5e-3 absolute sits an order of magnitude above the
# rounding and below a wrong function.
LOSS_ABS_TOL = 1.5e-3

# The train cells, beside it. A mean over thousands of tokens hides a precision: with both
# operands of every matrix product rounded to float8 (the control, `tests/test_control.py`)
# the mean loss moved by 2.6e-5, 1.6e-3 and 2.7e-3 on three seeds at Mistral's widths and by
# 9.1e-4 to 3.7e-3 at InternLM2's, so LOSS_ABS_TOL passes it as often as not. What shows a
# precision is a token's own loss. So the program's forward pass (`eval_logits_fn`, bfloat16
# matmuls, float32 accumulation) is compared with the reference token by token, and the root
# of the mean square of the difference is held to this limit. Readings (my chip run, PR 27):
# sound runs 0.01460 to 0.01542 over 12 seeds of 4096 tokens at Mistral's widths, and 0.02155
# to 0.02227 over 12 seeds of 16384 tokens at InternLM2's over four chips; the control 0.1523,
# 0.1543, 0.1562 and 0.2108, 0.2114, 0.2138 on three seeds each (the reference with bfloat16
# operands: 0.0101 to 0.0103 and 0.0141 to 0.0146). Steady from seed to seed on both sides,
# a factor of seven apart at the nearest: 0.05 is 2.2 times the largest sound reading and a
# third of the smallest control.
TOKEN_LOSS_RMS_TOL = 5e-2

# Cells 2 and 3. The engine multiplies in bfloat16 (float32 accumulation), so its
# logits differ from the reference's, and with random weights the two largest of 92544
# logits are often closer than that difference: then either id is a right answer. So
# the engine's ids are walked beside the reference's. Where they are equal the walk
# goes on, and the position counts as compared if the reference's own margin (largest
# logit less the second) is at least NEAR_TIE_MARGIN. Where they differ the walk ends,
# since the two sequences have parted for good: at a margin under the threshold that
# is a near-tie and no fault, at a margin over it the engine is wrong.
#
# The threshold is measured, not guessed (my chip run, PR 23: 200 probes of 64 + 16
# tokens on one server at internlm2-1.8b, logits of standard deviation 1.00, median
# margin 0.16). The engine parted from the reference in 101 probes; by the margin at
# the position: 36 of 87 positions under 0.01, 39 of 104 in [0.01, 0.02), 17 of 122 in
# [0.02, 0.035), 6 of 134 in [0.035, 0.05), 3 of 179 in [0.05, 0.075) (at 0.052, 0.064,
# 0.064), none of 1667 over 0.075. That is a noise of about 0.026 (one standard
# deviation) on the difference of two logits. The first threshold, 0.05, was under two
# of those deviations, and about one run in fifty read a near-tie as a fault (the
# driver's first check met one). 0.15 is near six deviations and over twice the widest
# parting seen. A dropped or changed term moves logits by their whole spread, 1, and
# arithmetic narrower than bfloat16 by several times 0.026: either parts the ids at
# margins well over 0.15 within a few positions, and fails.
#
# With 0.15, half of all positions count, and a probe gives 6 compared positions on
# average (none in one probe of ten, when it parts at once). The reference prepares
# MAX_PROBES probes, the server is sent them one at a time until MIN_COMPARED_POSITIONS
# are compared (2.3 probes on average); that all twelve give fewer than 8 has a
# probability near 2e-8 by the measured distribution.
NEAR_TIE_MARGIN = 0.15
MIN_COMPARED_POSITIONS = 8
MAX_PROBES = 12


def compare_greedy(ref_ids, ref_margins, got_ids) -> tuple:
    """(agrees, compared): whether `got_ids` parts from the reference nowhere but at a
    near-tie, and at how many positions of a clear margin the two were equal."""
    compared = 0
    for rid, margin, gid in zip(ref_ids, ref_margins, got_ids):
        if int(rid) != int(gid):
            return margin < NEAR_TIE_MARGIN, compared
        if margin >= NEAR_TIE_MARGIN:
            compared += 1
    return True, compared
