"""The plain reference of the `granite_hybrid` block: the forward pass in `jax.numpy`,
float32, true float32 matmuls, the recurrence one token at a time. No kernel, no cache, no
chunked scan, and nothing imported from the program: it reads the program's parameter tree
and the configuration file's `model` keys, and decides `correct`. The equations
(ibm-granite/granite-4.0-h-micro `config.json`, `model_type` granitemoehybrid; Mamba-2's
recurrence; what the config does not give is under `assumed` in
`configs/granite-4.0-h-micro.json`):

    x = embedding_multiplier * E[tokens]
    for each layer i, u = rmsnorm(x):
      attention (layer_types[i] == "attention"; H heads, Hkv key and value heads, no rotary)
        o_head = softmax_{s <= t}(attention_multiplier * q_t . k_s) v_s;  x += residual_multiplier * o W_o
      mamba (H heads of P channels, state N, one group, a convolution of `mamba_d_conv` taps)
        [z | xBC | dt] = u W_in_proj                           H P | H P + 2 N | H
        xBC_t = silu(b + sum_j w_j xBC_{t - taps + 1 + j})     zeros before the sequence
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t,  h_{-1} = 0
        y_t = h_t . C_t + D x_t
        x += residual_multiplier * (rmsnorm(y * silu(z)) * g) W_out_proj
      m = rmsnorm(x);  [a | b] = m W_in;  x += residual_multiplier * (silu(a) * b) W_out
    logits = rmsnorm(x) E^T / logits_scaling

Queries are taken `q_block` at a time and logits at the scored rows only, so that a request
of 2432 tokens is scored beside a server whose weights and cache fill 11.7 of the chip's 16 GB;
the mathematics is the same for any block. Tolerances are at the bottom, with their readings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax.core import meta


def plain_tree(params):
    """The program's tree without flax's partitioning boxes (this block's has none)."""
    return meta.unbox(params)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _rows(x, first, count: int):
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)


def _mamba(p, u, cfg: dict, op, state):
    """u: [S, D] -> [S, D]. `state`, where given, is applied to the recurrent state after every
    step (a control keeps it in a narrower type)."""
    S = u.shape[0]
    H, P, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = H * P
    W = inner + 2 * N
    zxd = op(u) @ op(_f32(p["in_proj"]["kernel"]))
    z, xBC, dt = zxd[:, :inner], zxd[:, inner:inner + W], zxd[:, inner + W:]
    before = jnp.concatenate([jnp.zeros((K - 1, W), jnp.float32), xBC])
    w = _f32(p["conv"]["kernel"])
    xBC = jax.nn.silu(sum(w[j] * before[j:j + S] for j in range(K)) + _f32(p["conv"]["bias"]))
    x, B, C = xBC[:, :inner].reshape(S, H, P), xBC[:, inner:inner + N], xBC[:, inner + N:]
    dt, A, D = jax.nn.softplus(dt + _f32(p["dt_bias"])), -jnp.exp(_f32(p["A_log"])), _f32(p["D"])

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[..., None] * B_t
        h = h if state is None else state(h)
        return h, jnp.sum(h * C_t, axis=-1) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, dt, B, C))
    y = _rmsnorm(y.reshape(S, inner) * jax.nn.silu(z), p["norm"]["scale"], cfg["norm_eps"])
    return op(y) @ op(_f32(p["out_proj"]["kernel"]))


def _attention(p, u, cfg: dict, q_block: int, op):
    """u: [S, D] with S a multiple of q_block -> [S, D]; queries a block at a time."""
    S = u.shape[0]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["hidden"] // H
    q = (op(u) @ op(_f32(p["q"]["kernel"]))).reshape(S // q_block, q_block, Hkv, H // Hkv, hd)
    k = (op(u) @ op(_f32(p["k"]["kernel"]))).reshape(S, Hkv, hd)
    v = (op(u) @ op(_f32(p["v"]["kernel"]))).reshape(S, Hkv, hd)
    pos = jnp.arange(S)

    def block(args):
        qb, first = args
        s = jnp.einsum("skgd,tkd->kgst", op(qb), op(k)) * cfg["attention_multiplier"]
        seen = (first + jnp.arange(q_block))[:, None] >= pos[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", op(pr), op(v)).reshape(q_block, H * hd)

    o = jax.lax.map(block, (q, jnp.arange(S // q_block) * q_block)).reshape(S, H * hd)
    return op(o) @ op(_f32(p["o"]["kernel"]))


def forward(params, cfg: dict, tokens, q_block: int = 256, operand=None, rows=None, state=None):
    """tokens: [S] int32 -> logits [S, V] float32, or with `rows` = (first, count) the logits
    of those positions only (first may be traced). `operand`, where given, is applied to both
    operands of every matrix product (the control of `benchmark/tests/test_granite_hybrid.py`
    rounds them to a narrower type), `state` to the recurrent state after every step. Call under
    `jax.default_matmul_precision("highest")`, as every entry point below does."""
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError("the reference of granite_hybrid is position-free")
    op = operand or (lambda a: a)
    S, F, r = tokens.shape[0], cfg["mlp_dim"], cfg["residual_multiplier"]
    # whole blocks of queries: a causal model's logits at a position do not depend on what follows it
    tokens = jnp.pad(tokens, (0, -S % q_block))
    E = _f32(params["embedding"])
    x = E[tokens] * cfg["embedding_multiplier"]
    for i in range(cfg["n_layers"]):
        lp = params[f"layer_{i}"]
        u = _rmsnorm(x, lp["attn_norm"]["scale"], cfg["norm_eps"])
        if cfg["layer_types"][i] == "mamba":
            x = x + r * _mamba(lp["attn"], u, cfg, op, state)
        else:
            x = x + r * _attention(lp["attn"], u, cfg, q_block, op)
        m = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg["norm_eps"])
        ab = op(m) @ op(_f32(lp["mlp"]["in"]["kernel"]))
        x = x + r * (op(jax.nn.silu(ab[:, :F]) * ab[:, F:]) @ op(_f32(lp["mlp"]["out"]["kernel"])))
    x = x[:S] if rows is None else _rows(x, rows[0], rows[1])
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    return op(x) @ op(E).T / cfg["logits_scaling"]


def token_losses(params, cfg: dict, tokens, targets, q_block: int = 256, operand=None):
    """Next-token cross-entropy at every position of one sequence. tokens, targets: [S] -> [S]."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, tokens, q_block, operand)
        gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold


def loss(params, cfg: dict, tokens, targets, q_block: int = 256, operand=None):
    """Mean next-token cross-entropy of one sequence."""
    return jnp.mean(token_losses(params, cfg, tokens, targets, q_block, operand))


def greedy(params, cfg: dict, prompt, n_new: int, operand=None):
    """Greedy-decode n_new tokens after `prompt` ([P] int32) by full forward passes over the
    whole sequence so far (padded to P + n_new). Returns (ids [n_new], margins [n_new]): the
    chosen id and the gap between the two largest logits at each step."""
    P = prompt.shape[0]
    buf = jnp.concatenate([prompt.astype(jnp.int32), jnp.zeros((n_new,), jnp.int32)])

    def step(j, carry):
        buf, ids, margins = carry
        with jax.default_matmul_precision("highest"):
            logits = forward(params, cfg, buf, operand=operand, rows=(P + j - 1, 1))[0]
        top2 = jax.lax.top_k(logits, 2)[0]
        nxt = jnp.argmax(logits).astype(jnp.int32)
        return (buf.at[P + j].set(nxt), ids.at[j].set(nxt), margins.at[j].set(top2[0] - top2[1]))

    init = (buf, jnp.zeros((n_new,), jnp.int32), jnp.zeros((n_new,), jnp.float32))
    _, ids, margins = jax.lax.fori_loop(0, n_new, step, init)
    return ids, margins


def score(params, cfg: dict, sequence, n_last: int, operand=None, length=None, q_block: int = 256, state=None):
    """The reference's next-token choice at each of the last `n_last` positions of `sequence`
    ([S] int32), given everything before it: (ids [n_last], margins [n_last], logits of the
    sequence's own tokens there less the largest [n_last]). One full forward pass: what a server
    generated is scored position by position, so a parting at one position does not end the
    comparison at the next. `length` (may be traced) is where the sequence ends if `sequence` is
    padded beyond it, so that one program scores sequences of any length up to S."""
    n = sequence.shape[0] if length is None else length
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, sequence, q_block, operand, rows=(n - n_last - 1, n_last), state=state)
    top2 = jax.lax.top_k(logits, 2)[0]
    own = jnp.take_along_axis(logits, _rows(sequence, n - n_last, n_last)[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), top2[:, 0] - top2[:, 1], own - top2[:, 0]


# -- tolerances ---------------------------------------------------------------------

# No train cell runs this block (PERF.md §7: the scan's backward pass), so no cell uses the two
# loss limits and they have no readings: they are here because `lib/blocks.py` asks every block
# for them (as it asks for `greedy` and `compare_greedy`, which this cell does not use either),
# at the dense block's values. A train cell of this block brings its own.
LOSS_ABS_TOL = 1.5e-3
TOKEN_LOSS_RMS_TOL = 5e-2

# The serve cell. The engine multiplies in bfloat16 with float32 accumulation, keeps K, V and the
# convolution's inputs in bfloat16 and the recurrent state in float32; the reference is float32
# throughout. Weights and cache fill 11.7 of the chip's 16 GB, so the reference reads the server's
# own tree (`LLMServer.weights()`) and scores the sequences the server generated (`score`): at
# every scored position the reference's choice given the same tokens before it. Two sets a run,
# each held to both limits on its own (`drivers/serve_closed_long.py`): MAX_PROBES probes of
# 1280 + 16 tokens sent before the window (128 positions), and after the window three of the
# requests it finished over their last 128 generated positions (384).
# The logits are small numbers: the head is the embedding again, drawn at 0.004 (`assumed`), so a
# logit's standard deviation over the vocabulary is about sqrt(2048) * 0.004 / 8 = 0.023, and the
# limits below are in that unit, not in the dense block's.
# Readings on the chip (my chip run, PR 32; PERF.md §6 gives the runs; `tools/calibrate_granite_hybrid.py`).
# A logit's standard deviation over the vocabulary reads 0.0225; the reference's margin (largest
# logit less the second) 0.0003 at the tenth percentile, 0.003 to 0.004 in the median, 0.010 to
# 0.012 at the ninetieth; the input token's own row lies 1.7 standard deviations over the rest
# and is the reference's choice at under 1% of positions.
# Sound, the engine's ids scored by this reference: they differ from the reference's at one
# position in eight, always at small margins; the mean over a set of how far the server's id lies
# under the reference's largest logit (0 where they agree) reads 0.00005 to 0.00024 over the 128
# probe positions of eleven weight seeds and 0.00011 to 0.00016 over a window sample's 384; the largest at
# any one position 0.0048. (This reference with bfloat16 operands, read against itself in float32: a logit's rms
# 0.0006, mean 0.00003, largest 0.0017: a fifth of the engine's, whose bfloat16 also rounds the
# convolution's inputs, K, V and what the scan's products read of the state.)
# Control, this reference with both operands of every matrix product rounded to float8 e4m3 (each
# tensor scaled), its own ids scored the same way: a logit's rms 0.0115 (half a standard deviation),
# mean 0.0113, 0.0129 and 0.0135 over the probes' 128 positions of three weight seeds, 0.0132 and 0.0134 over
# 256 positions of two requests of the window's sizes; largest 0.044 to 0.050.
# MEAN_DEFICIT_TOL 0.0013: between the largest sound reading (0.00024) and the smallest control
# reading (0.0113), 5 times the one and 9 times under the other (their geometric middle is 0.0016):
# the control fails it every time. NEAR_TIE_MARGIN 0.015: no id the server chose may lie further under the reference's largest
# logit; three times the largest sound reading at any position (0.0048), two thirds of a logit's
# standard deviation. It is there for a wrong function (a missing term, a state carried from the
# slot's last request, padding that steps), which fails it at once; the control fails it too (its
# largest in every set is over 0.04), though only the mean has to catch the control.
# A bfloat16 recurrent state (this reference, the state rounded after every step by `reduce_precision`,
# since the chip's compiler drops a pair of converts) reads 0.00028 over 128 probe positions, largest
# 0.0036: inside the sound range. After 1300 steps a rounded state moves an id no more than bfloat16
# products do, so this comparison does not hold the state's type; `tests/test_chip_compile.py` does
# (the compiled update is float32).
NEAR_TIE_MARGIN = 0.015
MEAN_DEFICIT_TOL = 0.0013
MIN_COMPARED_POSITIONS = 12
MAX_PROBES = 8


def compare_greedy(ref_ids, ref_margins, got_ids) -> tuple:
    """(agrees, compared) of a walk beside the reference's own greedy ids (`greedy`): whether
    `got_ids` parts from them nowhere but at a near-tie, and at how many positions of a clear
    margin the two were equal before that. (The harness's form; the cell uses `compare_scored`.)"""
    compared = 0
    for rid, margin, gid in zip(ref_ids, ref_margins, got_ids):
        if int(rid) != int(gid):
            return margin < NEAR_TIE_MARGIN, compared
        if margin >= NEAR_TIE_MARGIN:
            compared += 1
    return True, compared


def compare_scored(ref_ids, ref_margins, got_ids, deficits) -> tuple:
    """(agrees, compared, parted) for ids scored by `score`: every position is compared, and
    the server's id may lie no further under the reference's largest logit than NEAR_TIE_MARGIN
    (so it is the reference's wherever the reference's margin is that large); `parted` lists
    the margins where the ids differ."""
    parted = [float(m) for r, m, g in zip(ref_ids, ref_margins, got_ids) if int(r) != int(g)]
    return all(d <= NEAR_TIE_MARGIN for d in deficits), len(deficits), parted
