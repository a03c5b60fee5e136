"""The plain reference of the `lfm2` block: the forward pass in `jax.numpy`, float32, true
float32 matmuls, every expert over every token. No kernel, no cache, no sort, no tiles, and
nothing imported from the program: it reads the program's parameter tree and the configuration
file's `model` keys, and decides `correct`. The equations (LiquidAI/LFM2-24B-A2B `config.json`,
`model_type` lfm2_moe; what the config does not give is under `assumed` in
`configs/lfm2-24b-a2b.json`):

    x = E[tokens]                                                  (no multiplier)
    for each layer i, h = rmsnorm(x)                               (the published operator_norm)
      conv (layer_types[i] == "conv"; conv_L_cache taps, no bias, no activation)
        [B | C | u] = h W_in;  g_t = B_t * u_t
        c_t = sum_j w_j g_{t - taps + 1 + j}                       depthwise, causal, zeros before the sequence
        x += (C * c) W_out
      full_attention (H heads, Hkv key and value heads, head = hidden / H)
        q = rope(rmsnorm_head(h W_q));  k = rope(rmsnorm_head(h W_k));  v = h W_v     theta, rotate-half
        o_head = softmax_{s <= t}(q_t . k_s / sqrt(head)) v_s;  x += o W_o
      m = rmsnorm(x)                                               (the published ffn_norm)
      i < first_k_dense:  x += (silu(m W_gate) * (m W_up)) W_down  (published w1, w3, w2; width mlp_dim)
      else: s = sigmoid(m W_r) in float32; the experts_per_token experts of largest s + b;
            weights s_i / (sum of the chosen s + 1e-6), times routed_scaling_factor;
            x += sum over the chosen experts of weight_i E_i(m)    (no shared expert)
    logits = rmsnorm(x) E^T                                        (the published embedding_norm; tied)

Departures from the published modelling code, none of which changes a result: the convolution's
taps are kept as [taps, hidden] (published: a Conv1d weight [hidden, 1, taps], the same numbers
transposed); the published cache keeps `conv_L_cache` gated inputs a sequence where the last
`conv_L_cache - 1` are all a step reads; the three feed-forward matrices are named gate, up, down
as the repo's other blocks name them. The published code computes the router's scores in the
activations' type; here, as in the program, they are float32 (an `assumed` entry).

Queries are taken `q_block` at a time, experts one at a time and logits at the scored rows only,
so that a request of 3072 tokens is scored beside a server whose weights and cache fill 11.4 of
the chip's 16 GB; the mathematics is the same for any block. Tolerances are at the bottom, with
their readings.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax.core import meta

ROUTING_EPS = 1e-6


def plain_tree(params):
    """The program's tree without flax's partitioning boxes (this block's has none)."""
    return meta.unbox(params)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _rows(x, first, count: int):
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)


def _rope(x, theta):
    """x: [S, H, R] at positions 0 .. S - 1, rotate-half: pairs are (i, i + R/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _conv(p, h, cfg: dict, op):
    """h: [S, D] -> [S, D]."""
    S, D = h.shape
    K = cfg.get("conv_L_cache", 3)
    bcu = op(h) @ op(_f32(p["in_proj"]["kernel"]))
    g = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), bcu[:, :D] * bcu[:, 2 * D:]])
    w = _f32(p["conv"]["kernel"])
    c = sum(w[j] * g[j:j + S] for j in range(K))
    return op(bcu[:, D:2 * D] * c) @ op(_f32(p["out_proj"]["kernel"]))


def _attention(p, h, cfg: dict, q_block: int, op):
    """h: [S, D] with S a multiple of q_block -> [S, D]; queries a block at a time."""
    S = h.shape[0]
    H, Hkv, eps = cfg["n_heads"], cfg["n_kv_heads"], cfg["norm_eps"]
    hd = cfg["hidden"] // H
    q = _rmsnorm((op(h) @ op(_f32(p["q"]["kernel"]))).reshape(S, H, hd), p["q_norm"]["scale"], eps)
    k = _rmsnorm((op(h) @ op(_f32(p["k"]["kernel"]))).reshape(S, Hkv, hd), p["k_norm"]["scale"], eps)
    q = _rope(q, cfg["rope_theta"]).reshape(S // q_block, q_block, Hkv, H // Hkv, hd)
    k = _rope(k, cfg["rope_theta"])
    v = (op(h) @ op(_f32(p["v"]["kernel"]))).reshape(S, Hkv, hd)
    pos = jnp.arange(S)

    def block(args):
        qb, first = args
        s = jnp.einsum("skgd,tkd->kgst", op(qb), op(k)) / math.sqrt(hd)
        seen = (first + jnp.arange(q_block))[:, None] >= pos[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", op(pr), op(v)).reshape(q_block, H * hd)

    o = jax.lax.map(block, (q, jnp.arange(S // q_block) * q_block)).reshape(S, H * hd)
    return op(o) @ op(_f32(p["o"]["kernel"]))


def _swiglu(m, gate, up, down, op):
    """m already passed through `op`."""
    return op(jax.nn.silu(m @ op(_f32(gate))) * (m @ op(_f32(up)))) @ op(_f32(down))


def route(p, m, cfg: dict):
    """m: [S, D] -> (ids [S, k], weights [S, k]): float32, never the control's operand type."""
    s = jax.nn.sigmoid(m @ _f32(p["router"]["kernel"]))
    _, ids = jax.lax.top_k(s + _f32(p["router"]["bias"]), cfg["experts_per_token"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + ROUTING_EPS) * cfg.get("routed_scaling_factor", 1.0)


def experts(p, m, cfg: dict, op=lambda a: a):
    """The routed sum: every expert over every token, weighted by what the router gave it (0
    where it was not chosen), one expert at a time."""
    ids, weights = route(p, m, cfg)
    mm = op(m)

    def add(e, y):
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return y + w_e[:, None] * _swiglu(mm, p["experts"]["gate"][e], p["experts"]["up"][e], p["experts"]["down"][e], op)

    return jax.lax.fori_loop(0, cfg["n_routed_experts_total"], add, jnp.zeros_like(m))


def forward(params, cfg: dict, tokens, q_block: int = 256, operand=None, rows=None):
    """tokens: [S] int32 -> logits [S, V] float32, or with `rows` = (first, count) the logits of
    those positions only (first may be traced). `operand`, where given, is applied to both
    operands of every matrix product but the router's (the control of
    `benchmark/tests/test_lfm2.py` rounds them to a narrower type). Call under
    `jax.default_matmul_precision("highest")`, as every entry point below does."""
    op = operand or (lambda a: a)
    S = tokens.shape[0]
    # whole blocks of queries: a causal model's logits at a position do not depend on what follows it
    tokens = jnp.pad(tokens, (0, -S % q_block))
    E = params["embedding"]
    x = _f32(E[tokens])
    for i in range(cfg["n_layers"]):
        lp = params[f"layer_{i}"]
        h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg["norm_eps"])
        if cfg["layer_types"][i] == "conv":
            x = x + _conv(lp["attn"], h, cfg, op)
        else:
            x = x + _attention(lp["attn"], h, cfg, q_block, op)
        m = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg["norm_eps"])
        if i < cfg.get("first_k_dense", 1):
            mlp = lp["mlp"]
            x = x + _swiglu(op(m), mlp["gate"]["kernel"], mlp["up"]["kernel"], mlp["down"]["kernel"], op)
        else:
            x = x + experts(lp["mlp"], m, cfg, op)
    x = x[:S] if rows is None else _rows(x, rows[0], rows[1])
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    return op(x) @ op(_f32(E)).T


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


def score(params, cfg: dict, sequence, n_last: int, operand=None, length=None, q_block: int = 256):
    """The reference's next-token choice at each of the last `n_last` positions of `sequence`
    ([S] int32), given everything before it: (ids [n_last], margins [n_last], logits of the
    sequence's own tokens there less the largest [n_last]). One full forward pass: what a server
    generated is scored position by position, so a parting at one position does not end the
    comparison at the next. `length` (may be traced) is where the sequence ends if `sequence` is
    padded beyond it, so that one program scores sequences of any length up to S."""
    n = sequence.shape[0] if length is None else length
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, sequence, q_block, operand, rows=(n - n_last - 1, n_last))
    top2 = jax.lax.top_k(logits, 2)[0]
    own = jnp.take_along_axis(logits, _rows(sequence, n - n_last, n_last)[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), top2[:, 0] - top2[:, 1], own - top2[:, 0]


# -- tolerances ---------------------------------------------------------------------

# No train cell runs this block (PERF.md §7: `grouped_experts` has no backward pass), so no cell
# uses the two loss limits and they have no readings: they are here because `lib/blocks.py` asks
# every block for them (as it asks for `greedy` and `compare_greedy`, which this cell does not use
# either), at the dense block's values. A train cell of this block brings its own.
LOSS_ABS_TOL = 1.5e-3
TOKEN_LOSS_RMS_TOL = 5e-2

# The serve cell. The engine multiplies in bfloat16 with float32 accumulation and keeps the residual
# stream, K, V and the convolution's inputs in bfloat16; the reference is float32 throughout.
# Weights and cache fill 11.4 of the chip's 16 GB, so the reference reads the server's own tree
# (`LLMServer.weights()`) and scores the sequences the server generated (`score`): at every scored
# position the reference's choice given the same tokens before it. Two sets a run, each held to
# both limits on its own (`drivers/serve_closed_long.py`): MAX_PROBES probes of 1280 + 16 tokens sent
# before the window (128 positions), and after the window three of the requests it finished over
# their last 128 generated positions (384).
# Readings on the chip (my chip run, PR 34; PERF.md §6 gives the runs; `tools/calibrate_lfm2.py`).
# A logit's standard deviation over the vocabulary reads 0.904; the reference's margin (largest
# logit less the second) 0.02 to 0.03 at the tenth percentile, 0.12 to 0.17 in the median, 0.40 to
# 0.48 at the ninetieth; the input token's own row lies 0.2 to 0.4 standard deviations over the rest
# and is the reference's choice at 0.0% of positions.
# What rounding does to this block: eight layers in a row choose 4 of 64 experts by a hard top-k, and
# an expert whose score is near the fourth's flips under bfloat16; the position then adds another
# expert's output, later routers see another input and flip more, and its logits move by a large
# part of their spread. So how far an id lies under the reference's largest logit is 0 at most
# positions and has a heavy tail: this reference with bfloat16 operands, read against itself in
# float32, has a logit's rms of 0.03 to 0.19 by the sequence, a mean deficit of 0.013 to 0.094 over a
# set and a largest of 0.70 to 1.48. The largest over a set is therefore no statistic to put a limit
# on (twice the largest of 2,000 positions is passed by some position of 20,000), and the second
# limit is on a share.
# Sound, the engine's ids scored by this reference, 36 sets of 15 weight seeds: the mean over a set
# of how far the server's id lies under (0 where they agree) reads 0.031 to 0.121 over the probes'
# 128 positions, 0.067 to 0.113 over a window sample's 384, 0.089 to 0.113 over 256 positions of a
# 512- and a 2048-token request; the ids differ at a third of the positions, at margins to 0.65; the
# share of one sequence's 128 scored positions that lie further under than 0.7 reads 0.0 to 7.0%
# (9 of 128), of the probes' 128 together 0.0 to 4.7%; the largest at any one position 1.70.
# Control, this reference with both operands of every matrix product but the router's rounded to
# float8 e4m3 (each tensor scaled), its own ids scored the same way: a logit's rms 0.52 to 0.60;
# mean 0.741, 0.751, 0.698 over the probes' 128 positions of three weight seeds and 0.619, 0.690,
# 0.713 over 256 positions of two requests of the window's sizes; the share further under than 0.7
# 43.0 to 47.3% of a set; largest 2.0 to 3.4.
# MEAN_DEFICIT_TOL 0.27: between the largest sound reading (0.121) and the smallest control reading
# (0.619), 2.2 times the one and 2.3 times under the other (their geometric middle): the control
# fails it every time. NEAR_TIE_MARGIN 0.7 with FAR_SHARE_TOL 0.25: of one scored sequence's
# positions at most a quarter may lie further under the reference's largest logit than 0.7 (three
# quarters of a logit's standard deviation: further than that an id is the reference's at no
# margin the reference has at nine positions in ten): 3.6 times the largest sound share (7.0%) and
# 1.7 times under the smallest control share (43.0%). At a probe's 16 positions that is 4 of them,
# which a sound probe passes with 1.5 to 4% of its positions that far (5 of 16 at 4%: 0.05% of
# probes) and the control's probes fail nine times in ten (45%: 4 or fewer of 16 at 8.5%). It is
# there for a wrong function (a missing term, a window carried from the slot's last request, padding
# shifted in), which puts nearly every position that far: an id drawn at random lies 3.9 under, the
# largest of 65536 logits over their mean. The control fails both limits.
NEAR_TIE_MARGIN = 0.7
FAR_SHARE_TOL = 0.25
MEAN_DEFICIT_TOL = 0.27
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
    """(agrees, compared, parted) for the ids of one sequence scored by `score`: every position is
    compared, and of them at most FAR_SHARE_TOL may lie further under the reference's largest
    logit than NEAR_TIE_MARGIN (the driver holds the mean over all of a set's sequences to
    MEAN_DEFICIT_TOL besides, and lists the positions that lie that far under whether or not they
    are too many); `parted` lists the margins where the ids differ."""
    parted = [float(m) for r, m, g in zip(ref_ids, ref_margins, got_ids) if int(r) != int(g)]
    far = sum(d > NEAR_TIE_MARGIN for d in deficits)
    return far <= FAR_SHARE_TOL * len(deficits), len(deficits), parted
