"""The plain reference of the `laguna` block: the forward pass in `jax.numpy`, float32, true
float32 matmuls. No kernel, no cache, no ring, and nothing imported from the program: it reads the
program's parameter tree and the configuration file's `model` keys, and decides `correct`. The
equations (poolside/Laguna-S-2.1 `config.json`, `model_type` laguna; what the config does not say
is listed under `assumed` in `configs/laguna-s-2.1.json`), every norm an RMSNorm with its own gain:

    x = embedding[tokens]
    for each layer, of kind full (H = n_heads) or sliding (H = swa_n_heads), heads of head_width:
      h = norm_attn(x)
      q = h W_q as H heads;  k = h W_k, v = h W_v as n_kv_heads;  query head j reads KV head j // (H / n_kv_heads)
      rotary (rotate-half) on the first r values of every head of q and k, the rest passed through:
        sliding: r = head_width, f_i = swa_rope_theta^(-2i/r), cos and sin as they are
        full:    r = partial_rotary_factor x head_width, f_i = rope_theta^(-2i/r); YaRN over those r values:
                 d(n) = r ln(window0 / (2 pi n)) / (2 ln rope_theta), low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
                 t_i = clip((i - low) / (high - low), 0, 1), inv_i = f_i (1 - t_i) + (f_i / factor) t_i;
                 cos and sin times attention_factor
      o_j[t] = softmax_s(q_j[t] . k[s] / sqrt(head_width)) v[s] over s <= t (full) or t - sliding_window < s <= t
               (sliding: a literal mask over all positions)
      x += concat_j(sigmoid(h W_g)_j o_j) W_o
      m = norm_mlp(x)
      layer < first_k_dense:  f = (silu(m Wg) * (m Wu)) Wd
      else: p = softmax(m W_r) in float32 over all n_routed_experts_total; the experts_per_token largest (a literal
            sort); weights p_i / (sum of the chosen p) x routed_scaling_factor;
            f = sum over the chosen experts THIS CHIP HOLDS of weight_i E_i(m) + E_shared(m)
      x += f
    logits = norm_final(x) lm_head

The chip holds experts [first_expert, first_expert + n_routed_experts) of each layer's
n_routed_experts_total; a pair routed to an absent expert adds nothing, here as in the program
(`stands_for` in the file).

KV heads are taken one at a time and queries `q_block` at a time, everything that is a function of
one row (the projections, the feed-forward sub-layer) some blocks of rows at a time, a gated
product's inner width `COLUMNS` columns at a time, and an expert's tokens within such rows gathered
into a fixed number of rows (falling back to every token if the busiest expert has more), only so
that a request of 29k tokens fits a chip beside the server's weights and cache (12.6 of 16 GB); the
mathematics is the same for any block. Tolerances are at the bottom, with their readings.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax.core import meta

COLUMNS = 2048   # columns of a gated product's inner width taken at a time


def plain_tree(params):
    """The program's tree without flax's partitioning boxes (this block's has none)."""
    return meta.unbox(params)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def rotary_table(cfg: dict, full: bool) -> tuple:
    """(r, inverse frequencies [r / 2], what cos and sin are multiplied by) of a layer's kind."""
    hd = cfg["head_width"]
    if not full:
        half = hd // 2
        return hd, cfg["swa_rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half), 1.0
    r = int(hd * cfg.get("partial_rotary_factor", 1.0))
    half, theta = r // 2, cfg["rope_theta"]
    f = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    sc = dict(cfg.get("rope_scaling") or {})
    if not sc:
        return r, f, 1.0

    def d(n):
        return r * math.log(sc["original_max_position_embeddings"] / (2 * math.pi * n)) / (2 * math.log(theta))

    low, high = max(math.floor(d(sc.get("beta_fast", 32))), 0), min(math.ceil(d(sc.get("beta_slow", 1))), r - 1)
    t = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return r, f * (1.0 - t) + f / float(sc["factor"]) * t, float(sc.get("attention_factor", 1.0))


def _rope(x, positions, table: tuple):
    """x: [S, H, D]; the first r values of a head rotate-half over pairs (i, i + r/2), the rest as they are."""
    r, inv, factor = table
    half = r // 2
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * factor)[:, None, :], (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def _rows(a, start, n):
    return jax.lax.dynamic_slice_in_dim(a, start, n, axis=0)


def _row_blocks(n_blocks: int) -> int:
    """Blocks of `q_block` rows the row-wise parts take at a time: the most, up to 8, that divide the sequence."""
    return max(k for k in range(1, 9) if n_blocks % k == 0)


def _by_rows(f, x, rows: int):
    """`f` over `x` [S, ...] `rows` rows at a time (S a multiple of it); f returns an array or a tuple of arrays."""
    out = jax.lax.map(f, x.reshape((x.shape[0] // rows, rows) + x.shape[1:]))
    return jax.tree_util.tree_map(lambda a: a.reshape((x.shape[0],) + a.shape[2:]), out)


def _attention(p, h, cfg: dict, full: bool, q_block: int, op, window: int):
    """h: [S, hidden], the sub-layer's normed input, S a multiple of q_block. Loops (KV heads one at
    a time, queries by block) only so that 29k tokens fit; every score is the equations' own, and a
    sliding layer's window a mask over every position."""
    S = h.shape[0]
    H, Hkv, hd = cfg["n_heads"] if full else cfg["swa_n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    G, table, pos = H // Hkv, rotary_table(cfg, full), jnp.arange(S)
    hh = op(h)
    w_q, w_o = p["q"]["kernel"].reshape(-1, Hkv, G * hd), p["o"]["kernel"].reshape(Hkv, G * hd, -1)
    w_k, w_v = p["k"]["kernel"].reshape(-1, Hkv, hd), p["v"]["kernel"].reshape(-1, Hkv, hd)
    gate = jax.nn.sigmoid(hh @ op(_f32(p["g"]["kernel"]))).reshape(S, Hkv, G)

    def kv_head(j, out):
        q = op(_rope((hh @ op(_f32(w_q[:, j]))).reshape(S, G, hd), pos, table))
        k = op(_rope((hh @ op(_f32(w_k[:, j])))[:, None], pos, table)[:, 0])
        v = op(hh @ op(_f32(w_v[:, j])))

        def block(b):
            s0 = b * q_block
            back = (s0 + jnp.arange(q_block))[:, None] - pos[None, :]
            seen = (back >= 0) if full else (back >= 0) & (back < window)
            s = jnp.einsum("sgd,td->gst", _rows(q, s0, q_block), k) / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gst,td->sgd", op(pr), v)

        o = jax.lax.map(block, jnp.arange(S // q_block)).reshape(S, G, hd)
        o = o * jax.lax.dynamic_index_in_dim(gate, j, axis=1, keepdims=False)[..., None]
        return out + op(o.reshape(S, G * hd)) @ op(_f32(w_o[j]))

    return jax.lax.fori_loop(0, Hkv, kv_head, jnp.zeros((S, cfg["hidden"]), jnp.float32))


def _swiglu(m, gate, up, down, op):
    """(silu(m Wg) * (m Wu)) Wd as a sum over blocks of the inner width's columns. m: already `op`'s."""
    F = gate.shape[-1]
    cols = COLUMNS if F % COLUMNS == 0 else F

    def some(j, y):
        g, u = (op(_f32(jax.lax.dynamic_slice_in_dim(w, j * cols, cols, axis=1))) for w in (gate, up))
        return y + op(jax.nn.silu(m @ g) * (m @ u)) @ op(_f32(_rows(down, j * cols, cols)))

    return jax.lax.fori_loop(0, F // cols, some, jnp.zeros((m.shape[0], down.shape[-1]), jnp.float32))


def routing(m, router_kernel, k: int, scaling: float):
    """The literal router: softmax over every expert in float32, a sort, the `k` largest, renormalised
    and scaled. m: [S, D] -> (ids [S, k], weights [S, k])."""
    p = jax.nn.softmax(m @ _f32(router_kernel), axis=-1)  # float32, never the control's operand type
    ids = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(p, ids, axis=-1)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling


def _experts(p, m, cfg: dict, op, held=None):
    """The held experts' part of the routed sum, and the shared expert. `held` = (first, count) of
    the tree's experts summed, by default all the tree holds."""
    S = m.shape[0]
    first = cfg.get("first_expert", 0)
    lo, count = held or (first, cfg["n_routed_experts"])
    ids, weights = routing(m, p["router"]["kernel"], cfg["experts_per_token"], cfg.get("routed_scaling_factor", 1.0))
    sh = p["shared"]
    mm = op(m)
    y = _swiglu(mm, sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"], op)
    w_by_expert = jnp.sum(jnp.where(ids[None] == jnp.arange(lo, lo + count)[:, None, None], weights[None], 0.0), axis=-1)
    rows = S if S <= 1024 else max(512, S // 4)  # an expert's fair share is S * K / total, a twenty-fifth of S

    def add_some(e, y):  # the expert's tokens first, `rows` of them
        w_e, j = w_by_expert[e], lo - first + e
        order = jnp.argsort(w_e <= 0, stable=True)[:rows]
        out = _swiglu(mm[order], p["experts"]["gate"][j], p["experts"]["up"][j], p["experts"]["down"][j], op)
        return y.at[order].add(w_e[order][:, None] * out)

    def add_all(e, y):
        j = lo - first + e
        out = _swiglu(mm, p["experts"]["gate"][j], p["experts"]["up"][j], p["experts"]["down"][j], op)
        return y + w_by_expert[e][:, None] * out

    fits = jnp.max(jnp.sum(w_by_expert > 0, axis=-1)) <= rows
    return jax.lax.cond(fits, lambda y: jax.lax.fori_loop(0, count, add_some, y),
                        lambda y: jax.lax.fori_loop(0, count, add_all, y), y)


def forward(params, cfg: dict, tokens, q_block: int = 256, operand=None, rows=None, window=None, held=None,
            shared: bool = True):
    """tokens: [S] int32 -> logits [S, V] float32, or with `rows` = (first, count) the logits of
    those positions only (first may be traced). `operand`, where given, is applied to both
    operands of every matrix product but the router's (the control of
    `benchmark/tests/test_laguna.py` rounds them to a narrower type). `window`, where given, takes
    the place of `sliding_window` (the tests' other control). `held` = (first, count) sums those of
    the tree's experts alone and `shared=False` leaves the shared expert out (the share test).
    Call under `jax.default_matmul_precision("highest")`, as every entry point below does."""
    op = operand or (lambda a: a)
    S, eps = tokens.shape[0], cfg["norm_eps"]
    window = window or cfg["sliding_window"]
    # whole blocks of queries: a causal model's logits at a position do not depend on what follows it
    tokens = jnp.pad(tokens, (0, -S % q_block))

    x = _f32(params["embedding"][tokens])
    row_block = q_block * _row_blocks(tokens.shape[0] // q_block)
    for i in range(cfg["n_layers"]):
        lp = params[f"layer_{i}"]
        full = cfg["layer_types"][i] == "full_attention"
        x = x + _attention(lp["attn"], _rmsnorm(x, lp["attn_norm"]["scale"], eps), cfg, full, q_block, op, window)

        def feed_forward(xb, lp=lp, dense=i < cfg.get("first_k_dense", 1)):
            m, mp = _rmsnorm(xb, lp["mlp_norm"]["scale"], eps), lp["mlp"]
            if dense:
                return xb + _swiglu(op(m), mp["gate"]["kernel"], mp["up"]["kernel"], mp["down"]["kernel"], op)
            f = _experts(mp, m, cfg, op, held)
            if not shared:
                sh = mp["shared"]
                f = f - _swiglu(op(m), sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"], op)
            return xb + f

        x = _by_rows(feed_forward, x, row_block)
    x = x[:S] if rows is None else _rows(x, rows[0], rows[1])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    return op(x) @ op(_f32(params["lm_head"]["kernel"]))


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
    """Greedy-decode n_new tokens after `prompt` ([P] int32) by full forward passes over
    the whole sequence so far (padded to P + n_new: a causal model's logits at a position
    do not depend on what follows it). Returns (ids [n_new], margins [n_new]): the chosen id
    and the gap between the two largest logits at each step."""
    P = prompt.shape[0]
    buf = jnp.concatenate([prompt.astype(jnp.int32), jnp.zeros((n_new,), jnp.int32)])

    def step(j, carry):
        buf, ids, margins = carry
        with jax.default_matmul_precision("highest"):
            logits = forward(params, cfg, buf, operand=operand)[P + j - 1]
        top2 = jax.lax.top_k(logits, 2)[0]
        nxt = jnp.argmax(logits).astype(jnp.int32)
        return (buf.at[P + j].set(nxt), ids.at[j].set(nxt), margins.at[j].set(top2[0] - top2[1]))

    init = (buf, jnp.zeros((n_new,), jnp.int32), jnp.zeros((n_new,), jnp.float32))
    _, ids, margins = jax.lax.fori_loop(0, n_new, step, init)
    return ids, margins


def score(params, cfg: dict, sequence, n_last: int, operand=None, length=None, q_block: int = 256, window=None):
    """The reference's next-token choice at each of the last `n_last` positions of `sequence`
    ([S] int32), given everything before it: (ids [n_last], margins [n_last], logits of the
    sequence's own tokens there less the largest [n_last]). One full forward pass: what a
    server generated is scored position by position, so a parting at one position does not
    end the comparison at the next (the sequence scored is the server's own). `length`
    (may be traced) is where the sequence ends if `sequence` is padded beyond it, so that one
    program scores sequences of any length up to S."""
    n = sequence.shape[0] if length is None else length
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, sequence, q_block, operand, rows=(n - n_last - 1, n_last), window=window)
    top2 = jax.lax.top_k(logits, 2)[0]
    own = jnp.take_along_axis(logits, _rows(sequence, n - n_last, n_last)[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), top2[:, 0] - top2[:, 1], own - top2[:, 0]


# -- tolerances ---------------------------------------------------------------------

# No train cell runs this block (16 bytes a parameter gives no cut inside the guide's floors), so no
# cell uses the two loss limits and they have no readings: they are here because `lib/blocks.py`
# asks every block for them (as it asks for `greedy` and `compare_greedy`, which this cell does
# not use either), at the dense block's values. A train cell of this block brings its own.
LOSS_ABS_TOL = 1.5e-3
TOKEN_LOSS_RMS_TOL = 5e-2

# The serve cell. The engine multiplies in bfloat16 with float32 accumulation and keeps its slabs
# and rings in bfloat16; the reference is float32 throughout. Weights and cache fill 12.6 of the
# chip's 16 GB, so the reference reads the server's own tree (`LLMServer.weights()`) and scores the
# sequences the server generated (`score`): at every scored position the reference's choice given
# the same tokens before it. Two sets a run, each held to both limits on its own
# (`drivers/serve_closed_long.py`): 8 probes of 2304 + 16 tokens sent before the window (128
# positions), and after the window three of the requests it finished, one of them past 16k tokens,
# over their last 64 generated positions (192). Two limits, for `dots3`'s reason: an id at one
# position is a coarse reading (a router's tenth expert against its eleventh flips under bfloat16
# and moves one id far), a mean over a set is a fine one.
# Readings on the chip (my chip run, PR 46; PERF.md section 6 gives the runs; a logit's standard
# deviation is 1.00). Sound, the engine's ids over nineteen weight seeds, 38 sets of the cell's own
# (128 probe positions, 192 of a window's sample) and six more of 128 positions after 1500 and 16419
# tokens (`tools/calibrate_laguna.py control --long`): the mean of how far the server's id lies under
# the reference's largest logit 0.00499 to 0.02610 over a set (the largest 0.02610, 0.02569, 0.02115,
# 0.01922; the median 0.0127); the largest at one position 0.9426 (then 0.8483, 0.7933, 0.7752). The
# ids differ from the reference's at 9 to 16% of the positions, nearly all at near-ties: ten experts of
# 256 are chosen from softmax probabilities of unit-variance logits, the tenth and the eleventh lie
# 0.05 apart where bfloat16 activations move a logit by 0.004, so some tenth of the tokens swap an
# expert in a layer, and where a swapped expert is one of the 64 held the position's logits move by
# a twentieth to a half of their spread (the block's own programs in float32 on the chip lie 0.002
# rms from this reference, `tools/calibrate_laguna.py float32`: rounding, not the function).
# Control, this reference with both operands of every matrix product but the router's rounded to
# float8 e4m3 (each tensor scaled), one precision below the bfloat16 the configuration states,
# scored the same way at three weight seeds, six sets of 128: rms 0.28 a logit, ids differ at 48 to
# 54% of the positions; mean deficit 0.15219 to 0.20400; the largest at one position 1.01 to 1.72.
# Window, this reference in float32 with a window of 1024 keys for 512 (what a ring that kept or
# showed the wrong rows reads like), the same six sets: rms 0.19 to 0.20 a logit, ids differ at 32
# to 41%; mean deficit 0.05785, 0.06427, 0.07524, 0.07536, 0.08271, 0.09474; the largest at one
# position 0.60 to 0.95.
# MEAN_DEFICIT_TOL 0.042: between the largest sound reading (0.02610, 1.6 times under it; the sound
# sets' means have a median of 0.0127 and a log-spread that puts 0.042 at one set in three
# thousand) and the smallest control reading (the window's 0.05785, 1.4 times over it; the float8
# control's 0.15219 is 3.6 times over): both controls fail it in every set. No other statistic of
# the deficits parts the two better (a mean capped at 0.1, 0.2 or 0.3 a position, the share of
# positions over 0, 0.02, 0.05 or 0.1: each 2.0 to 2.4 times between the worst sound set and the
# weakest window set, as the plain mean's 2.2: PERF.md section 6), because a wide window is a weak
# fault at random weights: attention over 512 or 1024 random keys is close to a mean of values
# either way. A benchmark issue that may lengthen the probes' 16 tokens would narrow the sound
# sets' spread (PERF.md section 7).
# NEAR_TIE_MARGIN 2.0: a second, coarse net for a wrong function, not for precision: 2.1 times the
# largest sound reading at one position (0.9426 over some 6,500 scored positions, whose tail falls
# tenfold every 0.3: at 1.5 one check of the driver in a hundred would meet a position past it);
# an id drawn at random lies 3.9 under (the largest of 25088 logits over their mean). Both controls
# pass it (the float8 control's worst position 1.01 to 1.72, the window's 0.60 to 0.95) and fail by
# MEAN_DEFICIT_TOL alone, which is what the rule asks of a control (one of the cell's limits, not
# each).
# Every scored position is compared: 128 and 192 against MIN_COMPARED_POSITIONS 12, which fails a
# run whose window finished nothing to score.
NEAR_TIE_MARGIN = 2.0
MEAN_DEFICIT_TOL = 0.042
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
