"""The plain reference of the `pangu_moe` block: the forward pass in `jax.numpy`, float32,
true float32 matmuls. No kernel, no cache, and nothing imported from the program: it
reads the program's parameter tree and the configuration file's `model` keys, and
decides `correct`. The equations (FreedomIntelligence/openPangu-Ultra-MoE-718B `config.json`
and its `modeling_openpangu_moe.py`; what the config does not say is listed under `assumed`
in `configs/openpangu-ultra-moe-718b.json`), every norm an RMSNorm with its own gain:

    x = embedding[tokens]
    for each layer:
      a      = norm_in(x)
      c_q    = norm_qa(a W_qa);  q = c_q W_qb -> H x [nope | rope], rotary on rope
      [c_kv | k_r] = a W_kva;  c_kv = norm_kva(c_kv);  k_r rotary, one for all heads
      [k_nope | v] = c_kv W_kvb
      o_head[t] = softmax_{s <= t}((q_nope[t] . k_nope[s] + q_rope[t] . k_r[s]) / sqrt(nope + rope)) v[s]
      x     += norm_post_attn(concat_heads(o_head) W_o)            (the two post-norms: the published sandwich_norm)
      m      = norm_pre_mlp(x)
      layer < first_k_dense:  f = (silu(m Wg) * (m Wu)) Wd
      else: s = sigmoid(m W_r) in float32; the experts_per_token experts of largest s;
            weights s_i / (sum of the chosen s + 1e-20), times routed_scaling_factor;
            f = sum over the chosen experts THIS CHIP HOLDS of weight_i E_i(m) + E_shared(m)
      x     += norm_post_mlp(f)
    logits = norm_final(x) lm_head

The chip holds experts [first_expert, first_expert + n_routed_experts) of each layer's
n_routed_experts_total; a pair routed to an absent expert adds nothing, here as in the program,
and the post-norm takes that partial sum (`stands_for` in the file).

Heads are taken `HEAD_GROUP` at a time and queries `q_block` at a time, everything that is a
function of one row (the latents, the feed-forward sub-layer with its norms) some blocks of rows
at a time, a gated product's inner width `COLUMNS` columns at a time (a sum over the columns'
blocks), and an expert's tokens within such rows gathered into a fixed number of rows (falling
back to every token if the busiest expert has more), only so that a request of 25k tokens at
7680 wide fits a chip beside the server's weights and cache (12.1 of 16 GB); the mathematics is the same for any block. Tolerances are at
the bottom, with their readings.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax.core import meta

ROUTING_EPS = 1e-20
HEAD_GROUP = 8   # heads whose keys and values are expanded at a time
COLUMNS = 2048   # columns of a gated product's inner width taken at a time


def plain_tree(params):
    """The program's tree without flax's partitioning boxes (this block's has none)."""
    return meta.unbox(params)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, positions, theta):
    """x: [S, H, R], rotate-half: pairs are (i, i + R/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rows(a, start, n):
    return jax.lax.dynamic_slice_in_dim(a, start, n, axis=0)


def _row_blocks(n_blocks: int) -> int:
    """Blocks of `q_block` rows the row-wise parts take at a time: the most, up to 8, that divide the sequence."""
    return max(k for k in range(1, 9) if n_blocks % k == 0)


def _by_rows(f, x, rows: int):
    """`f` over `x` [S, ...] `rows` rows at a time (S a multiple of it); f returns an array or a tuple of arrays."""
    out = jax.lax.map(f, x.reshape((x.shape[0] // rows, rows) + x.shape[1:]))
    return jax.tree_util.tree_map(lambda a: a.reshape((x.shape[0],) + a.shape[2:]), out)


def _attention(p, lp, x, norm, cfg: dict, q_block: int, op):
    """x: [S, hidden], S a multiple of q_block; `norm` the layer's input norm. Loops (the latents by
    blocks of rows, heads by group, queries by block) only so that 25k tokens fit; every score is
    the equations' own, every row up to the query's."""
    S, eps = x.shape[0], cfg["norm_eps"]
    H, kv_rank = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, v_dim, theta = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["rope_theta"]
    hg = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    pos = jnp.arange(S)

    def latents(xb):
        aa = op(norm(lp, "attn_norm", xb))
        c_q = op(_rmsnorm(aa @ op(_f32(p["q_a"]["kernel"])), p["q_norm"]["scale"], eps))
        kv = aa @ op(_f32(p["kv_a"]["kernel"]))
        return c_q, op(_rmsnorm(kv[:, :kv_rank], p["kv_norm"]["scale"], eps)), kv[:, kv_rank:]

    c_q, c_kv, k_r = _by_rows(latents, x, q_block * _row_blocks(S // q_block))
    k_r = _rope(k_r[:, None], pos, theta)
    w_q = p["q_b"]["kernel"].reshape(-1, H, nope + rope)  # in the tree's own type: a group at a time is made float32

    def heads(g, out):
        h0 = g * hg
        q = jnp.einsum("sr,rhd->shd", c_q, op(_f32(jax.lax.dynamic_slice_in_dim(w_q, h0, hg, axis=1))))
        q = op(jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], axis=-1))
        kvx = jnp.einsum("sc,chd->shd", c_kv, op(_f32(jax.lax.dynamic_slice_in_dim(p["kv_b"]["kernel"], h0, hg, axis=1))))
        k = op(jnp.concatenate([kvx[..., :nope], jnp.broadcast_to(k_r, (S, hg, rope))], axis=-1))
        v = op(kvx[..., nope:])

        def block(b):
            s0 = b * q_block
            back = (s0 + jnp.arange(q_block))[:, None] - pos[None, :]
            s = jnp.einsum("shd,khd->hsk", _rows(q, s0, q_block), k)
            pr = jax.nn.softmax(jnp.where((back >= 0)[None], s / math.sqrt(nope + rope), -jnp.inf), axis=-1)
            return jnp.einsum("hsk,khd->shd", op(pr), v)

        o = jax.lax.map(block, jnp.arange(S // q_block)).reshape(S, hg, v_dim)
        return out + jnp.einsum("shd,hde->se", op(o), op(_f32(_rows(p["o"]["kernel"], h0, hg))))

    return jax.lax.fori_loop(0, H // hg, heads, jnp.zeros((S, cfg["hidden"]), jnp.float32))


def _swiglu(m, gate, up, down, op):
    """(silu(m Wg) * (m Wu)) Wd as a sum over blocks of the inner width's columns. m: already `op`'s."""
    F = gate.shape[-1]
    cols = COLUMNS if F % COLUMNS == 0 else F

    def some(j, y):
        g, u = (op(_f32(jax.lax.dynamic_slice_in_dim(w, j * cols, cols, axis=1))) for w in (gate, up))
        return y + op(jax.nn.silu(m @ g) * (m @ u)) @ op(_f32(_rows(down, j * cols, cols)))

    return jax.lax.fori_loop(0, F // cols, some, jnp.zeros((m.shape[0], down.shape[-1]), jnp.float32))


def _experts(p, m, cfg: dict, op):
    """The held experts' part of the routed sum, and the shared expert."""
    S = m.shape[0]
    E, first, K = cfg["n_routed_experts"], cfg.get("first_expert", 0), cfg["experts_per_token"]
    s = jax.nn.sigmoid(m @ _f32(p["router"]["kernel"]))  # float32, never the control's operand type
    chosen, ids = jax.lax.top_k(s, K)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + ROUTING_EPS) * cfg.get("routed_scaling_factor", 1.0)
    sh = p["shared"]
    mm = op(m)
    y = _swiglu(mm, sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"], op)
    held = jnp.arange(first, first + E)
    w_by_expert = jnp.sum(jnp.where(ids[None] == held[:, None, None], weights[None], 0.0), axis=-1)  # [E, S]
    rows = S if S <= 1024 else max(512, S // 4)  # an expert's fair share is S * K / total, a thirty-second of S

    def add_some(e, y):  # the expert's tokens first, `rows` of them
        w_e = w_by_expert[e]
        order = jnp.argsort(w_e <= 0, stable=True)[:rows]
        out = _swiglu(mm[order], p["experts"]["gate"][e], p["experts"]["up"][e], p["experts"]["down"][e], op)
        return y.at[order].add(w_e[order][:, None] * out)

    def add_all(e, y):
        out = _swiglu(mm, p["experts"]["gate"][e], p["experts"]["up"][e], p["experts"]["down"][e], op)
        return y + w_by_expert[e][:, None] * out

    fits = jnp.max(jnp.sum(w_by_expert > 0, axis=-1)) <= rows
    return jax.lax.cond(fits, lambda y: jax.lax.fori_loop(0, E, add_some, y),
                        lambda y: jax.lax.fori_loop(0, E, add_all, y), y)


def forward(params, cfg: dict, tokens, q_block: int = 256, operand=None, rows=None, drop=None):
    """tokens: [S] int32 -> logits [S, V] float32, or with `rows` = (first, count) the logits
    of those positions only (first may be traced). `operand`, where given, is applied to
    both operands of every matrix product but the router's (the control of
    `benchmark/tests/test_pangu_moe.py` rounds them to a narrower type). `drop` names one of a
    layer's four norms to leave out (the tests' other control). Call under
    `jax.default_matmul_precision("highest")`, as every entry point below does."""
    op = operand or (lambda a: a)
    S, eps = tokens.shape[0], cfg["norm_eps"]
    # whole blocks of queries: a causal model's logits at a position do not depend on what follows it
    tokens = jnp.pad(tokens, (0, -S % q_block))

    def norm(lp, name, y):
        return y if name == drop else _rmsnorm(y, lp[name]["scale"], eps)

    x = _f32(params["embedding"][tokens])
    row_block = q_block * _row_blocks(tokens.shape[0] // q_block)
    for i in range(cfg["n_layers"]):
        lp = params[f"layer_{i}"]
        x = x + norm(lp, "attn_post_norm", _attention(lp["attn"], lp, x, norm, cfg, q_block, op))

        def feed_forward(xb, lp=lp, dense=i < cfg.get("first_k_dense", 1)):
            m, mp = norm(lp, "mlp_norm", xb), lp["mlp"]
            if dense:
                f = _swiglu(op(m), mp["gate"]["kernel"], mp["up"]["kernel"], mp["down"]["kernel"], op)
            else:
                f = _experts(mp, m, cfg, op)
            return xb + norm(lp, "mlp_post_norm", f)

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


def score(params, cfg: dict, sequence, n_last: int, operand=None, length=None, q_block: int = 256):
    """The reference's next-token choice at each of the last `n_last` positions of `sequence`
    ([S] int32), given everything before it: (ids [n_last], margins [n_last], logits of the
    sequence's own tokens there less the largest [n_last]). One full forward pass: what a
    server generated is scored position by position, so a parting at one position does not
    end the comparison at the next (the sequence scored is the server's own). `length`
    (may be traced) is where the sequence ends if `sequence` is padded beyond it, so that one
    program scores sequences of any length up to S."""
    n = sequence.shape[0] if length is None else length
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, sequence, q_block, operand, rows=(n - n_last - 1, n_last))
    top2 = jax.lax.top_k(logits, 2)[0]
    own = jnp.take_along_axis(logits, _rows(sequence, n - n_last, n_last)[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), top2[:, 0] - top2[:, 1], own - top2[:, 0]


# -- tolerances ---------------------------------------------------------------------

# No train cell runs this block (14 bytes a parameter would need 56 GB for the cut), so no cell
# uses the two loss limits and they have no readings: they are here because `lib/blocks.py`
# asks every block for them (as it asks for `greedy` and `compare_greedy`, which this cell does
# not use either), at the dense block's values. A train cell of this block brings its own.
LOSS_ABS_TOL = 1.5e-3
TOKEN_LOSS_RMS_TOL = 5e-2

# The serve cell. The engine multiplies in bfloat16 with float32 accumulation and keeps its cache
# in bfloat16; the reference is float32 throughout. Weights and cache fill 12.1 of the chip's 16 GB,
# so the reference reads the server's own tree (`LLMServer.weights()`) and scores the sequences the
# server generated (`score`): at every scored position the reference's choice given the same tokens
# before it. Two sets a run, each held to both limits on its own (`drivers/serve_closed_long.py`):
# 8 probes of 4352 + 16 tokens sent before the window (128 positions), and after the window three
# of the requests it finished, one of them past 16k tokens, over their last 128 generated
# positions (384). Two limits, for `dots3`'s reason: an id at one position is a coarse reading (a
# router's eighth expert against its ninth flips under bfloat16 and moves one id far), a mean over
# a set is a fine one.
# Readings on the chip (my chip run, PR 39; PERF.md section 6 gives the runs; a logit's standard
# deviation is 1.00). Sound, the engine's ids over fourteen weight seeds, 28 sets: the mean of how far
# the server's id lies under the reference's largest logit 0.00000 to 0.00244 over a set of 128
# probe positions (then 0.00235, 0.00221) and 0.00023 to 0.00161 over a window sample's 384;
# 0.00003 to 0.00153 over four sets of 128 at 6144 and 16419 tokens; the largest at one position
# 0.2843 (then 0.2830, 0.2282, 0.2262, 0.2226). Control, this reference with both operands of every
# matrix product but the router's rounded to float8 e4m3 (each tensor scaled), one precision below
# the bfloat16 the configuration states, scored the same way at two weight seeds: rms 0.14 to 0.17 a
# logit, ids differ at two positions in five; mean deficit 0.06363 and 0.05866 over the 128 probe
# positions, 0.03901, 0.02157, 0.05811 and 0.02212 over 128 generated positions after 6144 and
# 16419 tokens; the largest at one position 0.6194. Fault, this reference in float32 with one of a
# layer's four norms left out, a wrong function at the cell's own widths, over the 128 probe
# positions of one weight seed (`tools/calibrate_pangu_moe.py control --drop <norm>`): mean deficit
# 0.06445 without `mlp_norm`, 0.23875 without `attn_norm`, 0.26929 without `mlp_post_norm`, 2.81179
# without `attn_post_norm`; the largest at one position 0.5142, 1.1749, 1.3066 and 4.6695.
# MEAN_DEFICIT_TOL 0.007: between the largest sound reading (0.00244, 2.9 times under it) and the
# smallest control reading (0.02157, 3.1 times over it); the control and every fault fail it in
# every set, the faults by 9 to 400 times.
# NEAR_TIE_MARGIN 0.7: between the largest sound reading at one position (0.2843, 2.5 times under
# it) and the smallest reading of a fault that the margin is there for (1.1749 without `attn_norm`,
# 1.7 times over it): three of the four left-out norms fail it as well as the mean. It is a second,
# coarse net for a wrong function, not for precision: the float8 control (0.6194) and the mildest
# fault (`mlp_norm`, 0.5142) pass it and fail by MEAN_DEFICIT_TOL alone, which is what the rule asks
# of a control (one of the cell's limits, not each). It is not set nearer the sound readings because
# the largest of 128 or 384 deficits is the statistic that a fresh seed moves most (a near-tied
# expert flipped under bfloat16).
# Every scored position is compared: 128 and 384 against MIN_COMPARED_POSITIONS 12, which fails a
# run whose window finished nothing to score.
NEAR_TIE_MARGIN = 0.7
MEAN_DEFICIT_TOL = 0.007
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
