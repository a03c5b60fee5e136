"""The plain reference of the `dots3` block: the forward pass in `jax.numpy`, float32,
true float32 matmuls. No kernel, no cache, and nothing imported from the program: it
reads the program's parameter tree and the configuration file's `model` keys, and
decides `correct`. The equations (dots-studio/dots3-note-prev `config.json`; DeepSeek-V3
latent attention, the DeepSeek-V3.2 indexer, `noaux_tc` sigmoid routing; what the config
names and does not define is listed under `assumed` in `configs/dots3-note-prev.json`):

    x = embedding[tokens]
    for each layer i, h = rmsnorm(x):
      full layer (layer_types[i] == "full_attention"; H heads, rope theta)
        c_q  = r_q rmsnorm(h W_qa);  q = c_q W_qb -> H x [nope | rope], rotary on rope
        [c_kv | k_r] = h W_kva;  c_kv = r_kv rmsnorm(c_kv);  k_r rotary, one for all heads
        [k_nope | v] = c_kv W_kvb                            (r = sqrt(hidden / rank))
        q_I = c_q W_qI (Hi x Di); k_I = layernorm(h W_kI); rotary on the first `rope` of each
        w = (h W_w) Hi^-1/2 Di^-1/2
        I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t
        S_t = the index_topk positions of largest I[t, .]   (all of them while t < index_topk)
        o_head = softmax_{s in S_t}((q_nope . k_nope[s] + q_rope . k_r[s]) / sqrt(nope + rope)) v[s]
        x += concat_heads(sigmoid(h W_g)_head o_head) W_o
      sliding layer: the same at the swa_* sizes, no indexer, s with 0 <= t - s < sliding_window
      m = rmsnorm(x)
      layer < first_k_dense:  x += (silu(m Wg) * (m Wu)) Wd
      else: s = sigmoid(m W_r) in float32; the experts_per_token experts of largest s + b;
            weights s_i / sum of the chosen s, times routed_scaling_factor;
            x += sum over the chosen experts THIS CHIP HOLDS of weight_i E_i(m) + E_shared(m)
    logits = rmsnorm(x) lm_head

The chip holds experts [first_expert, first_expert + n_routed_experts) of each layer's
n_routed_experts_total: what the absent experts would add is left out, here as in the
program, and that partial sum goes on to the next layer (`stands_for` in the file).

Queries are taken `q_block` at a time, heads `HEAD_GROUP` at a time, a sliding layer's
keys by the band a block of queries can see, the dense layer's rows by blocks, and an
expert's tokens gathered into a fixed number of rows (falling back to every token if a
layer's busiest expert has more), only so that a request of 25k tokens fits a chip beside
the server's weights; the mathematics is the same for any block. Tolerances are at the
bottom, with their readings.
"""

from __future__ import annotations

import math

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


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _rope(x, positions, theta):
    """x: [S, H, R], rotate-half: pairs are (i, i + R/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dims(cfg: dict, full: bool) -> dict:
    if full:
        return dict(H=cfg["n_heads"], q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                    nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
                    theta=cfg["rope_theta"])
    return dict(H=cfg["swa_n_heads"], q_rank=cfg["swa_q_lora_rank"], kv_rank=cfg["swa_kv_lora_rank"],
                nope=cfg["swa_qk_nope_head_dim"], rope=cfg["swa_qk_rope_head_dim"], v=cfg["swa_v_head_dim"],
                theta=cfg["swa_rope_theta"])


HEAD_GROUP = 16  # heads whose keys and values are expanded at a time


def _rows(a, start, n):
    return jax.lax.dynamic_slice_in_dim(a, start, n, axis=0)


def _selection(ix, c_q, hh, cfg: dict, theta: float, q_block: int, op):
    """The indexer's choice for every block of queries: [S / q_block, q_block, S] bool."""
    S, eps, R = hh.shape[0], cfg["norm_eps"], cfg["qk_rope_head_dim"]
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    pos = jnp.arange(S)
    w_q = op(_f32(ix["q"]["kernel"]))
    k_i = _layernorm(hh @ op(_f32(ix["k"]["kernel"])), ix["k_norm"]["scale"], ix["k_norm"]["bias"], eps)
    k_i = op(jnp.concatenate([_rope(k_i[:, None, :R], pos, theta)[:, 0], k_i[:, R:]], axis=-1))
    w = (hh @ op(_f32(ix["w"]["kernel"]))) * (Hi ** -0.5 * Di ** -0.5)

    def block(b):
        s0 = b * q_block
        qpos = s0 + jnp.arange(q_block)
        q_i = jnp.einsum("sr,rhd->shd", _rows(c_q, s0, q_block), w_q)
        q_i = op(jnp.concatenate([_rope(q_i[..., :R], qpos, theta), q_i[..., R:]], axis=-1))
        score = jnp.einsum("shk,sh->sk", jax.nn.relu(jnp.einsum("shd,kd->shk", q_i, k_i)), _rows(w, s0, q_block))
        back = qpos[:, None] - pos[None, :]
        score = jnp.where(back >= 0, score, -jnp.inf)
        _, chosen = jax.lax.top_k(score, min(cfg["index_topk"], S))
        mask = jnp.zeros(score.shape, bool).at[jnp.arange(q_block)[:, None], chosen].set(True)
        return mask & (back >= 0)

    return jax.lax.map(block, jnp.arange(S // q_block))


def _attention(p, h, cfg: dict, full: bool, q_block: int, op):
    """h: [S, hidden], S a multiple of q_block. Loops (queries by block, heads by group, a
    sliding layer's keys by the band a block can see) only so that 25k tokens fit beside the
    server's weights; every score is the equations' own."""
    S, eps = h.shape[0], cfg["norm_eps"]
    d = _dims(cfg, full)
    hg = HEAD_GROUP if d["H"] % HEAD_GROUP == 0 else d["H"]
    rescale = cfg.get("mla_rescale", True)
    r_q = math.sqrt(cfg["hidden"] / d["q_rank"]) if rescale else 1.0
    r_kv = math.sqrt(cfg["hidden"] / d["kv_rank"]) if rescale else 1.0
    pos, hh = jnp.arange(S), op(h)
    c_q = op(_rmsnorm(hh @ op(_f32(p["q_a"]["kernel"])), p["q_norm"]["scale"], eps) * r_q)
    kv = hh @ op(_f32(p["kv_a"]["kernel"]))
    c_kv = op(_rmsnorm(kv[:, :d["kv_rank"]], p["kv_norm"]["scale"], eps) * r_kv)
    k_r = _rope(kv[:, None, d["kv_rank"]:], pos, d["theta"])
    w_q, w_kv, w_o = (op(_f32(p[n]["kernel"])) for n in ("q_b", "kv_b", "o"))
    gate = jax.nn.sigmoid(hh @ op(_f32(p["gate"]["kernel"])))
    chosen = _selection(p["indexer"], c_q, hh, cfg, d["theta"], q_block, op) if full else None
    band = S if full else min(S, q_block + cfg["sliding_window"] - 1)

    def heads(g, out):
        h0 = g * hg
        q = jnp.einsum("sr,rhd->shd", c_q, jax.lax.dynamic_slice_in_dim(w_q, h0, hg, axis=1))
        q = jnp.concatenate([q[..., :d["nope"]], _rope(q[..., d["nope"]:], pos, d["theta"])], axis=-1)
        kvx = jnp.einsum("sc,chd->shd", c_kv, jax.lax.dynamic_slice_in_dim(w_kv, h0, hg, axis=1))
        k = op(jnp.concatenate([kvx[..., :d["nope"]], jnp.broadcast_to(k_r, (S, hg, d["rope"]))], axis=-1))
        v = op(kvx[..., d["nope"]:])

        def block(b):
            s0 = b * q_block
            first = 0 if full else jnp.clip(s0 - (cfg["sliding_window"] - 1), 0, S - band)
            back = (s0 + jnp.arange(q_block))[:, None] - (first + jnp.arange(band))[None, :]
            mask = chosen[b] if full else (back >= 0) & (back < cfg["sliding_window"])
            s = jnp.einsum("shd,khd->hsk", op(_rows(q, s0, q_block)), _rows(k, first, band))
            pr = jax.nn.softmax(jnp.where(mask[None], s / math.sqrt(d["nope"] + d["rope"]), -jnp.inf), axis=-1)
            return jnp.einsum("hsk,khd->shd", op(pr), _rows(v, first, band))

        o = jax.lax.map(block, jnp.arange(S // q_block)).reshape(S, hg, d["v"])
        o = o * jax.lax.dynamic_slice_in_dim(gate, h0, hg, axis=1)[..., None]
        return out + jnp.einsum("shd,hde->se", op(o), _rows(w_o, h0, hg))

    return jax.lax.fori_loop(0, d["H"] // hg, heads, jnp.zeros((S, cfg["hidden"]), jnp.float32))


def _swiglu(m, gate, up, down, op):
    return op(jax.nn.silu(m @ op(_f32(gate))) * (m @ op(_f32(up)))) @ op(_f32(down))


def _swiglu_by_rows(m, gate, up, down, op, block: int):
    """`_swiglu` a block of rows at a time: the dense layer's [S, 13824] products are 1.4 GB each at 25k rows."""
    g, u, dn = op(_f32(gate)), op(_f32(up)), op(_f32(down))
    out = jax.lax.map(lambda b: op(jax.nn.silu(_rows(m, b * block, block) @ g) * (_rows(m, b * block, block) @ u)) @ dn,
                      jnp.arange(m.shape[0] // block))
    return out.reshape(m.shape[0], -1)


def _experts(p, m, cfg: dict, op):
    """The held experts' part of the routed sum, and the shared expert."""
    S = m.shape[0]
    E, first, K = cfg["n_routed_experts"], cfg.get("first_expert", 0), cfg["experts_per_token"]
    s = jax.nn.sigmoid(m @ _f32(p["router"]["kernel"]))  # float32, never the control's operand type
    _, ids = jax.lax.top_k(s + _f32(p["router"]["bias"]), K)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.get("routed_scaling_factor", 1.0)
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


def forward(params, cfg: dict, tokens, q_block: int = 256, operand=None, rows=None):
    """tokens: [S] int32 -> logits [S, V] float32, or with `rows` = (first, count) the logits
    of those positions only (first may be traced). `operand`, where given, is applied to
    both operands of every matrix product but the router's (the control of
    `benchmark/tests/test_dots3.py` rounds them to a narrower type). Call under
    `jax.default_matmul_precision("highest")`, as every entry point below does."""
    op = operand or (lambda a: a)
    S = tokens.shape[0]
    # whole blocks of queries: a causal model's logits at a position do not depend on what follows it
    tokens = jnp.pad(tokens, (0, -S % q_block))
    x = _f32(params["embedding"][tokens])
    for i in range(cfg["n_layers"]):
        lp = params[f"layer_{i}"]
        h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg["norm_eps"])
        x = x + _attention(lp["attn"], h, cfg, cfg["layer_types"][i] == "full_attention", q_block, op)
        m = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg["norm_eps"])
        if i < cfg.get("first_k_dense", 1):
            mp = lp["mlp"]
            x = x + _swiglu_by_rows(op(m), mp["gate"]["kernel"], mp["up"]["kernel"], mp["down"]["kernel"], op, q_block)
        else:
            x = x + _experts(lp["mlp"], m, cfg, op)
    x = x[:S] if rows is None else _rows(x, rows[0], rows[1])
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])
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
    do not depend on what follows it, nor does a selection among the positions before
    it). Returns (ids [n_new], margins [n_new]): the chosen id and the gap between the two
    largest logits at each step."""
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

# No train cell runs this block (16 bytes a parameter would need 65 GB for the cut), so no cell
# uses the two loss limits and they have no readings: they are here because `lib/blocks.py`
# asks every block for them (as it asks for `greedy` and `compare_greedy`, which this cell does
# not use either), at the dense block's values. A train cell of this block brings its own.
LOSS_ABS_TOL = 1.5e-3
TOKEN_LOSS_RMS_TOL = 5e-2

# The serve cell. The engine multiplies in bfloat16 with float32 accumulation and keeps its
# cache in bfloat16; the reference is float32 throughout. The weights and the cache fill 9.7
# of the chip's 16 GB, so the reference cannot hold a second copy beside the server: it reads
# the server's own tree (`LLMServer.weights()`) and scores the sequences the server generated
# (`score`): at every scored position the reference's choice given the same tokens before it.
# Two sets of sequences a run, each held to both limits below on its own
# (`drivers/serve_closed_long.py`): 8 probes of 4352 + 16 tokens sent before the window (128
# positions), and after the window three of the requests it finished, one of them past 16k
# tokens, over their last 128 generated positions (384).
# No id the server chose may lie further under the reference's largest logit than
# NEAR_TIE_MARGIN: where the reference's margin (largest logit less the second) is at least
# that, the server's id has to be the reference's; under it either id can be a right answer.
# This block has two more places where bfloat16 can flip a choice than the dense one, a
# router's eighth expert against its ninth and the selection's 2048th key against its 2049th,
# so the threshold is this block's own and not inherited.
# Readings on the chip (my chip run, PR 28; PERF.md §6 gives the runs). Sound, the engine's ids
# scored by this reference over some 11,000 positions of 31 weight seeds: they differ at one
# position in fifty, at margins under 0.035 (the engine's logits are off by 0.015 rms; this
# reference with bfloat16 operands: rms 0.0056 a logit, partings under 0.008); a dozen times at
# 0.04 to 0.1349; and once, in a window's sample, at 0.317, the id lying 0.3441 under. The
# cause is a router's choice among this chip's experts flipping under bfloat16: the engine's
# own functions, teacher-forced over 384 positions of a 16k-token request (call N), are exact
# in float32 (rms 0.00001) and in bfloat16 move the difference of two candidates' logits by
# 0.0085 in the median, over 0.1 at one position in 35 (the flipped ones, a logit's rms 0.06
# to 0.12 there) and by 0.178 at most; two flips at one token reach twice that, about once in
# ten thousand positions. Control, this reference with both operands of every matrix product
# rounded to float8 e4m3 (each tensor scaled), scored the same way: rms 0.076 to 0.091 a logit,
# ids differ at one position in five, at margins up to 0.516.
# Two limits, because an id at one position is a coarse reading: one flipped expert moves an
# id further than the control's steady error does in a hundred positions.
# NEAR_TIE_MARGIN 0.7: twice the largest sound reading (0.3441), four single flips; it is there
# for a wrong function (a missing term, a mask off by one), which fails it at once. The control
# passes it. (It was 0.3, 2.2 times the largest of the first 7,500 positions, 0.1349, until a
# sound run failed it in the next 3,500: a check of 14 runs scores 7,000.)
# MEAN_DEFICIT_TOL 0.006: the mean over a set's scored positions of how far the server's id lies
# under the reference's largest logit (0 where they agree). Sound: 0.00000 to 0.00238 over the
# 128 probe positions of 25 runs as committed (the largest is a run whose ids differed at 0.0621,
# 0.0831 and 0.136; the next 0.00152), 0.00008 to 0.00138 over a window sample's 384 in ten (the
# largest is the run with the id 0.3441 under).
# Control, measured (call K): 0.02102 and 0.01541 over the 128 probe positions of two weight
# seeds; 0.01433 (a 6144-token prompt) and 0.01772 (16419 tokens) over 128 generated positions
# each. The limit is the geometric middle of the largest sound reading and the smallest control
# reading at these sizes: 2.5 times the one, 2.4 times under the other; the control fails it every
# time. (It was 0.004 while the control's reading at 128 positions was inferred as 0.0084 from
# sets of 64, which read 0.00496 to 0.0273; no set is that small now.)
# Every scored position is compared: 128 and 384 against MIN_COMPARED_POSITIONS 12, which
# fails a run whose window finished nothing to score. (Margins: median 0.15 to 0.18, a quarter
# of all positions at 0.3 or more, one in 25 at 0.7.)
NEAR_TIE_MARGIN = 0.7
MEAN_DEFICIT_TOL = 0.006
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
