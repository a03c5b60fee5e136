"""The `lfm2` block on the serve path: gated short convolutions whose last inputs live in the
engine's slots, grouped-query attention layers with an RMSNorm over every head of q and of k
before the rotation, and, after the leading dense layers, every layer's feed-forward a sum of
sigmoid-routed experts, all of them held here (`ModelConfig(block="lfm2")`;
LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe).

One set of pure functions over one parameter tree, behind the seam every block is served
through (`models/__init__.py`). `forward_plain` is the repo's plain reference (float32, no
cache, no chunks, every expert over every token) that the tests hold the cached paths to.

    x = E[token]
    each layer i:  x = x + op_i(rmsnorm(x));  x = x + ff_i(rmsnorm(x))
    logits = rmsnorm(x) . E^T                                        (tied embedding)

`conv` operator (`conv_L_cache` taps, no bias, no activation):

    [B | C | u] = W_in h;  g_t = B_t * u_t
    c_t = sum_j w_j g_{t - taps + 1 + j}                     depthwise, causal, zeros before the prompt
    out = W_out (C_t * c_t)

`full_attention` operator: q of `n_heads`, k and v of `n_kv_heads`, q and k normed a head, then
rotary (`rope_theta`), scores over sqrt(head_dim), causal softmax, W_o: the dense block's cached
products (`llama._attn_cached` with `qk_norm`).
ff_i: W_down(silu(W_gate h) * W_up h) at `mlp_dim` for i < `first_k_dense`; otherwise
`ops/moe.py`: `sigmoid_routing` (the chosen scores over their sum + 1e-6) and `grouped_experts`
over all `n_routed_experts` (= `n_routed_experts_total`, `first_expert` 0); no shared expert.

The cache, one tuple a layer: a `conv` layer keeps no rows but a state a slot,
(conv [slots, taps - 1, hidden] in `cfg.dtype`: the gated inputs g of the last taps - 1 positions,
oldest first), a `full_attention` layer the dense block's (K, V) slabs [slots, max_seq, Hkv, D].
A length makes none of a state's old contents harmless, so `granite_hybrid`'s three rules hold: a
prompt's first chunk (`offset == 0`) starts from zeros whatever the slot held; the right-padding
of a bucketed chunk is not shifted into the window; a decode step leaves a slot whose `gate` is
off exactly as it was. The published code keeps `conv_L_cache` inputs a slot, the oldest of which
no step reads again; here it is not kept.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, scaffold
from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm
from ray_tpu.ops.moe import expert_tile_rows, routed_experts, sigmoid_routing

# Served by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7): a prefix hit
# needs a snapshot of the convolution's inputs at a block boundary, the train step an expert layer
# with a backward pass, several chips an exchange of tokens between the experts' holders.
SUPPORTS = frozenset()

ROUTING_EPS = 1e-6  # under the chosen scores' sum, as the published code has it

# What a program counts (`init_stats`): the expert layers' counts, then the state's, in this order.
EXPERT_COUNTS = ("pairs_routed", "pairs_held", "experts_hit", "tiles_run", "layer_steps",
                 "decode_experts_hit", "decode_layer_steps")
STATE_COUNTS = ("prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps")


# -- sizes ---------------------------------------------------------------------------


def _is_conv(cfg: ModelConfig, i: int) -> bool:
    return cfg.layer_types[i] == "conv"


def state_bytes(cfg: ModelConfig) -> int:
    """A slot's convolution inputs over all `conv` layers."""
    layers = sum(_is_conv(cfg, i) for i in range(cfg.n_layers))
    return layers * (cfg.conv_L_cache - 1) * cfg.hidden * jnp.dtype(cfg.dtype).itemsize


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, how it is drawn)}: a positive number is a kernel's
    fan-in (normal at 1 / sqrt(fan-in)), "ones" a norm scale, "zeros" the router's selection
    bias (its trained values are in no config), "embedding" the draw `_draw` names."""
    if cfg.n_routed_experts != cfg.n_routed_experts_total or cfg.first_expert:
        raise ValueError("block 'lfm2' holds every expert of a layer: n_routed_experts == n_routed_experts_total, "
                         "first_expert == 0")
    Dm, out = cfg.hidden, {}
    out["embedding",] = ((cfg.vocab_size, Dm), "embedding")
    for i in range(cfg.n_layers):
        L, a, m = f"layer_{i}", (f"layer_{i}", "attn"), (f"layer_{i}", "mlp")
        out[L, "attn_norm", "scale"] = ((Dm,), "ones")
        out[L, "mlp_norm", "scale"] = ((Dm,), "ones")
        if _is_conv(cfg, i):
            out[a + ("in_proj", "kernel")] = ((Dm, 3 * Dm), Dm)
            out[a + ("conv", "kernel")] = ((cfg.conv_L_cache, Dm), cfg.conv_L_cache)
            out[a + ("out_proj", "kernel")] = ((Dm, Dm), Dm)
        else:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            out[a + ("q", "kernel")] = ((Dm, q), Dm)
            out[a + ("k", "kernel")] = ((Dm, kv), Dm)
            out[a + ("v", "kernel")] = ((Dm, kv), Dm)
            out[a + ("o", "kernel")] = ((q, Dm), q)
            out[a + ("q_norm", "scale")] = ((cfg.head_dim,), "ones")
            out[a + ("k_norm", "scale")] = ((cfg.head_dim,), "ones")
        if i < cfg.first_k_dense:
            F = cfg.mlp_dim
            out[m + ("gate", "kernel")] = ((Dm, F), Dm)
            out[m + ("up", "kernel")] = ((Dm, F), Dm)
            out[m + ("down", "kernel")] = ((F, Dm), F)
        else:
            E, F = cfg.n_routed_experts, cfg.moe_mlp_dim
            out[m + ("router", "kernel")] = ((Dm, E), Dm)
            out[m + ("router", "bias")] = ((E,), "zeros")
            out[m + ("experts", "gate")] = ((E, Dm, F), Dm)
            out[m + ("experts", "up")] = ((E, Dm, F), Dm)
            out[m + ("experts", "down")] = ((E, F, Dm), F)
    out["final_norm", "scale"] = ((Dm,), "ones")
    return out


def num_params(cfg: ModelConfig) -> int:
    return scaffold.num_params(param_shapes(cfg))


# The embedding's standard deviation: the family's initializer_range. The head is the embedding
# again, so the input token's own row scores sqrt(hidden) * std / rms(x) standard deviations over
# the other rows' logits, x the last layer's output (rms about 3: every operator adds about 1 to
# its square): a third of one, so greedy decoding does not repeat its input and no smaller value
# is needed (as `granite_hybrid`, whose embedding is multiplied by 12, needed one).
EMBEDDING_STD = 0.02


def _draw(key, shape, how, dtype):
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    return scaffold.normal(key, shape, EMBEDDING_STD if how == "embedding" else 1.0 / math.sqrt(how), dtype)


serving_params = scaffold.as_drawn


def init_params(cfg: ModelConfig, key):
    """The tree at seeded random weights in `cfg.param_dtype`: `scaffold.tree_from_shapes`, each
    leaf by `_draw`."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype, _draw)


# -- the cache and the counts --------------------------------------------------------


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    kv = llama.kv_slab_shape(cfg, slots, max_seq)  # heads of 64: two to a row of 128 lanes
    return [
        (jnp.zeros((slots, cfg.conv_L_cache - 1, cfg.hidden), cfg.dtype),)
        if _is_conv(cfg, i) else (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))
        for i in range(cfg.n_layers)
    ]


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: the expert layers' int32 array (`EXPERT_COUNTS`, then
    the pairs each expert took, summed over the layers) and the state's (`STATE_COUNTS`)."""
    return (jnp.zeros((len(EXPERT_COUNTS) + cfg.n_routed_experts,), jnp.int32),
            jnp.zeros((len(STATE_COUNTS),), jnp.int32))


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["experts"]` and `["state"]`. Experts: token-expert pairs routed and held
    (all of them, here), experts that took at least one valid pair and tiles the loop ran, both
    summed over the expert layers and the programs' steps (`layer_steps` counts those; the
    `decode_` pair counts the decode programs' alone), since the engine started and since the
    last report, with the largest and the mean load of an expert there. State: positions the
    prefill programs ran and how many of them were padding, states reset (a prompt's first
    chunk: admissions), decode steps times the slots they advanced."""
    (experts, state), (w_experts, w_state) = total, window
    n = len(EXPERT_COUNTS)
    out = {"held": cfg.n_routed_experts, "of": cfg.n_routed_experts_total, "first": cfg.first_expert}
    out.update({name: int(experts[j]) for j, name in enumerate(EXPERT_COUNTS)})
    out["window"] = {name: int(w_experts[j]) for j, name in enumerate(EXPERT_COUNTS)}
    out["window"].update(max_load=int(w_experts[n:].max()), mean_load=float(w_experts[n:].mean()))
    st = {name: int(state[j]) for j, name in enumerate(STATE_COUNTS)}
    st["window"] = {name: int(w_state[j]) for j, name in enumerate(STATE_COUNTS)}
    st["bytes_per_slot"] = state_bytes(cfg)
    return {"experts": out, "state": st}


# -- a conv layer --------------------------------------------------------------------


def _project_in(p, h):
    """h: [..., D] -> the gated input g = B * u and the output gate C, each [..., D]."""
    D = h.shape[-1]
    with jax.named_scope("in_proj"):
        bcu = _dense(h, p["in_proj"]["kernel"])
        return bcu[..., :D] * bcu[..., 2 * D:], bcu[..., D:2 * D]


def _taps(p, inputs):
    """inputs: one [..., D] array a tap, oldest first -> sum_j w_j inputs_j [..., D]. Its own
    three lines and not `granite_hybrid._taps` with two flags: that one adds a bias and applies
    silu, this one is a bare weighted sum, and nothing but the sum would be shared."""
    w = p["conv"]["kernel"].astype(jnp.float32)
    return sum(w[j] * a.astype(jnp.float32) for j, a in enumerate(inputs)).astype(inputs[0].dtype)


def _project_out(p, C, c):
    with jax.named_scope("out_proj"):
        return _dense(C * c, p["out_proj"]["kernel"])


def _conv_prefill(p, h, cache, offset, n_valid, cfg: ModelConfig):
    """h: [1, S, D], a chunk at positions offset + [0, S) of which the first `n_valid` are the
    prompt's; cache: (conv [1, taps - 1, D],) of the chunk's slot."""
    (conv,) = cache
    S, K = h.shape[1], cfg.conv_L_cache
    conv = jnp.where(offset == 0, 0, conv)
    g, C = _project_in(p, h[0])
    with jax.named_scope("conv"):
        seen = jnp.concatenate([conv[0], g.astype(conv.dtype)], axis=0)              # [K - 1 + S, D]
        c = _taps(p, [seen[j:j + S] for j in range(K)])
        # the last K - 1 inputs before the padding: the carried ones where the chunk is shorter
        conv = jax.lax.dynamic_slice_in_dim(seen, n_valid, K - 1, axis=0)[None]
    return _project_out(p, C, c)[None], (conv,)


def _conv_decode(p, h, cache, gate, cfg: ModelConfig):
    """h: [B, 1, D]; cache: (conv [B, taps - 1, D],); a slot whose gate is off keeps it bit for bit."""
    (conv,) = cache
    g, C = _project_in(p, h[:, 0])
    with jax.named_scope("conv"):
        window = jnp.concatenate([conv, g.astype(conv.dtype)[:, None]], axis=1)
        c = _taps(p, [window[:, j] for j in range(cfg.conv_L_cache)])
        conv = jnp.where(gate[:, None, None], window[:, 1:], conv)
    return _project_out(p, C, c)[:, None], (conv,)


# -- the layers round the operators ----------------------------------------------------


def _attention(p, normed, positions, cache, write_at, gate, cfg: ModelConfig):
    out, k, v = llama._attn_cached(p, normed, positions, cache[0], cache[1], write_at, cfg, write_gate=gate,
                                   qk_norm=(p["q_norm"]["scale"], p["k_norm"]["scale"]))
    return out, (k, v)


def _forward(params, cfg: ModelConfig, tokens, valid, mix, decoding: bool):
    """The layers round `mix(i, layer_params, normed) -> (out, cache_i)`: hidden states after the
    final norm, the caches, and the expert layers' counts (`EXPERT_COUNTS`, then pairs by expert)."""
    with jax.named_scope("embedding"):
        x = params["embedding"][tokens].astype(cfg.dtype)
    caches, counts = [], jnp.zeros((cfg.n_routed_experts,), jnp.int32)
    hit = tiles = jnp.zeros((), jnp.int32)
    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            with jax.named_scope("attn_norm"):
                normed = _rmsnorm(x, layer["attn_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attn"):
                out, cache = mix(i, layer["attn"], normed)
            caches.append(cache)
            x = x + out
            with jax.named_scope("mlp_norm"):
                normed = _rmsnorm(x, layer["mlp_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                if i < cfg.first_k_dense:
                    x = x + llama._mlp(layer["mlp"], normed)
                else:
                    y, c = routed_experts(layer["mlp"], normed, valid, cfg.experts_per_token, cfg.routed_scaling_factor,
                                          eps=ROUTING_EPS)
                    # the tiles `grouped_experts`' loop ran for them
                    t = jnp.sum(-(-c // expert_tile_rows(valid.size * cfg.experts_per_token, cfg.n_routed_experts)))
                    x, counts = x + y, counts + c
                    hit, tiles = hit + jnp.sum(c > 0, dtype=jnp.int32), tiles + t.astype(jnp.int32)
    with jax.named_scope("final_norm"):
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    n_moe = scaffold.num_expert_layers(cfg)
    pairs = jnp.sum(valid, dtype=jnp.int32) * (cfg.experts_per_token * n_moe)
    named = dict(pairs_routed=pairs, pairs_held=jnp.sum(counts), experts_hit=hit, tiles_run=tiles, layer_steps=n_moe)
    if decoding:
        named.update(decode_experts_hit=hit, decode_layer_steps=n_moe)
    return x, caches, jnp.concatenate([scaffold.counts(EXPERT_COUNTS, **named), counts])


def _head(params, cfg: ModelConfig, x):
    """x: [..., D] -> logits [..., V] float32 against the embedding again."""
    with jax.named_scope("lm_head"):
        return jax.lax.dot_general(x, params["embedding"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)


# -- what the engine's programs call ---------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len, lora=None, adapter_id=None):
    """The engine's prefill program for this block. tokens: [1, S] right-padded, the chunk at
    positions offset + [0, S) of a prompt of `total_len` tokens, into slot `slot`. Returns
    (logits of the prompt's last token if it is in this chunk, caches, stats)."""
    S = tokens.shape[1]
    n_valid = jnp.minimum(S, total_len - offset)
    view = scaffold.slot_view(caches, slot)
    positions = offset + jnp.arange(S)[None, :]
    # a query sees the rows up to its own position, the earlier chunks' and this chunk's:
    # `_attn_cached` reads that from the slot's length, `offset`

    def mix(i, p, normed):
        if _is_conv(cfg, i):
            return _conv_prefill(p, normed, view[i], offset, n_valid, cfg)
        return _attention(p, normed, positions, view[i], offset[None], None, cfg)

    x, new, experts = _forward(params, cfg, tokens, jnp.arange(S)[None, :] < n_valid, mix, decoding=False)
    caches = scaffold.write_back(caches, new, slot)
    last = scaffold.last_row(x, offset, total_len)
    state = scaffold.counts(STATE_COUNTS, prefill_positions=S, prefill_padding=S - n_valid, states_reset=offset == 0)
    return _head(params, cfg, last)[0], caches, (experts, state)


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots with `gate`
    advance their state, write their rows and are routed. Returns (logits [B, V], caches, stats)."""
    positions = lens[:, None]

    def mix(i, p, normed):
        if _is_conv(cfg, i):
            return _conv_decode(p, normed, caches[i], gate, cfg)
        return _attention(p, normed, positions, caches[i], lens, gate, cfg)

    x, new, experts = _forward(params, cfg, last_token[:, None], gate[:, None], mix, decoding=True)
    return _head(params, cfg, x[:, 0]), new, (experts, scaffold.counts(STATE_COUNTS, decode_slot_steps=jnp.sum(gate)))


# -- the plain reference -------------------------------------------------------------


def forward_plain(params, cfg: ModelConfig, tokens, experts=None):
    """tokens [S] -> logits [S, V]: float32 under "highest", the convolution from zeros before the
    prompt, every score matrix whole, every expert over every token. No cache, no chunks, no
    padding, no sort. `experts` is the (first, count) of the experts summed, by default all of
    them; the router always scores every one (the share test of `tests/test_lfm2.py`)."""
    first, count = experts or (0, cfg.n_routed_experts)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    S, K, hd = tokens.shape[0], cfg.conv_L_cache, cfg.head_dim
    causal = jnp.tril(jnp.ones((S, S), bool))

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps) * f32(scale)

    def rope(x):  # [S, H, hd], rotate-half
        half = hd // 2
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        return jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin, x[..., half:] * cos + x[..., :half] * sin], -1)

    def conv(p, h):
        D = h.shape[-1]
        bcu = h @ f32(p["in_proj"]["kernel"])
        g = jnp.concatenate([jnp.zeros((K - 1, D)), bcu[:, :D] * bcu[:, 2 * D:]])
        w = f32(p["conv"]["kernel"])
        return (bcu[:, D:2 * D] * sum(w[j] * g[j:j + S] for j in range(K))) @ f32(p["out_proj"]["kernel"])

    def attention(p, h):
        G = cfg.n_heads // cfg.n_kv_heads
        q = rope(norm((h @ f32(p["q"]["kernel"])).reshape(S, cfg.n_heads, hd), p["q_norm"]["scale"]))
        k = rope(norm((h @ f32(p["k"]["kernel"])).reshape(S, cfg.n_kv_heads, hd), p["k_norm"]["scale"]))
        v = (h @ f32(p["v"]["kernel"])).reshape(S, cfg.n_kv_heads, hd)
        s = jnp.einsum("skgd,tkd->kgst", q.reshape(S, cfg.n_kv_heads, G, hd), k) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", pr, v).reshape(S, -1) @ f32(p["o"]["kernel"])

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)

    with jax.default_matmul_precision("highest"):
        E = f32(params["embedding"])
        x = E[tokens]
        for i in range(cfg.n_layers):
            layer = params[f"layer_{i}"]
            h = norm(x, layer["attn_norm"]["scale"])
            x = x + (conv if _is_conv(cfg, i) else attention)(layer["attn"], h)
            h, m = norm(x, layer["mlp_norm"]["scale"]), layer["mlp"]
            if i < cfg.first_k_dense:
                x = x + swiglu(h, m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"])
                continue
            ids, weights = sigmoid_routing(h, m["router"]["kernel"], m["router"]["bias"], cfg.experts_per_token,
                                           cfg.routed_scaling_factor, eps=ROUTING_EPS)
            y = jnp.zeros_like(x)
            for e in range(first, first + count):
                w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
                y = y + w_e[:, None] * swiglu(h, m["experts"]["gate"][e], m["experts"]["up"][e], m["experts"]["down"][e])
            x = x + y
        return norm(x, params["final_norm"]["scale"]) @ E.T
