"""The `granite_hybrid` block on the serve path: Mamba-2 layers whose recurrent state lives
in the engine's slots beside the KV rows of a few position-free grouped-query attention
layers (`ModelConfig(block="granite_hybrid")`; ibm-granite/granite-4.0-h-micro).

One set of pure functions over one parameter tree, behind the seam every block is served
through (`models/__init__.py`). `forward_plain` is the repo's plain reference (token by
token, float32, no cache, no chunks) that the tests hold the cached paths to.

    x = embedding_multiplier * E[token]
    each layer i:  x = x + residual_multiplier * mixer_i(rmsnorm(x))
                   x = x + residual_multiplier * W_out(silu(a) * b),  [a | b] = W_in(rmsnorm(x))
    logits = rmsnorm(x) . E^T / logits_scaling                      (tied embedding)

`attention` mixer: q of `n_heads`, k and v of `n_kv_heads`, no rotary where
`position_embedding_type` is "nope", scores scaled by `attention_multiplier`, causal
softmax, W_o: the dense block's cached products (`llama._attn_cached`).
`mamba` mixer (H = `mamba_n_heads` heads of P = `mamba_d_head`, state N = `mamba_d_state`,
one group; inner width I = H P, convolution width W = I + 2 N over `mamba_d_conv` taps):

    [z | xBC | dt] = W_in_proj(u)                             I | W | H
    xBC_t = silu(b + sum_j w_j xBC_{t - taps + 1 + j})        depthwise, causal, zeros before the prompt
    [x | B | C] = xBC                                         I -> [H, P] | N | N
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;  y_t = h_t . C_t + D x_t     (`ops/ssd.py`)
    out = W_out_proj(rmsnorm(y * silu(z)) * g)

The cache, one tuple a layer: a `mamba` layer keeps no rows but a state a slot,
(conv [slots, taps - 1, W] in `cfg.dtype`: the convolution's last inputs; h [slots, H, P, N]
float32), an `attention` layer the dense block's (K, V) slabs [slots, max_seq, Hkv, D]. A
length makes none of a state's old contents harmless, so: a prompt's first chunk
(`offset == 0`) starts from zeros whatever the slot held; the right-padding of a bucketed
chunk takes no step and is not shifted into the convolution's window; a decode step leaves
a slot whose `gate` is off exactly as it was.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, scaffold
from ray_tpu.models.transformer import ModelConfig, _dense, _rmsnorm
from ray_tpu.ops.ssd import ssd_chunked, ssd_step

# Served by LLMServer / DecodeEngine on one device, and nothing else yet (PERF.md §7): a prefix
# hit needs a snapshot of the state at a block boundary, a rejected draft a state that rolls back.
SUPPORTS = frozenset()
# Nothing but the next program reads the caches: every program of the engine consumes them
# (`donate_argnums`); undonated, every program would copy the states.

# What a program counts (`init_stats`), in this order.
COUNTS = ("prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps")


# -- sizes ---------------------------------------------------------------------------


def _is_mamba(cfg: ModelConfig, i: int) -> bool:
    return cfg.layer_types[i] == "mamba"


def mamba_dims(cfg: ModelConfig) -> tuple:
    """(heads, channels a head, state, inner width, convolution width, in_proj's outputs)."""
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    inner = H * P
    return H, P, N, inner, inner + 2 * N, 2 * inner + 2 * N + H


def state_bytes(cfg: ModelConfig) -> int:
    """A slot's recurrent state and convolution inputs over all `mamba` layers."""
    H, P, N, _, W, _ = mamba_dims(cfg)
    layers = sum(_is_mamba(cfg, i) for i in range(cfg.n_layers))
    return layers * (H * P * N * 4 + (cfg.mamba_d_conv - 1) * W * jnp.dtype(cfg.dtype).itemsize)


# -- the tree ------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree as {path tuple: (shape, how it is drawn)}: a positive number is a kernel's
    fan-in (normal at 1 / sqrt(fan-in)), "ones" a norm scale or D, "zeros" the convolution's
    bias, and "A_log", "dt_bias", "embedding" the draws `_draw` names."""
    Dm, F, out = cfg.hidden, cfg.mlp_dim, {}
    H, _, _, inner, W, proj = mamba_dims(cfg)
    out["embedding",] = ((cfg.vocab_size, Dm), "embedding")
    for i in range(cfg.n_layers):
        L, a = f"layer_{i}", (f"layer_{i}", "attn")
        out[L, "attn_norm", "scale"] = ((Dm,), "ones")
        out[L, "mlp_norm", "scale"] = ((Dm,), "ones")
        if _is_mamba(cfg, i):
            out[a + ("in_proj", "kernel")] = ((Dm, proj), Dm)
            out[a + ("conv", "kernel")] = ((cfg.mamba_d_conv, W), cfg.mamba_d_conv)
            out[a + ("conv", "bias")] = ((W,), "zeros")
            out[a + ("A_log",)] = ((H,), "A_log")
            out[a + ("dt_bias",)] = ((H,), "dt_bias")
            out[a + ("D",)] = ((H,), "ones")
            out[a + ("norm", "scale")] = ((inner,), "ones")
            out[a + ("out_proj", "kernel")] = ((inner, Dm), inner)
        else:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            out[a + ("q", "kernel")] = ((Dm, q), Dm)
            out[a + ("k", "kernel")] = ((Dm, kv), Dm)
            out[a + ("v", "kernel")] = ((Dm, kv), Dm)
            out[a + ("o", "kernel")] = ((q, Dm), q)
        out[L, "mlp", "in", "kernel"] = ((Dm, 2 * F), Dm)
        out[L, "mlp", "out", "kernel"] = ((F, Dm), F)
    out["final_norm", "scale"] = ((Dm,), "ones")
    return out


def num_params(cfg: ModelConfig) -> int:
    return scaffold.num_params(param_shapes(cfg))


# The embedding's standard deviation. The config gives none. The head is the embedding again,
# so the input token's own row scores embedding_multiplier * sqrt(hidden) * std / rms(x) standard
# deviations over the other rows' logits, x the last layer's output (rms about 1.5 after 80
# sub-layers of 0.22): at the family's 0.02 that is 7 and greedy decoding repeats its input, a
# model that is all argmax, on which no rounding shows. At this value it is under 2.
EMBEDDING_STD = 0.004


def _draw(key, shape, how, dtype):
    """One leaf. "A_log" and "dt_bias" are Mamba-2's own initial values for the recurrence (A uniform
    in [1, 16], the step softplus(dt_bias) log-uniform in [0.001, 0.1]), kept in float32 whatever
    `dtype` is."""
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    if how == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    return scaffold.normal(key, shape, EMBEDDING_STD if how == "embedding" else 1.0 / math.sqrt(how), dtype)


serving_params = scaffold.as_drawn  # `A_log` and `dt_bias` stay float32, as the recurrence reads them


def init_params(cfg: ModelConfig, key):
    """The tree at seeded random weights in `cfg.param_dtype` (A_log and dt_bias in float32):
    `scaffold.tree_from_shapes`, each leaf by `_draw`."""
    return scaffold.tree_from_shapes(param_shapes(cfg), key, cfg.param_dtype, _draw)


# -- the cache and the counts --------------------------------------------------------


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    H, P, N, _, W, _ = mamba_dims(cfg)
    kv = llama.kv_slab_shape(cfg, slots, max_seq)  # heads of 64: two to a row of 128 lanes
    return [
        (jnp.zeros((slots, cfg.mamba_d_conv - 1, W), cfg.dtype), jnp.zeros((slots, H, P, N), jnp.float32))
        if _is_mamba(cfg, i) else (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))
        for i in range(cfg.n_layers)
    ]


def init_stats(cfg: ModelConfig) -> tuple:
    """Zeros shaped like a program's stats: one int32 array, `COUNTS`."""
    return (jnp.zeros((len(COUNTS),), jnp.int32),)


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    """`scheduler_stats()["state"]`: positions the prefill programs ran and how many of them
    were padding, states reset (a prompt's first chunk: admissions), decode steps times the
    slots they advanced; since the engine started, and since the last report under "window"."""
    (total,), (window,) = total, window
    out = {name: int(total[j]) for j, name in enumerate(COUNTS)}
    out["window"] = {name: int(window[j]) for j, name in enumerate(COUNTS)}
    out["bytes_per_slot"] = state_bytes(cfg)
    return {"state": out}


# -- a mamba layer -------------------------------------------------------------------


def _project_in(p, u, cfg: ModelConfig):
    """u: [..., D] -> z [..., I], xBC [..., W] before its convolution, dt [..., H] float32
    before its softplus."""
    _, _, _, inner, W, _ = mamba_dims(cfg)
    with jax.named_scope("in_proj"):
        zxd = _dense(u, p["in_proj"]["kernel"])
    return zxd[..., :inner], zxd[..., inner:inner + W], zxd[..., inner + W:].astype(jnp.float32)


def _recurrence_terms(p, xBC, dt, cfg: ModelConfig):
    """The convolution's output split into x [..., H, P], B and C [..., N]; the step after
    its softplus, A and D, in float32."""
    H, P, N, inner, _, _ = mamba_dims(cfg)
    x = xBC[..., :inner].reshape(xBC.shape[:-1] + (H, P))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return (x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), xBC[..., inner:inner + N], xBC[..., inner + N:],
            p["D"].astype(jnp.float32))


def _taps(p, inputs):
    """inputs: one [..., W] array a tap, oldest first -> silu(b + sum_j w_j inputs_j) [..., W]."""
    w = p["conv"]["kernel"].astype(jnp.float32)
    out = sum(w[j] * a.astype(jnp.float32) for j, a in enumerate(inputs)) + p["conv"]["bias"].astype(jnp.float32)
    return jax.nn.silu(out).astype(inputs[0].dtype)


def _project_out(p, y, z, cfg: ModelConfig):
    """y, z: [..., I]: the gate first, then the norm over the whole inner width, then W_out."""
    with jax.named_scope("gate_norm"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        normed = _rmsnorm(gated, p["norm"]["scale"], cfg.norm_eps).astype(y.dtype)
    with jax.named_scope("out_proj"):
        return _dense(normed, p["out_proj"]["kernel"])


def _mamba_prefill(p, u, cache, offset, n_valid, cfg: ModelConfig):
    """u: [1, S, D], a chunk at positions offset + [0, S) of which the first `n_valid` are
    the prompt's; cache: (conv [1, taps - 1, W], h [1, H, P, N]) of the chunk's slot."""
    conv, h = cache
    S, K = u.shape[1], cfg.mamba_d_conv
    fresh = offset == 0
    conv, h = jnp.where(fresh, 0, conv), jnp.where(fresh, 0.0, h)
    z, xBC, dt = _project_in(p, u[0], cfg)
    with jax.named_scope("conv"):
        seen = jnp.concatenate([conv[0], xBC.astype(conv.dtype)], axis=0)          # [K - 1 + S, W]
        xBC = _taps(p, [seen[j:j + S] for j in range(K)])
        # the last K - 1 inputs before the padding: the carried ones where the chunk is shorter
        conv = jax.lax.dynamic_slice_in_dim(seen, n_valid, K - 1, axis=0)[None]
    with jax.named_scope("ssm"):
        x, dt, A, B, C, D = _recurrence_terms(p, xBC, dt, cfg)
        y, h_last = ssd_chunked(x, dt, A, B, C, D, h[0], jnp.arange(S) < n_valid, chunk=cfg.mamba_chunk_size)
    return _project_out(p, y.reshape(S, -1), z, cfg)[None], (conv, h_last[None])


def _mamba_decode(p, u, cache, gate, cfg: ModelConfig):
    """u: [B, 1, D]; cache: (conv [B, taps - 1, W], h [B, H, P, N]); a slot whose gate is
    off keeps both bit for bit."""
    conv, h = cache
    z, xBC, dt = _project_in(p, u[:, 0], cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate([conv, xBC.astype(conv.dtype)[:, None]], axis=1)
        xBC = _taps(p, [window[:, j] for j in range(cfg.mamba_d_conv)])
        conv = jnp.where(gate[:, None, None], window[:, 1:], conv)
    with jax.named_scope("ssm"):
        x, dt, A, B, C, D = _recurrence_terms(p, xBC, dt, cfg)
        y, h = ssd_step(x, dt, A, B, C, D, h, gate)
    return _project_out(p, y.reshape(y.shape[0], -1), z, cfg)[:, None], (conv, h)


# -- the layers round the mixers ------------------------------------------------------


def _attention(p, normed, positions, cache, write_at, gate, cfg: ModelConfig):
    out, k, v = llama._attn_cached(
        p, normed, positions, cache[0], cache[1], write_at, cfg, write_gate=gate,
        score_scale=cfg.attention_multiplier, rotate=cfg.position_embedding_type != "nope")
    return out, (k, v)


def _mlp(p, x, cfg: ModelConfig):
    ab = _dense(x, p["in"]["kernel"])
    return _dense(jax.nn.silu(ab[..., :cfg.mlp_dim]) * ab[..., cfg.mlp_dim:], p["out"]["kernel"])


def _forward(params, cfg: ModelConfig, tokens, mix):
    """The layers round `mix(i, layer_params, normed) -> (out, cache_i)`: hidden states after
    the final norm, and the caches."""
    with jax.named_scope("embedding"):
        x = params["embedding"][tokens].astype(cfg.dtype) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
    r, caches = jnp.asarray(cfg.residual_multiplier, cfg.dtype), []
    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            with jax.named_scope("attn_norm"):
                normed = _rmsnorm(x, layer["attn_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attn"):
                out, cache = mix(i, layer["attn"], normed)
            caches.append(cache)
            x = x + r * out
            with jax.named_scope("mlp_norm"):
                normed = _rmsnorm(x, layer["mlp_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                x = x + r * _mlp(layer["mlp"], normed, cfg)
    with jax.named_scope("final_norm"):
        return _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps), caches


def _head(params, cfg: ModelConfig, x):
    """x: [..., D] -> logits [..., V] float32 against the embedding again."""
    with jax.named_scope("lm_head"):
        logits = jax.lax.dot_general(x, params["embedding"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling


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
        if _is_mamba(cfg, i):
            return _mamba_prefill(p, normed, view[i], offset, n_valid, cfg)
        return _attention(p, normed, positions, view[i], offset[None], None, cfg)

    x, new = _forward(params, cfg, tokens, mix)
    caches = scaffold.write_back(caches, new, slot)
    last = scaffold.last_row(x, offset, total_len)
    stats = scaffold.counts(COUNTS, prefill_positions=S, prefill_padding=S - n_valid, states_reset=offset == 0)
    return _head(params, cfg, last)[0], caches, (stats,)


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora=None, adapter_ids=None):
    """The engine's decode step for this block: one token for every slot; only slots with
    `gate` advance their state and write their rows. Returns (logits [B, V], caches, stats)."""
    positions = lens[:, None]

    def mix(i, p, normed):
        if _is_mamba(cfg, i):
            return _mamba_decode(p, normed, caches[i], gate, cfg)
        return _attention(p, normed, positions, caches[i], lens, gate, cfg)

    x, new = _forward(params, cfg, last_token[:, None], mix)
    return _head(params, cfg, x[:, 0]), new, (scaffold.counts(COUNTS, decode_slot_steps=jnp.sum(gate)),)


# -- the plain reference -------------------------------------------------------------


def forward_plain(params, cfg: ModelConfig, tokens):
    """tokens [S] -> logits [S, V]: float32 under "highest", the recurrence one token at a
    time from a zero state, the convolution from zeros before the prompt, every score
    matrix whole. No cache, no chunks, no padding."""
    if cfg.position_embedding_type != "nope":
        raise NotImplementedError("the plain reference of granite_hybrid is position-free")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    S, K = tokens.shape[0], cfg.mamba_d_conv
    H, P, N, inner, W, _ = mamba_dims(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps) * f32(scale)

    def mamba(p, u):
        zxd = u @ f32(p["in_proj"]["kernel"])
        z, xBC, dt = zxd[:, :inner], zxd[:, inner:inner + W], zxd[:, inner + W:]
        padded = jnp.concatenate([jnp.zeros((K - 1, W)), xBC])
        w = f32(p["conv"]["kernel"])
        xBC = jax.nn.silu(sum(w[j] * padded[j:j + S] for j in range(K)) + f32(p["conv"]["bias"]))
        x, B, C = xBC[:, :inner].reshape(S, H, P), xBC[:, inner:inner + N], xBC[:, inner + N:]
        dt, A = jax.nn.softplus(dt + f32(p["dt_bias"])), -jnp.exp(f32(p["A_log"]))

        def step(h, t):
            x_t, dt_t, B_t, C_t = t
            h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[..., None] * B_t
            return h, jnp.sum(h * C_t, axis=-1) + f32(p["D"])[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, dt, B, C))
        return norm(y.reshape(S, inner) * jax.nn.silu(z), p["norm"]["scale"]) @ f32(p["out_proj"]["kernel"])

    def attention(p, u):
        G = cfg.n_heads // cfg.n_kv_heads
        q = (u @ f32(p["q"]["kernel"])).reshape(S, cfg.n_kv_heads, G, cfg.head_dim)
        k = (u @ f32(p["k"]["kernel"])).reshape(S, cfg.n_kv_heads, cfg.head_dim)
        v = (u @ f32(p["v"]["kernel"])).reshape(S, cfg.n_kv_heads, cfg.head_dim)
        s = jnp.einsum("skgd,tkd->kgst", q, k) * cfg.attention_multiplier
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", pr, v).reshape(S, -1) @ f32(p["o"]["kernel"])

    with jax.default_matmul_precision("highest"):
        E = f32(params["embedding"])
        x = E[tokens] * cfg.embedding_multiplier
        for i in range(cfg.n_layers):
            layer = params[f"layer_{i}"]
            u = norm(x, layer["attn_norm"]["scale"])
            x = x + cfg.residual_multiplier * (mamba if _is_mamba(cfg, i) else attention)(layer["attn"], u)
            ab = norm(x, layer["mlp_norm"]["scale"]) @ f32(layer["mlp"]["in"]["kernel"])
            x = x + cfg.residual_multiplier * ((jax.nn.silu(ab[:, :cfg.mlp_dim]) * ab[:, cfg.mlp_dim:])
                                               @ f32(layer["mlp"]["out"]["kernel"]))
        return norm(x, params["final_norm"]["scale"]) @ E.T / cfg.logits_scaling
