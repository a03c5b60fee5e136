"""The dense llama-family block on the serve path (`ModelConfig(block="llama")`):
RMSNorm, rotary embeddings, grouped-query attention against a per-slot KV slab, a
SwiGLU MLP, a tied or untied head.

Pure functions over the flax Transformer's parameter tree (`models/transformer.py`,
`scan_layers=False` layout), which the train step builds and checkpoints hold. The
engine's programs call `prefill`, `decode` and `verify`; `gather_rows`, `attach_rows`
and the two detached prefills are the slab's side of the prefix cache and of the PD
hand-over, whose rows travel as one `[L, 2, rows, Hkv, D]` array; the draft model of
`llm/scheduler/spec.py` runs `_forward_cached` over a slab of its own.

The cache: per layer a pair (K, V) of `[slots, max_seq, Hkv, D]` arrays in `cfg.dtype`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu import models
from ray_tpu.models.transformer import ModelConfig, Transformer, _dense, _mesh_to_split_over, _rmsnorm, _rope
from ray_tpu.ops import attention

# What the engine and the layers round it may ask of this block (`models.require`).
SUPPORTS = frozenset({"lora", "speculation", "tp", "prefix_cache", "pd", "train", "checkpoint"})


def init_params(cfg: ModelConfig, key):
    """The tree `load_model` serves at random weights: the flax model's own."""
    return Transformer(cfg).init(key, jnp.zeros((1, 8), jnp.int32))["params"]


# The leaves `_forward_cached` reads only through a cast to `cfg.dtype`: every kernel (`_dense`
# casts it to its input's type) and the table (`embed[tokens].astype`: a cast commutes with a
# gather, and the tied head casts the table too). The norms read their scales in float32.
_CAST_ON_READ = frozenset({"q", "k", "v", "o", "gate", "up", "down", "lm_head"})


def serving_params(cfg: ModelConfig, params):
    """The tree the engine holds (`models/__init__.py`): kernels and table in `cfg.dtype`, which
    is the value every product read of them already, norm scales as given. A train step's or a
    checkpoint's tree is in `cfg.param_dtype`, float32 as a rule: held so, every program read
    twice the bytes it multiplied, or converted them once an execution."""
    return models.cast_leaves(
        params, cfg.dtype,
        lambda path: path == ("embedding",) or (path[-1] == "kernel" and path[-2] in _CAST_ON_READ))


def kv_slab_shape(cfg: ModelConfig, slots: int, max_seq: int) -> tuple:
    """One K or V slab of heads under 128 wide, kept whole rows of 128 lanes: the row-major
    elements of `[slots, max_seq, Hkv, D]` as `[slots, max_seq, Hkv * D // 128, 128]`. An
    array whose last axis is under 128 wide is not stored row-major on the TPU (its rows
    become the lanes), and every program that reads it a row at a time first copies all of
    it into another layout (PERF.md §6, PR 35). `_attn_cached` takes either form; the blocks
    whose engine paths take a slab only whole (`granite_hybrid`, `lfm2`) keep theirs so.
    This block's own (`init_caches`) stay a head a row: its prefix gather, attach and TP
    sharding address axis 2 as the KV heads (ROADMAP S5: the follow-up that ends the fork)."""
    width = cfg.n_kv_heads * cfg.head_dim
    if cfg.head_dim < 128 and 128 % cfg.head_dim == 0 and width % 128 == 0:
        return (slots, max_seq, width // 128, 128)
    return (slots, max_seq, cfg.n_kv_heads, cfg.head_dim)


def init_caches(cfg: ModelConfig, slots: int, max_seq: int) -> list:
    kv_shape = (slots, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return [
        (jnp.zeros(kv_shape, cfg.dtype), jnp.zeros(kv_shape, cfg.dtype))
        for _ in range(cfg.n_layers)
    ]


def init_stats(cfg: ModelConfig) -> tuple:
    """The block counts nothing: its programs return no stats."""
    return ()


def report(cfg: ModelConfig, total: tuple, window: tuple) -> dict:
    return {}


# -- pure functional forward over the param tree ---------------------------


def _lora_delta(x, A, B_, scale):
    """Per-slot low-rank delta: x [B,S,M]; A [B,M,r]; B_ [B,r,O]; scale [B]."""
    h = jnp.einsum("bsm,bmr->bsr", x, A.astype(x.dtype))
    d = jnp.einsum("bsr,bro->bso", h, B_.astype(x.dtype))
    return d * scale[:, None, None].astype(x.dtype)


def _attn_cached(layer, x, positions, cache_k, cache_v, write_at, cfg,
                 lora_layer=None, adapter_ids=None, write_gate=None, score_scale=None, rotate=True,
                 qk_norm=None):
    """One attention layer against the KV cache.

    x: [B, S, M]; positions: [B, S]; cache_k/v: [B, T, Hkv, D] (or [B, T, Hkv * D // 128,
    128], `kv_slab_shape`); write_at: [B], the slot's length: the S new rows are written
    at rows write_at + [0, S), and query i sees the rows j <= write_at + i. That one
    number a slot is all the visibility there is, so the rows past it are not read
    (`_cached_products`).
    lora_layer (optional): stacked adapters {"q_A": [A,M,r], "q_B": [A,r,H*D],
    "v_A", "v_B", "scale": [A]} gathered per slot by adapter_ids [B] — the
    multi-LoRA batching role of the reference's punica path, as plain gathers +
    batched matmuls so one jitted program serves any adapter mix.
    write_gate (optional): [B] bool — slots with a False gate leave their
    cache rows untouched (the batched speculative-verify program runs every
    slot through the forward but must only land KV for participants). The
    programs that step every slot at once hand one in (decode, verify); a
    one-slot view (prefill) has none, and that is what routes the attention:
    the kernel over the live rows for the first, the products for the second
    (`_cached_products`).
    score_scale: what the scores are multiplied by, 1 / sqrt(head_dim) where None; rotate: whether
    queries and keys take rotary positions (`models/granite_hybrid.py` serves its position-free
    attention layers through here with a scale of its own; the scope `kv_attn` holds the
    slab's write and the attention against it, for every model).
    qk_norm: (q scale, k scale), each [head_dim], of an RMSNorm over every head of q and of k
    before the rotation (`models/lfm2.py`; scope `qk_norm`); None: no norm and no operation more.
    """
    B, S, _ = x.shape
    q = _dense(x, layer["q"]["kernel"].reshape(cfg.hidden, -1)).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    )
    k = _dense(x, layer["k"]["kernel"].reshape(cfg.hidden, -1)).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim
    )
    v = _dense(x, layer["v"]["kernel"].reshape(cfg.hidden, -1)).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim
    )
    if lora_layer is not None:
        scale = lora_layer["scale"][adapter_ids]
        dq = _lora_delta(
            x, lora_layer["q_A"][adapter_ids], lora_layer["q_B"][adapter_ids], scale
        )
        q = q + dq.reshape(B, S, cfg.n_heads, cfg.head_dim)
        dv = _lora_delta(
            x, lora_layer["v_A"][adapter_ids], lora_layer["v_B"][adapter_ids], scale
        )
        v = v + dv.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if qk_norm is not None:
        with jax.named_scope("qk_norm"):
            q = _rmsnorm(q, qk_norm[0], cfg.norm_eps)
            k = _rmsnorm(k, qk_norm[1], cfg.norm_eps)
    if rotate:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    if score_scale is None:
        score_scale = 1.0 / math.sqrt(cfg.head_dim)
    with jax.named_scope("kv_attn"):
        out, cache_k, cache_v = _cached_products(q, k, v, cache_k, cache_v, write_at, cfg,
                                                 write_gate, score_scale)
    o_kernel = layer["o"]["kernel"].reshape(-1, cfg.hidden)
    proj = _dense(out.reshape(B, S, -1), o_kernel)
    return proj, cache_k, cache_v


def _cached_products(q, k, v, cache_k, cache_v, write_at, cfg, write_gate, scale):
    """The new rows into the slab, then attention against it. q: [B, S, H, D];
    k, v: [B, S, Hkv, D] -> (out [B, S, Hkv, G, D], cache_k, cache_v)."""
    B, S = q.shape[:2]
    new_k = k.astype(cache_k.dtype).reshape((B, S) + cache_k.shape[2:])
    new_v = v.astype(cache_v.dtype).reshape((B, S) + cache_v.shape[2:])
    # Grouped queries: head h reads KV head h // G, so Hkv is the major factor of the split.
    qg = q.reshape(B, S, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)
    # The programs that step every slot of the engine at once (decode, multi-step, verify:
    # the ones that gate their writes) go, on the TPU, to the kernel that reads of each slot
    # the row blocks up to its last visible row and nothing past it: the rows no slot holds
    # were most of a decode step (PERF.md §6, PR 35). It writes the step's rows itself, a copy
    # a slot behind its own reads: as a gated write of XLA's before it they were a serial loop
    # of slots x layers x (K, V) row writes, twice the attention they served (PERF.md §6, PR 47).
    # Their callers trace them under the engine's mesh (`llm/_engine.py:_traced_on`). A one-slot
    # view has no gate (a prefill chunk of any bucket, a detached prefill, the draft's own
    # steps): its queries are many or its rows few, and it takes one write of its rows and the
    # two products over the whole slab, with the mask built from the same lengths; so do, after
    # XLA's gated write, a slab of heads under 128 wide kept a head a row, which is not
    # row-major on the TPU, and every other backend, which has no kernel.
    if write_gate is not None and attention._use_pallas() and attention.cached_attention_takes(cache_k.shape[-1]):
        return _cached_attention_on_mesh(qg, cache_k, cache_v, write_at, scale, new_k, new_v, write_gate)
    if write_gate is None:
        origin = (0,) * (cache_k.ndim - 2)

        def put(slot_cache, slot_new, at):
            return jax.lax.dynamic_update_slice(slot_cache, slot_new, (at,) + origin)

        cache_k = jax.vmap(put)(cache_k, new_k, write_at)
        cache_v = jax.vmap(put)(cache_v, new_v, write_at)
    else:
        cache_k = attention.put_gated(cache_k, new_k, write_at, write_gate)
        cache_v = attention.put_gated(cache_v, new_v, write_at, write_gate)
    return attention.cached_attention_xla(qg, cache_k, cache_v, write_at, scale=scale), cache_k, cache_v


def _cached_attention_on_mesh(qg, cache_k, cache_v, lens, scale, new_k, new_v, gate, interpret: bool = False):
    """The kernel, writing the step's rows at `lens` where `gate`, on one device or under the
    mesh of the enclosing `with mesh:` (the TP engine traces its programs inside one) ->
    (out, cache_k, cache_v). A `pallas_call` has no partitioning rule, and attention is
    independent per KV head: under a mesh whose `tp` axis splits the slabs' heads the call runs
    inside a `shard_map` over that axis, the new rows split as the slabs are; where `tp` does not
    divide them the slabs are whole on every device (`llm/tp.py:kv_cache_sharding`) and so is the call."""
    mesh = _mesh_to_split_over()

    def run(qg, cache_k, cache_v, new_k, new_v, lens, gate):
        return attention.cached_attention(qg, cache_k, cache_v, lens, scale=scale, new_k=new_k, new_v=new_v,
                                          write_at=lens, gate=gate, interpret=interpret)

    if mesh is None:
        return run(qg, cache_k, cache_v, new_k, new_v, lens, gate)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    heads = "tp" if tp > 1 and qg.shape[2] % tp == 0 and cache_k.shape[2] % tp == 0 else None
    slab = P(None, None, heads)
    return jax.shard_map(run, mesh=mesh, in_specs=(slab,) * 5 + (P(), P()), out_specs=(slab,) * 3,
                         check_vma=False)(qg, cache_k, cache_v, new_k, new_v, lens, gate)


def _mlp(layer, x):
    gate = _dense(x, layer["gate"]["kernel"])
    up = _dense(x, layer["up"]["kernel"])
    return _dense(jax.nn.silu(gate) * up, layer["down"]["kernel"])


def _forward_cached(params, cfg: ModelConfig, tokens, positions, caches, write_at,
                    lora=None, adapter_ids=None, write_gate=None):
    """tokens: [B,S] -> logits [B,S,V]; updates caches in place (returned). write_at: [B],
    each slot's length before these tokens (`_attn_cached`).

    lora: the AdapterCache's STACKED tables ({"q_A": [L, S, M, r], ...}) —
    per-layer views are extracted here inside the trace, so paging swaps the
    whole table reference without touching program shapes.

    The named scopes are the flax model's module names (`layer_<i>/attn`,
    `mlp`, `attn_norm`, `mlp_norm`, `final_norm`, `lm_head`) plus `embedding`:
    one list of scopes reads a device trace of either model (PERF.md §3).
    They are metadata on the operations and change no program."""
    embed = params["embedding"]
    with jax.named_scope("embedding"):
        x = embed[tokens].astype(cfg.dtype)
    new_caches = []
    for i in range(cfg.n_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope(f"layer_{i}"):
            with jax.named_scope("attn_norm"):
                normed = _rmsnorm(x, layer["attn_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attn"):
                attn_out, ck, cv = _attn_cached(
                    layer["attn"], normed, positions, caches[i][0], caches[i][1],
                    write_at, cfg,
                    lora_layer=None if lora is None else {k: v[i] for k, v in lora.items()},
                    adapter_ids=adapter_ids,
                    write_gate=write_gate,
                )
            new_caches.append((ck, cv))
            x = x + attn_out
            with jax.named_scope("mlp_norm"):
                normed = _rmsnorm(x, layer["mlp_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                x = x + _mlp(layer["mlp"], normed)
    with jax.named_scope("final_norm"):
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = jax.lax.dot_general(
                x.astype(cfg.dtype), embed.astype(cfg.dtype),
                (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
        else:
            logits = _dense(x, params["lm_head"]["kernel"]).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
    return logits, new_caches


def _scatter_slot_caches(caches, new_slot, slot):
    """Write a [1, T, ...] slot view back into the full [B, T, ...] caches."""
    out = []
    for (ck_full, cv_full), (ck, cv) in zip(caches, new_slot):
        out.append((
            jax.lax.dynamic_update_slice(ck_full, ck.astype(ck_full.dtype),
                                         (slot, 0, 0, 0)),
            jax.lax.dynamic_update_slice(cv_full, cv.astype(cv_full.dtype),
                                         (slot, 0, 0, 0)),
        ))
    return out


# -- what the engine's programs call ------------------------------------------


def prefill(params, cfg: ModelConfig, tokens, caches, slot, offset, total_len,
            lora, adapter_id):
    """tokens: [1, Sbucket] right-padded, starting at row/position `offset`
    (0 = whole-prompt prefill; >0 = a later CHUNK, or suffix-only prefill
    behind a prefix cache hit whose KV was attached to rows [0, offset)).
    Writes slot `slot`'s cache rows [offset, offset+S). offset and total_len
    are traced scalars: a chunked prefill of any length mix reuses one program
    a bucket. Returns (logits of the prompt's last token, caches, no stats)."""
    S = tokens.shape[1]
    positions = offset + jnp.arange(S)[None, :]
    # one-slot caches view
    slot_caches = [
        (c[0][slot][None], c[1][slot][None]) for c in caches
    ]
    # visibility follows from the slot's length, `offset`: key row j <= global query
    # position offset+i; attached prefix rows [0, offset) are all visible, pad rows
    # beyond stay hidden
    logits, new_slot_caches = _forward_cached(
        params, cfg, tokens, positions, slot_caches, offset[None],
        lora=lora, adapter_ids=adapter_id[None],
    )
    out_caches = _scatter_slot_caches(caches, new_slot_caches, slot)
    last = logits[0, total_len - 1 - offset]
    return last, out_caches, ()


def decode(params, cfg: ModelConfig, last_token, caches, lens, gate, lora, adapter_ids):
    """One token for every slot. last_token: [B]; lens: [B] current lengths;
    gate: [B] bool, only slots in the decode phase land their KV row.
    Returns (logits [B, V], caches, no stats)."""
    positions = lens[:, None]
    # key j visible iff j <= lens (the new token writes at index lens)
    logits, new_caches = _forward_cached(
        params, cfg, last_token[:, None], positions, caches, lens,
        lora=lora, adapter_ids=adapter_ids, write_gate=gate,
    )
    return logits[:, 0], new_caches, ()


def verify(params, cfg: ModelConfig, tokens, caches, lens, gate, lora, adapter_ids):
    """The speculative phase's forward: tokens [B, k+1] at positions lens..lens+k
    for EVERY slot; slots with a False gate flow through for batching and leave
    their KV rows untouched. Returns (logits [B, k+1, V], caches, no stats)."""
    B, S = tokens.shape
    positions = lens[:, None] + jnp.arange(S)[None, :]
    logits, new_caches = _forward_cached(
        params, cfg, tokens, positions, caches, lens,
        lora=lora, adapter_ids=adapter_ids, write_gate=gate,
    )
    return logits, new_caches, ()


# -- the slab's rows on their way to and from the prefix pool and a PD peer ----


def gather_rows(caches, slot, *, rows: int):
    """Slot `slot`'s cache rows [0, rows) of every layer as one array in
    the prefix pool's layout, [L, 2, rows, Hkv, D] in the caches' dtype.
    The caches are read, not consumed: no donation."""

    def take(c):
        return jax.lax.dynamic_slice(
            c, (slot, 0, 0, 0), (1, rows) + c.shape[2:])[0]

    return jnp.stack([jnp.stack([take(ck), take(cv)]) for ck, cv in caches])


def attach_rows(caches, kv, slot):
    """Write a transferred KV prefix into slot's cache rows [0, P).
    kv: [L, 2, P, Hkv, D] (P = padded prefix bucket)."""
    out = []
    for i in range(len(caches)):
        ck = jax.lax.dynamic_update_slice(
            caches[i][0], kv[i, 0][None].astype(caches[i][0].dtype), (slot, 0, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            caches[i][1], kv[i, 1][None].astype(caches[i][1].dtype), (slot, 0, 0, 0)
        )
        out.append((ck, cv))
    return out


def prefill_detached(params, cfg: ModelConfig, tokens, lora, adapter_id):
    """Prefill that occupies no slot (the PD prefill side). tokens: [1, S]
    right-padded. Returns (logits [S, V], kv [L, 2, S, Hkv, D])."""
    S = tokens.shape[1]
    positions = jnp.arange(S)[None, :]
    caches = init_caches(cfg, 1, S)
    logits, new_caches = _forward_cached(
        params, cfg, tokens, positions, caches, jnp.zeros((1,), jnp.int32),
        lora=lora, adapter_ids=adapter_id[None],
    )
    kv = jnp.stack(
        [jnp.stack([ck[0], cv[0]]) for ck, cv in new_caches]
    )  # [L, 2, S, Hkv, D]
    return logits[0], kv


def prefill_detached_suffix(params, cfg: ModelConfig, prefix, tokens, off, lora,
                            adapter_id):
    """Detached prefill of a suffix (tokens [1, sb], right-padded) behind a cached
    prefix [L, 2, mb, Hkv, D] of which rows [0, off) are valid.
    Returns (logits [sb, V], the suffix's kv [L, 2, sb, Hkv, D])."""
    mb, sb = prefix.shape[2], tokens.shape[1]
    # cache layout: rows [0, off) = the attached prefix's valid rows, rows [off, off+sb) =
    # this pass's suffix writes, over the prefix's padding: the rows a query sees are then
    # the rows up to its own, as in every other program (`_attn_cached`).
    caches = []
    for i in range(cfg.n_layers):
        zeros = jnp.zeros(
            (1, sb, cfg.n_kv_heads, cfg.head_dim), cfg.dtype
        )
        caches.append((
            jnp.concatenate(
                [prefix[i, 0][None].astype(cfg.dtype), zeros], axis=1
            ),
            jnp.concatenate(
                [prefix[i, 1][None].astype(cfg.dtype), zeros], axis=1
            ),
        ))
    positions = off + jnp.arange(sb)[None, :]
    logits, new_caches = _forward_cached(
        params, cfg, tokens, positions, caches, off[None].astype(jnp.int32),
        lora=lora, adapter_ids=adapter_id[None],
    )
    suffix_kv = jnp.stack([
        jnp.stack([jax.lax.dynamic_slice_in_dim(ck[0], off, sb), jax.lax.dynamic_slice_in_dim(cv[0], off, sb)])
        for ck, cv in new_caches
    ])  # [L, 2, sb, Hkv, D]
    return logits[0], suffix_kv
