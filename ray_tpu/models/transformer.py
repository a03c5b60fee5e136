"""Decoder-only transformer (llama family), TPU-first.

The flagship model the framework trains and serves (reference trains torch models through
Ray Train and serves via vLLM; here the model is native: flax + Pallas flash attention +
logical-axis sharding). Every parameter is annotated with logical axis names which
parallel/mesh.py binds to the (dp, fsdp, tp, sp, pp, ep) hardware mesh — the same module
runs single-chip, FSDP, tensor-parallel, and sequence-parallel without code changes.

Architecture: RMSNorm, rotary embeddings, grouped-query attention, SwiGLU MLP, untied or
tied output head; bfloat16 activations with float32 RMSNorm accumulation (MXU-friendly).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax._src.mesh import thread_resources  # what `with mesh:` sets; pjit reads it too
from jax.sharding import PartitionSpec

from ray_tpu import models
from ray_tpu.ops.attention import reference_attention


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    mlp_dim: int = 1408
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # What `init_params`, a checkpoint and the train step hold the weights in (float32: the
    # optimizer's master copy). The serve engine holds in `dtype` what its programs multiply
    # in `dtype` (`models/__init__.py`: `serving_params`), cast once when a replica starts.
    param_dtype: Any = jnp.float32
    tie_embeddings: bool = False
    remat: bool = True
    # What the backward pass may keep from forward under remat:
    # "full"      recompute everything (lowest memory, ~20% slower/layer at 8B
    #             shape);
    # "attn"      save flash-attention outputs only;
    # "dots"      save every matmul output (XLA dots_saveable — fastest, but
    #             keeps the [S, mlp_dim] gate/up activations: ~330 MB/layer at
    #             the 8B shape, s2048);
    # "selective" save the attention-side tensors (post-rope q/k/v, attention
    #             out, o/down projections, pre-MLP norm) and RECOMPUTE the
    #             wide [S, mlp_dim] gate/up matmuls — ~100 MB/layer at the 8B
    #             shape: the memory/speed point that fits an fsdp=8 v5e pod.
    remat_policy: str = "full"
    scan_layers: bool = True
    attention: str = "flash"  # flash | reference | ring | ulysses
    sp_axis: str = "sp"
    # MoE: >0 replaces the dense MLP with that many experts (expert-parallel over
    # the "ep" mesh axis; reference has no native EP — SURVEY.md §2.3).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coeff: float = 0.01
    # Which block the layers are. "llama": the dense block of this module, which every
    # path of the framework runs. "dots3": latent attention with a learned sparse
    # indexer, windowed latent layers over a ring cache and sigmoid-routed experts
    # beside a shared one (`models/dots3.py`), which only the serve engine runs; the
    # fields below are that block's, under its published names where the two agree.
    # `n_heads` and `rope_theta` are the full layers'; `mlp_dim` the leading dense layers'.
    # "granite_hybrid": Mamba-2 layers whose recurrent state lives in the engine's slots
    # beside the KV rows of a few position-free GQA layers (`models/granite_hybrid.py`,
    # served only); its fields are the group after dots3's. "lfm2": gated short convolutions whose last
    # inputs live in the engine's slots, GQA layers with q/k norms and rotary, all of a layer's
    # sigmoid-routed experts held (`models/lfm2.py`, served only): dots3's expert fields
    # (`first_k_dense`, `n_routed_experts*`, `experts_per_token`, `moe_mlp_dim`) and `conv_L_cache`.
    # "pangu_moe": dense latent attention over the whole cache (dots3's full-layer latent fields, `mla_rescale`
    # off), a norm after every sub-layer as well as before it, dots3's expert fields (`models/pangu_moe.py`,
    # served only); every layer is of one kind, so it takes no `layer_types`.
    # "xing4": pangu_moe's latent attention and expert layer (with a selection bias) round a residual of
    # `hc_mult` streams mixed by manifold-constrained hyper-connections (`ops/hyper_connection.py`), rotary
    # frequencies scaled as `rope_scaling` says (`models/xing4.py`, served only); the `hc_*` fields are its own.
    # "laguna": grouped-query layers of two kinds in one model, full layers of `n_heads` over `max_seq`-row K/V slabs and
    # window layers of `swa_n_heads` over a ring of `sliding_window` rows, heads of `head_width`, a sigmoid gate a head, a
    # rotary table a kind (`rope_theta` under `rope_scaling` over `partial_rotary_factor` of a head; `swa_rope_theta` over
    # all of it), softmax-routed experts (`router_score`) beside a shared one (`models/laguna.py`, served only).
    block: str = "llama"
    layer_types: tuple = ()            # per layer; dots3, laguna: "full_attention" | "sliding_attention";
                                       # granite_hybrid: "mamba" | "attention"; lfm2: "conv" | "full_attention"
    head_width: int = 0                # a head's width where the config states one; 0: hidden // n_heads (`head_dim`)
    partial_rotary_factor: float = 1.0  # the share of a head, from its start, that a full layer rotates
    router_score: str = "sigmoid"      # what an expert layer's router makes of its logits: "sigmoid" | "softmax"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_rescale: bool = True           # latents scaled by sqrt(hidden / rank) after their norms
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    sliding_window: int = 0            # positions a sliding layer sees, the token itself counted
    swa_n_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    first_k_dense: int = 1             # leading layers with a dense MLP
    n_routed_experts_total: int = 0    # the router's width
    n_routed_experts: int = 0          # routed experts this chip holds ...
    first_expert: int = 0              # ... from this id on
    n_shared_experts: int = 1
    experts_per_token: int = 0
    moe_mlp_dim: int = 0
    routed_scaling_factor: float = 1.0
    mamba_n_heads: int = 0             # heads of a mamba layer's recurrence ...
    mamba_d_head: int = 0              # ... channels a head (n_heads x d_head = the inner width)
    mamba_d_state: int = 0             # state a channel; B and C are this wide (one group)
    mamba_d_conv: int = 4              # taps of the causal depthwise convolution
    mamba_chunk_size: int = 256        # positions a block of the prefill scan takes at once
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # the attention layers' score scale
    logits_scaling: float = 1.0        # logits are divided by it
    position_embedding_type: str = "rope"  # "nope": the attention layers rotate nothing
    conv_L_cache: int = 3              # lfm2: taps of a conv layer's causal depthwise convolution
    hc_mult: int = 0                   # xing4: streams of the residual path
    hc_sinkhorn_iters: int = 0         # column-then-row normalisations that make the streams' mixing matrix doubly stochastic
    hc_eps: float = 1e-6               # under the streams' RMS and under every Sinkhorn divisor
    hc_res_clamp_min: float = -30.0    # the mixing matrix's logits are held to [min, max] before the exponential
    hc_res_clamp_max: float = 30.0
    # The published `rope_scaling` group ({"type": "yarn", "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "mscale", "mscale_all_dim"}), kept as sorted pairs so that the config stays hashable; None: plain rotary.
    # Read by the latent-attention blocks through `models/latent.py:attn_dims` (`yarn_inv_freq`, `yarn_mscale` below);
    # laguna's group carries its own `attention_factor`, which its cos and sin are multiplied by.
    rope_scaling: Any = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))  # a JSON list, hashable
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if (self.block != "llama" and len(self.layer_types) != self.n_layers
                and models.names_its_layers(self)):
            raise ValueError(f"block {self.block!r} needs one of layer_types per layer: "
                             f"{len(self.layer_types)} for n_layers={self.n_layers}")

    @property
    def head_dim(self) -> int:
        return self.head_width or self.hidden // self.n_heads

    def num_params(self) -> int:
        e = self.vocab_size * self.hidden
        attn = self.hidden * self.head_dim * (self.n_heads * 2 + self.n_kv_heads * 2)
        if self.moe_experts > 0:
            # router + per-expert in/out projections (2 matmuls each)
            mlp = self.hidden * self.moe_experts + (
                self.moe_experts * 2 * self.hidden * self.mlp_dim
            )
        else:
            mlp = 3 * self.hidden * self.mlp_dim
        norms = 2 * self.hidden
        per_layer = attn + mlp + norms
        head = 0 if self.tie_embeddings else e
        return e + self.n_layers * per_layer + self.hidden + head


# Named configs; parameter counts cited for parity with common baselines.
CONFIGS: dict[str, ModelConfig] = {
    "test-tiny": ModelConfig(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=2, mlp_dim=128,
        max_seq=128, dtype=jnp.float32, remat=False, scan_layers=False,
        attention="reference",
    ),
    "gpt2-125m": ModelConfig(
        vocab_size=50257, hidden=768, n_layers=12, n_heads=12, n_kv_heads=12,
        mlp_dim=3072, max_seq=1024, tie_embeddings=True,
    ),
    "llama3-1b": ModelConfig(
        vocab_size=128256, hidden=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        mlp_dim=8192, max_seq=8192, tie_embeddings=True,
    ),
    "llama3-8b": ModelConfig(
        vocab_size=128256, hidden=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        mlp_dim=14336, max_seq=8192,
    ),
}


def yarn_inv_freq(head_dim: int, theta: float, scaling: dict):
    """YaRN's frequency table, float32 [head_dim / 2]: pair i turns at theta^(-2i/d) where it completes more than
    `beta_fast` turns over the original window, at that over `factor` where it completes fewer than `beta_slow`,
    and at a linear blend of the two between (the published "yarn" `rope_scaling`; at factor 1 the plain table)."""
    import numpy as np

    half, factor, window = head_dim // 2, float(scaling["factor"]), scaling["original_max_position_embeddings"]
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair_of(turns):  # the pair that completes `turns` turns over the original window
        return head_dim * math.log(window / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(scaling.get("beta_slow", 1))), head_dim - 1)
    blend = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * (1.0 - blend) + plain / factor * blend).astype(np.float32)


def yarn_mscale(scaling: dict, key: str) -> float:
    """YaRN's magnitude m(s) = 0.1 s ln(factor) + 1 for s = scaling[key] (1 where the factor is 1 or the key absent or 0):
    cos and sin carry m(mscale) / m(mscale_all_dim), the scores m(mscale_all_dim)^2."""
    s, factor = scaling.get(key) or 0.0, float(scaling["factor"])
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1.0 and s else 1.0


def _rope_angles(positions: jax.Array, head_dim: int, theta: float, inv_freq=None):
    """cos/sin tables for rotary embedding: [B,S,half] f32 each. `inv_freq`, where given, is the
    table of frequencies [half] in place of theta's (a scaled rotary: `yarn_inv_freq`).

    Computed ONCE per forward (Transformer.__call__) and broadcast through the
    layer scan — inside the scan the transcendentals re-ran every layer (XLA
    does not hoist loop-invariant code out of scans; ~4 ms/step measured at
    the bench shape)."""
    half = head_dim // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    if positions.ndim == 1:
        positions = positions[None, :]
    # Angle computation stays f32 (position * freq overflows bf16 precision
    # fast); the rotation itself runs in the activation dtype — the [B,S,H,D]
    # elementwise traffic is the cost, and bf16 halves it per layer.
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    return jnp.cos(angles), jnp.sin(angles)


def _rope_apply(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply the rotation. x: [B,S,H,D]; cos/sin: [B,S,D//2] f32."""
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rope_apply_bhsd(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply the rotation in the kernel-native layout. x: [B,H,S,D]."""
    cos = cos[:, None, :, :].astype(x.dtype)
    sin = sin[:, None, :, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rope(x: jax.Array, positions: jax.Array, theta: float, inv_freq=None) -> jax.Array:
    """Rotary position embedding. x: [B, S, H, D]; positions: [B, S] or [S]."""
    cos, sin = _rope_angles(positions, x.shape[-1], theta, inv_freq)
    return _rope_apply(x, cos, sin)


def _dense(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """x [..., M] @ kernel [M, N] in x's dtype, accumulated in float32: the serve
    path's matrix product (the engine's own model and `models/dots3.py`)."""
    return jax.lax.dot_general(
        x, kernel.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis in float32, returned in x's dtype (serve path)."""
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


class _HeadProj(nn.Module):
    """[B,S,E] -> [B,H,S,D] projection: the head/seq transpose folds into the
    matmul itself instead of materializing in HBM (the flash kernel consumes
    [B,H,S,D] natively). Param tree identical to the DenseGeneral it replaces
    (kernel [E,H,D] under the same name) — checkpoints are interchangeable."""

    heads: int
    head_dim: int
    dtype: Any
    param_dtype: Any
    axis_names: tuple

    @nn.compact
    def __call__(self, x):
        # DenseGeneral initializes multi-dim kernels on the FLATTENED 2-D
        # shape (fan-in = E) and reshapes; replicate exactly so this param is
        # bit-identical to the DenseGeneral it replaces under the same rng.
        def init(key, shape, dtype):
            flat = (shape[0], shape[1] * shape[2])
            return nn.initializers.lecun_normal()(key, flat, dtype).reshape(shape)

        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(init, self.axis_names),
            (x.shape[-1], self.heads, self.head_dim),
            self.param_dtype,
        )
        return jnp.einsum(
            "bse,ehd->bhsd", x.astype(self.dtype), kernel.astype(self.dtype)
        )


class _OutProjBhsd(nn.Module):
    """[B,H,S,D] -> [B,S,E]; kernel [H,D,E] matches DenseGeneral axis=(-2,-1)."""

    features: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        def init(key, shape, dtype):
            flat = (shape[0] * shape[1], shape[2])
            return nn.initializers.lecun_normal()(key, flat, dtype).reshape(shape)

        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(init, ("heads", "head_dim", "embed")),
            (x.shape[1], x.shape[-1], self.features),
            self.param_dtype,
        )
        return jnp.einsum(
            "bhsd,hde->bse", x.astype(self.dtype), kernel.astype(self.dtype)
        )


def _mesh_to_split_over():
    """The mesh of the enclosing `with mesh:`; None outside any, on one device, or
    already inside a `shard_map`."""
    mesh = thread_resources.env.physical_mesh
    if mesh.empty or mesh.size == 1 or not jax.sharding.get_abstract_mesh().empty:
        return None
    return mesh


def _axis_tuple(a) -> tuple:
    """One entry of `nn.logical_to_mesh_axes` (a name, a tuple of names or None) as a tuple."""
    return (a,) if isinstance(a, str) else tuple(a or ())


def _flash_on_mesh(flash, q, k, v, names: tuple):
    """Call a flash-attention entry point under the mesh of the enclosing `with mesh:`.

    The TPU compiler refuses to partition a Mosaic kernel ("wrap the call in a
    shard_map"), so a step over more than one device cannot leave the kernel to
    GSPMD. Batch and heads are split the way the logical rules split them; the
    sequence and head_dim stay whole on every shard, which is what the kernel
    needs. Heads are split only if q and kv heads split alike and evenly (GQA
    groups must stay together); otherwise every shard computes all heads. The
    CPU takes the same path, so the virtual-mesh tests cover it.

    `names` are q's logical axis names, e.g. ("batch", "heads", "seq", "head_dim").
    """
    mesh = _mesh_to_split_over()
    if mesh is None:
        return flash(q, k, v, True, None)
    axes = dict(zip(names, nn.logical_to_mesh_axes(names)))
    kv_axes = nn.logical_to_mesh_axes(("kv_heads",))[0]

    def ways(a) -> int:
        return math.prod(mesh.shape[x] for x in _axis_tuple(a))

    i_b, i_h = names.index("batch"), names.index("heads")
    batch = axes["batch"] if q.shape[i_b] % ways(axes["batch"]) == 0 else None
    heads = axes["heads"]
    if heads != kv_axes or q.shape[i_h] % ways(heads) or k.shape[i_h] % ways(heads):
        heads = None
    parts = [None] * len(names)
    parts[i_b], parts[i_h] = batch, heads
    spec = PartitionSpec(*parts)
    return jax.shard_map(
        lambda q, k, v: flash(q, k, v, True, None), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        # The mean-of-squares reduction runs in f32 (768 bf16 squares summed
        # in bf16 would lose ~2 decimal digits); the normalization multiply
        # runs in the activation dtype — for bf16 models that halves this
        # op's elementwise/HBM cost, and the values were about to be rounded
        # to bf16 anyway. f32 models are bit-identical to the f32-throughout
        # form.
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return x * (inv.astype(x.dtype) * scale.astype(x.dtype))


class Attention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, rope=None):
        cfg = self.cfg
        if cfg.attention == "flash":
            return self._flash_bhsd(x, positions, rope)
        dense = lambda features, names, name: nn.DenseGeneral(  # noqa: E731
            features,
            axis=-1,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), names
            ),
            name=name,
        )
        q = dense((cfg.n_heads, cfg.head_dim), ("embed", "heads", "head_dim"), "q")(x)
        k = dense((cfg.n_kv_heads, cfg.head_dim), ("embed", "kv_heads", "head_dim"), "k")(x)
        v = dense((cfg.n_kv_heads, cfg.head_dim), ("embed", "kv_heads", "head_dim"), "v")(x)
        if rope is None:
            rope = _rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        q = _rope_apply(q, *rope)
        k = _rope_apply(k, *rope)
        if cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            # Saving post-rope q/k/v lets the flash backward kernel run
            # without recomputing projections+rope; k/v are small under GQA.
            q = checkpoint_name(q, "save")
            k = checkpoint_name(k, "save")
            v = checkpoint_name(v, "save")

        if cfg.attention == "reference":
            out = reference_attention(q, k, v, causal=True)
        elif cfg.attention == "ring":
            from ray_tpu.ops.ring_attention import ring_attention

            out = ring_attention(q, k, v, cfg.sp_axis, causal=True)
        elif cfg.attention == "ulysses":
            from ray_tpu.ops.ring_attention import ulysses_attention

            out = ulysses_attention(q, k, v, cfg.sp_axis, causal=True)
        else:
            raise ValueError(f"unknown attention {cfg.attention!r}: flash | reference | ring | ulysses")
        if cfg.remat and cfg.remat_policy == "attn":
            from jax.ad_checkpoint import checkpoint_name

            out = checkpoint_name(out, "attn_out")
        elif cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            out = checkpoint_name(out, "save")

        proj = nn.DenseGeneral(
            cfg.hidden,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")
            ),
            name="o",
        )(out)
        return proj

    def _flash_bhsd(self, x, positions, rope):
        """Transpose-free train path: projections emit [B,H,S,D] directly,
        the flash kernel runs in its native layout, and the output projection
        contracts straight back to [B,S,E] — the 11 per-layer HBM transposes
        of the [B,S,H,D] route never materialize. Same param tree."""
        from ray_tpu.ops.attention import flash_attention_bhsd

        cfg = self.cfg
        q = _HeadProj(cfg.n_heads, cfg.head_dim, cfg.dtype, cfg.param_dtype,
                      ("embed", "heads", "head_dim"), name="q")(x)
        k = _HeadProj(cfg.n_kv_heads, cfg.head_dim, cfg.dtype, cfg.param_dtype,
                      ("embed", "kv_heads", "head_dim"), name="k")(x)
        v = _HeadProj(cfg.n_kv_heads, cfg.head_dim, cfg.dtype, cfg.param_dtype,
                      ("embed", "kv_heads", "head_dim"), name="v")(x)
        if rope is None:
            rope = _rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        q = _rope_apply_bhsd(q, *rope)
        k = _rope_apply_bhsd(k, *rope)
        if cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            q = checkpoint_name(q, "save")
            k = checkpoint_name(k, "save")
            v = checkpoint_name(v, "save")
        out = _flash_on_mesh(flash_attention_bhsd, q, k, v,
                             ("batch", "heads", "seq", "head_dim"))
        if cfg.remat and cfg.remat_policy == "attn":
            from jax.ad_checkpoint import checkpoint_name

            out = checkpoint_name(out, "attn_out")
        elif cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            out = checkpoint_name(out, "save")
        return _OutProjBhsd(cfg.hidden, cfg.dtype, cfg.param_dtype,
                            name="o")(out)


class MLP(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda features, names, name: nn.DenseGeneral(  # noqa: E731
            features,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), names
            ),
            name=name,
        )
        gate = dense(cfg.mlp_dim, ("embed", "mlp"), "gate")(x)
        up = dense(cfg.mlp_dim, ("embed", "mlp"), "up")(x)
        down = dense(cfg.hidden, ("mlp", "embed"), "down")(nn.silu(gate) * up)
        if cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            # Save the NARROW down-projection output; the wide [S, mlp_dim]
            # gate/up activations are recomputed in backward.
            down = checkpoint_name(down, "save")
        return down


class Block(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, rope=None):
        cfg = self.cfg
        attn_out = Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions, rope
        )
        x = x + attn_out
        normed = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
        if cfg.remat and cfg.remat_policy == "selective":
            from jax.ad_checkpoint import checkpoint_name

            normed = checkpoint_name(normed, "save")
        if cfg.moe_experts > 0:
            from ray_tpu.ops.moe import MoEMLP

            mlp_out, aux = MoEMLP(
                d_model=cfg.hidden, d_ff=cfg.mlp_dim,
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="moe",
            )(normed)
        else:
            mlp_out = MLP(cfg, name="mlp")(normed)
            aux = jnp.zeros((), jnp.float32)
        x = x + mlp_out
        return x, aux


class Transformer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False):
        cfg = self.cfg
        models.require(cfg, "train")
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :].astype(jnp.int32)
        embed = self.param(
            "embedding",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden),
            cfg.param_dtype,
        )
        with jax.named_scope("embedding"):  # a parameter, not a module: flax names no scope
            x = embed[tokens].astype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        # Rotary cos/sin computed once, broadcast into every layer (the scan
        # would otherwise recompute the transcendentals per layer).
        rope = _rope_angles(positions, cfg.head_dim, cfg.rope_theta)

        def remat_block():
            if cfg.remat_policy == "attn":
                policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_out"
                )
            elif cfg.remat_policy == "selective":
                policy = jax.checkpoint_policies.save_only_these_names(
                    "save", "flash_residuals"
                )
            elif cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_saveable
            else:
                policy = None
            return nn.remat(Block, prevent_cse=False, policy=policy)

        if cfg.scan_layers:
            block = Block
            if cfg.remat:
                block = remat_block()
            ScannedBlocks = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
                in_axes=(nn.broadcast, nn.broadcast),
            )
            x, aux_stack = ScannedBlocks(cfg, name="layers")(
                x, positions, rope
            )
            moe_aux = jnp.sum(aux_stack)
        else:
            moe_aux = jnp.zeros((), jnp.float32)
            for i in range(cfg.n_layers):
                block_cls = remat_block() if cfg.remat else Block
                x, aux = block_cls(cfg, name=f"layer_{i}")(x, positions, rope)
                moe_aux = moe_aux + aux
        if cfg.moe_experts > 0:
            # Reaches the training loss without changing the return signature:
            # apply(..., mutable=["losses"]) surfaces it; plain apply ignores it.
            self.sow("losses", "moe_aux", cfg.moe_aux_coeff * moe_aux)

        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if return_hidden:
            # Training fast path: the caller computes a chunked fused
            # cross-entropy against the embedding table instead of
            # materializing [B,S,V] float32 logits (see fused_cross_entropy_loss).
            return x
        # Head matmul on the MXU bf16 path with f32 accumulation (an f32 matmul here
        # costs ~8x MXU throughput); loss math stays f32 downstream.
        if cfg.tie_embeddings:
            logits = jax.lax.dot_general(
                x.astype(cfg.dtype), embed.astype(cfg.dtype),
                (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            logits = nn.DenseGeneral(
                cfg.vocab_size,
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")
                ),
                name="lm_head",
            )(x).astype(jnp.float32)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))


def cross_entropy_loss(logits, targets, mask=None):
    """Mean next-token loss. logits:[B,S,V] float32; targets:[B,S] int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def fused_cross_entropy_loss(hidden, table, targets, mask=None, *, chunk=256,
                             contract_dim=1, compute_dtype=jnp.bfloat16):
    """Chunked head-matmul + cross-entropy that never materializes full logits.

    [B,S,V] float32 logits are written and re-read in the forward pass and again as
    the softmax's gradient in the backward pass. Here a chunk of the sequence at a
    time is multiplied by the head, so at most [B,chunk,V] logits are live in either
    pass; the backward pass computes each chunk's logits again (a second head matmul)
    rather than keep them. `build_train_step` takes this path once whole logits
    would pass 2 GB.

    hidden: [B,S,E] (pre-head, post-final-norm); table: the tied embedding
    [V,E] (contract_dim=1) or an untied lm_head kernel [E,V] (contract_dim=0);
    targets: [B,S] int32. Matches cross_entropy_loss numerically (same bf16
    matmul with f32 accumulation as the model head).

    On a mesh. Called under `with mesh:` and `nn.logical_axis_rules`, the function
    reads which mesh axes the rules give `batch`. Where axes of more than one device
    split the batch, both chunk loops run on each device's own sequences against
    the whole table (a `shard_map` over those axes; `tp` and `sp` stay the
    compiler's): the cast table is gathered once on its way in, each device sums its
    partial gradient of the whole table over the chunks in float32, and those sums
    meet once, in the compute dtype, on their way out. No loop's body holds a
    collective of the table's size, whatever the number of chunks; left to the
    compiler, each chunk gathers the table twice and all-reduces its gradient
    (PERF.md §6, PR 31). The mask is a constant there: it gets no gradient. With no
    mesh or no rules, on one device, with no axis that splits the batch (`tp` or
    `sp` alone), with a batch the axes do not divide, or inside another
    `shard_map`, the scan runs as it is and its collectives are the compiler's.
    """
    split = _loss_split(hidden.shape)
    if split is None:
        total, count = _chunk_sums_scan(hidden, table, targets, mask, chunk,
                                        contract_dim, compute_dtype)
    else:
        total, count = _chunk_sums_on_mesh(hidden, table.astype(compute_dtype), targets,
                                           mask, chunk, contract_dim, *split)
    return total / jnp.maximum(count, 1.0)


def _loss_split(hidden_shape):
    """(mesh, the mesh axes of more than one device that split the batch) for
    `fused_cross_entropy_loss`, or None where the loop is left to the compiler. Read
    from the mesh of the enclosing `with mesh:` and the logical rules in force, as
    `_flash_on_mesh` reads them."""
    mesh = _mesh_to_split_over()
    if mesh is None:
        return None
    batch = tuple(x for x in _axis_tuple(nn.logical_to_mesh_axes(("batch",))[0])
                  if mesh.shape[x] > 1)
    if not batch or hidden_shape[0] % math.prod(mesh.shape[x] for x in batch):
        return None
    return mesh, batch


def _in_chunks(hidden, targets, mask, chunk):
    """Sequence chunks first, for a scan: ([n,B,c,E], [n,B,c], the mask's [n,B,c] or None)."""
    B, S, E = hidden.shape
    c = math.gcd(S, chunk)
    n = S // c
    hs = hidden.reshape(B, n, c, E).swapaxes(0, 1)
    ts = targets.reshape(B, n, c).swapaxes(0, 1)
    ms = None if mask is None else mask.reshape(B, n, c).swapaxes(0, 1)
    return hs, ts, ms


def _head_logits(h, w, contract_dim):
    """[B,c,E] x the head -> [B,c,V] float32; operands in w's dtype, as the model's head."""
    return jax.lax.dot_general(
        h.astype(w.dtype), w, (((2,), (contract_dim,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _chunk_loss_sums(h, w, t, m, contract_dim):
    """One chunk's (sum of the tokens' losses, their count)."""
    logits = _head_logits(h, w, contract_dim)  # [B,c,V]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if m is not None:
        return jnp.sum(nll * m), jnp.sum(m)
    return jnp.sum(nll), jnp.asarray(nll.size, jnp.float32)


def _scan_sums(chunk_sums, xs):
    """Add `chunk_sums(h, t, m)` over the chunks `xs` (of `_in_chunks`)."""
    def body(carry, x):
        s, cnt = chunk_sums(*x)
        return (carry[0] + s, carry[1] + cnt), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    return jax.lax.scan(body, init, xs)[0]


def _chunk_sums_scan(hidden, table, targets, mask, chunk, contract_dim, compute_dtype):
    """(sum of the tokens' losses, their count): a scan over sequence chunks whose body,
    under `jax.checkpoint`, casts the table and multiplies; autodiff makes the backward
    loop. Every collective a split table or batch needs is the compiler's, in the body."""
    @jax.checkpoint
    def chunk_sums(h, t, m):
        return _chunk_loss_sums(h, table.astype(compute_dtype), t, m, contract_dim)

    return _scan_sums(chunk_sums, _in_chunks(hidden, targets, mask, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _chunk_sums_on_mesh(hidden, w, targets, mask, chunk, contract_dim, mesh, batch_axes):
    """`_chunk_sums_scan` against the cast table `w`, with both loops inside a
    `shard_map` over the axes that split the batch: the same sums from the same
    products, each device on its own sequences against the whole of `w`. What crosses
    the devices does so where a `shard_map` begins or ends, once: `w` is gathered on
    its way in, and the devices' partial gradients of it are summed on their way out.
    The backward loop is written out because the scan's own transpose would sum the
    whole table's cotangent over the chunks in w's dtype; here that sum is float32."""
    return _on_mesh_fwd(hidden, w, targets, mask, chunk, contract_dim, mesh, batch_axes)[0]


def _per_device(f, mesh, batch_axes, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(batch_axes), check_vma=False)


def _on_mesh_fwd(hidden, w, targets, mask, chunk, contract_dim, mesh, batch_axes):
    rows, whole = PartitionSpec(batch_axes), PartitionSpec()

    def device_sums(hidden, w, targets, mask):
        sums = _scan_sums(lambda h, t, m: _chunk_loss_sums(h, w, t, m, contract_dim),
                          _in_chunks(hidden, targets, mask, chunk))
        return jax.lax.psum(sums, batch_axes)

    sums = _per_device(device_sums, mesh, batch_axes,
                       (rows, whole, rows, None if mask is None else rows), whole,
                       )(hidden, w, targets, mask)
    return sums, (hidden, w, targets, mask)


def _on_mesh_bwd(chunk, contract_dim, mesh, batch_axes, saved, cts):
    hidden, w, targets, mask = saved
    rows, whole = PartitionSpec(batch_axes), PartitionSpec()

    def device_grads(g, hidden, w, targets, mask):
        def body(dw, x):
            h, t, m = x
            p = jax.nn.softmax(_head_logits(h, w, contract_dim), axis=-1)
            scale = g if m is None else g * m[..., None]
            dlogits = ((p - jax.nn.one_hot(t, p.shape[-1], dtype=p.dtype)) * scale).astype(w.dtype)
            dh = jax.lax.dot_general(
                dlogits, w, (((2,), (1 - contract_dim,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(h.dtype)
            hc = h.astype(w.dtype)
            dw = dw + jax.lax.dot_general(
                *((hc, dlogits) if contract_dim == 0 else (dlogits, hc)),
                (((0, 1), (0, 1)), ((), ())), preferred_element_type=jnp.float32)
            return dw, dh

        dw, dhs = jax.lax.scan(body, jnp.zeros(w.shape, jnp.float32),
                               _in_chunks(hidden, targets, mask, chunk))
        # this device's float32 sum over the chunks leaves in w's dtype, one of a stack
        # of as many as there are devices along the batch
        return dhs.swapaxes(0, 1).reshape(hidden.shape), dw.astype(w.dtype)[None]

    dhidden, dws = _per_device(device_grads, mesh, batch_axes,
                               (whole, rows, whole, rows, None if mask is None else rows),
                               (rows, rows))(cts[0], hidden, w, targets, mask)
    # The stack's sum is the one reduction of the table's size, made in the compute
    # dtype as the layers' gradients are (jnp.sum would make it in float32).
    dw = jax.lax.reduce(dws, jnp.zeros((), dws.dtype), jax.lax.add, (0,))
    return dhidden, dw, None, None


_chunk_sums_on_mesh.defvjp(_on_mesh_fwd, _on_mesh_bwd)


def init_params(cfg: ModelConfig, rng=None, batch: int = 1, seq: int | None = None):
    model = Transformer(cfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seq = seq or min(cfg.max_seq, 128)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    return model, model.init(rng, tokens)


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in CONFIGS:
        raise ValueError(f"unknown model {name!r}; known: {sorted(CONFIGS)}")
    cfg = CONFIGS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
