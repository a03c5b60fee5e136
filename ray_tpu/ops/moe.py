"""Mixture-of-Experts layers: the train step's and the serve path's.

Train (`MoEMLP`, `top_k_routing`): expert parallelism over the "ep" mesh axis. The
reference framework has no native expert parallelism (SURVEY.md §2.3: vLLM
kwargs pass-through only); here it is a library op. Design is the standard TPU
MoE recipe: top-k router → capacity-bounded dispatch (dense einsum with a
one-hot dispatch mask keeps everything static-shaped for XLA) → experts as a
batched matmul sharded over "ep" → combine weighted by router probs. With the
experts dimension sharded on "ep", pjit turns the dispatch/combine einsums into
all-to-alls over ICI — no hand-written collectives needed.

Shapes (E experts, C capacity per expert, k top-k):
    tokens  [B, S, M]  →  dispatch [B, S, E, C]  →  expert in [E, B*C', M] ...

Serve (`routed_experts`: `sigmoid_routing` or `softmax_routing`, `grouped_experts`, `swiglu`; every
served block with an expert layer calls it): sigmoid scores with a selection bias, or a softmax's
probabilities, and renormalised top-k, then a dropless product over the held experts' sorted, tiled pairs, then the shared expert
where the tree has one (below).
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


def top_k_routing(router_logits, k: int, capacity: int):
    """Compute dispatch/combine tensors from router logits.

    router_logits: [T, E] (T = flattened tokens). Returns:
      dispatch [T, E, C] bool-ish float: token t occupies slot c of expert e
      combine  [T, E, C] float: dispatch weighted by router prob
      aux_loss: load-balancing loss (Switch-style mean(prob)*mean(assignment)*E)
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _topv, topi = jax.lax.top_k(probs, k)  # [T, k]
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)  # [T, k, E]
    assignment = onehot.sum(1)  # [T, E] in {0,1} per expert
    # position of each token within its expert's queue (capacity slots)
    position_in_expert = (jnp.cumsum(assignment, axis=0) - assignment)  # [T, E]
    keep = assignment * (position_in_expert < capacity)
    slot = jax.nn.one_hot(position_in_expert, capacity, dtype=jnp.float32)  # [T,E,C]
    dispatch = keep[..., None] * slot  # [T, E, C]
    gates = probs * keep  # zero out dropped
    denom = gates.sum(-1, keepdims=True) + 1e-9
    combine = (gates / denom)[..., None] * dispatch
    # Switch load-balance loss
    density = assignment.mean(0)          # fraction routed per expert
    density_proxy = probs.mean(0)
    aux_loss = (density * density_proxy).sum() * E
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Drop-in MoE replacement for a dense MLP block.

    Partitioning: expert weights carry a leading E dim annotated with the
    "expert" logical axis → sharded over the mesh's ep axis by the rules table.
    """

    d_model: int
    d_ff: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16        # compute dtype
    param_dtype: jnp.dtype = jnp.float32   # storage dtype (f32: adamw updates
    # at lr*grad scale underflow bf16 mantissas and experts stop learning)

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        B, S, M = x.shape
        E, K = self.num_experts, self.top_k
        T = B * S
        capacity = max(1, int(self.capacity_factor * T * K / E))
        flat = x.reshape(T, M)

        router = self.param(
            "router",
            nn.with_logical_partitioning(nn.initializers.lecun_normal(), ("embed", None)),
            (M, E), jnp.float32,
        )
        logits = flat.astype(jnp.float32) @ router
        dispatch, combine, aux_loss = top_k_routing(logits, K, capacity)

        w_in = self.param(
            "w_in",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")
            ),
            (E, M, self.d_ff), self.param_dtype,
        ).astype(self.dtype)
        w_out = self.param(
            "w_out",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")
            ),
            (E, self.d_ff, M), self.param_dtype,
        ).astype(self.dtype)
        # dispatch: [T,E,C] x [T,M] -> expert inputs [E,C,M] (XLA inserts the
        # token->expert all-to-all when E is sharded on ep)
        expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(self.dtype), flat)
        h = jax.nn.silu(jnp.einsum("ecm,emf->ecf", expert_in, w_in))
        expert_out = jnp.einsum("ecf,efm->ecm", h, w_out)
        # combine back: [T,E,C] x [E,C,M] -> [T,M]
        out = jnp.einsum("tec,ecm->tm", combine.astype(self.dtype), expert_out)
        return out.reshape(B, S, M), aux_loss.astype(jnp.float32)


# -- dropless, sorted, grouped experts over the experts this chip holds -----------
#
# The serving path's expert product. The router chooses among
# ALL of a layer's experts; this chip holds `E` of them, ids [first, first + E).
# Token-expert pairs whose expert lives here are sorted by expert and laid out in
# tiles of `tile` rows, every expert's group starting on a tile boundary, and one
# loop runs a gated (three-matrix) expert over each tile that holds a pair: no
# capacity, no dropped token, and an expert nobody chose costs nothing. Pairs of
# absent experts add nothing here (their chips add them in a deployment). The
# train step's `MoEMLP` above is the older capacity-dropping one-hot dispatch.


def _matmul(a, b):
    """a [..., M] @ b [M, N] in a's type, accumulated in float32."""
    return jax.lax.dot_general(a, b.astype(a.dtype), (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32).astype(a.dtype)


def swiglu(p, x):
    """The gated expert E(h) = (silu(h Wg) * (h Wu)) Wd over a tree {gate, up, down: {kernel}}: a
    dense layer's feed-forward, and an expert layer's shared expert."""
    return _matmul(jax.nn.silu(_matmul(x, p["gate"]["kernel"])) * _matmul(x, p["up"]["kernel"]), p["down"]["kernel"])


def _router_logits(h, router_kernel):
    """h [N, D] @ W_r [D, E] in float32 at the highest precision: a near-tied expert is chosen by it."""
    return jax.lax.dot_general(
        h.astype(jnp.float32), router_kernel.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST)


def sigmoid_routing(h, router_kernel, router_bias, k: int, scaling: float = 1.0, eps: float = 0.0):
    """`noaux_tc` routing: scores `sigmoid(h W_r)` in float32, the `k` experts of
    largest score + bias chosen (the bias chooses and does not weigh), weights the
    chosen scores over their sum (plus `eps`, where a model's code adds one) times
    `scaling`. h: [N, D] -> (ids [N, k] int32, weights [N, k] float32)."""
    scores = jax.nn.sigmoid(_router_logits(h, router_kernel))
    _, ids = jax.lax.top_k(scores + router_bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    weights = chosen / (total + eps if eps else total) * scaling
    return ids.astype(jnp.int32), weights


def softmax_routing(h, router_kernel, k: int, scaling: float = 1.0):
    """The Qwen-MoE family's routing with `norm_topk_prob`: probabilities `softmax(h W_r)` in float32 over
    every expert, the `k` largest chosen, weights the chosen probabilities over their sum times `scaling`
    (the numbers a softmax over the `k` largest logits gives). h: [N, D] -> (ids [N, k] int32, weights
    [N, k] float32)."""
    chosen, ids = jax.lax.top_k(jax.nn.softmax(_router_logits(h, router_kernel), axis=-1), k)
    return ids.astype(jnp.int32), chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling


def expert_tile_rows(pairs: int, experts: int) -> int:
    """Rows a tile holds: near a held expert's fair share of the pairs, a power of
    two from 16 (a bfloat16 sublane tile) to 128 (an MXU pass)."""
    tile = 16
    while tile < 128 and tile * experts < pairs:
        tile *= 2
    return tile


def grouped_experts(x, ids, weights, valid, w_gate, w_up, w_down, first: int = 0,
                    tile: Optional[int] = None):
    """sum_k weights[n, k] * E_{ids[n, k]}(x[n]) over the pairs whose expert is held
    here, E(h) = (silu(h Wg) * (h Wu)) Wd.

    x: [N, D]; ids, weights: [N, K] (global expert ids); valid: [N] bool, rows that
    are padding route nowhere; w_gate, w_up: [E, D, F]; w_down: [E, F, D].
    Returns (y [N, D] in x's dtype, counts [E] int32: valid pairs per held expert)."""
    N, D = x.shape
    K = ids.shape[1]
    E = w_gate.shape[0]
    tile = tile or expert_tile_rows(N * K, E)
    n_pairs = N * K
    rows = -(-n_pairs // tile) * tile + E * tile  # every group may waste a tile's end

    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < E) & jnp.repeat(valid, K)
    key = jnp.where(held, local, E)  # pairs of absent experts sort to the end
    counts = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    group_tiles = -(-counts // tile)
    tiles_before = jnp.cumsum(group_tiles) - group_tiles
    pairs_before = jnp.cumsum(counts) - counts
    e_of = jnp.minimum(sorted_key, E - 1)
    dest_sorted = jnp.where(
        sorted_key < E,
        tiles_before[e_of] * tile + jnp.arange(n_pairs) - pairs_before[e_of], rows)
    dest = jnp.zeros((n_pairs,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))

    # each row's token by a gather (a scatter of whole rows is several times slower on the
    # TPU): only the small inverse map, pair of a row, is scattered; empty rows read a zero row
    pair_of_row = jnp.full((rows,), n_pairs, jnp.int32).at[dest].set(jnp.arange(n_pairs, dtype=jnp.int32), mode="drop")
    xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[pair_of_row // K]
    n_tiles = jnp.sum(group_tiles)
    tile_expert = jnp.searchsorted(jnp.cumsum(group_tiles), jnp.arange(rows // tile), side="right")
    tile_expert = jnp.minimum(tile_expert, E - 1).astype(jnp.int32)

    def one_tile(t, ys):
        e = tile_expert[t]
        xt = jax.lax.dynamic_slice(xs, (t * tile, 0), (tile, D))
        h = jax.nn.silu(_matmul(xt, w_gate[e])) * _matmul(xt, w_up[e])
        return jax.lax.dynamic_update_slice(ys, _matmul(h, w_down[e]), (t * tile, 0))

    ys = jax.lax.fori_loop(0, n_tiles, one_tile, jnp.zeros((rows, D), x.dtype))
    out = ys.at[dest].get(mode="fill", fill_value=0).reshape(N, K, D)
    y = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32), weights * held.reshape(N, K))
    return y.astype(x.dtype), counts


def routed_experts(p, x, valid, k: int, scaling: float = 1.0, eps: float = 0.0, first: int = 0,
                   score: str = "sigmoid"):
    """An expert layer over the tree p = {router: {kernel[, bias]}, experts: {gate, up, down}[,
    shared: {gate, up, down: {kernel}}]}, under the scopes `router`, `experts`, `shared_expert`:
    the held experts' part of the routed sum (ids [first, first + E)) plus the shared expert where
    the tree has one. `score` is the router's: "sigmoid" (`sigmoid_routing`; a router without a
    `bias` chooses by its scores alone) or "softmax" (`softmax_routing`: no bias, no `eps`).
    x: [..., D]; valid: x's leading shape, rows that are padding or gated off route nowhere.
    Returns (y as x, counts [E] int32 of valid pairs a held expert took)."""
    flat = x.reshape(-1, x.shape[-1])
    router = p["router"]
    with jax.named_scope("router"):
        if score == "softmax":
            ids, weights = softmax_routing(flat, router["kernel"], k, scaling)
        else:
            bias = router["bias"] if "bias" in router else jnp.zeros((router["kernel"].shape[-1],), jnp.float32)
            ids, weights = sigmoid_routing(flat, router["kernel"], bias, k, scaling, eps=eps)
    with jax.named_scope("experts"):
        y, counts = grouped_experts(flat, ids, weights, valid.reshape(-1), p["experts"]["gate"],
                                    p["experts"]["up"], p["experts"]["down"], first=first)
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + swiglu(p["shared"], flat)
    return y.reshape(x.shape), counts
