"""Pipeline parallelism: GPipe-style microbatched stages over the `pp` mesh axis.

The reference provides pipeline parallelism only through vLLM/compiled-graph actor
pipelines (reference: python/ray/dag/ per-actor exec loops; vllm_models.py:219
pipeline_parallel_size pass-through). TPU-native, the pipeline is a single SPMD
program: layers are stacked on a leading dim and sharded over `pp` (each stage holds
L/S layers), microbatched activations circulate stage-to-stage with `lax.ppermute`,
and the whole forward — scan over (num_microbatches + S - 1) pipeline ticks — is
differentiable, so jax.grad produces the backward pipeline (reversed ppermutes) with
gradients accumulated across microbatches automatically.

Schedule: plain GPipe fill-drain. The bubble fraction is (S-1)/(M+S-1); pick
num_microbatches >= ~4x the stage count. The head/loss pass runs ONCE after
the tick scan, as a sequential lax.map over the M collected microbatches with
non-final stages masked out: every stage executes the identical collective
sequence (a per-stage lax.cond skip would deadlock — the replicated head
params' gradient psum would run inside a branch only the last stage takes),
and the sequential map keeps exactly one microbatch's [b, T, V] logits live
at a time instead of materializing all M at once.

Composition (round 5): pp (and dp) are MANUAL shard_map axes — the ppermute
schedule needs them — while every other mesh axis (tp, sp, ...) stays AUTO
(`jax.shard_map(..., axis_names={"pp", "dp"})`): layer/head params placed with
tp-sharded feature dims keep those shardings inside the pipelined program and
XLA inserts the tensor-parallel collectives around the stage matmuls, exactly
as it would outside the pipeline. Sequence parallelism composes the same way
(Ulysses-style resharding via sharding constraints inside layer_fn). The
reference reaches TP x PP only by passing both sizes through to vLLM
(vllm_models.py:215-219); here the composition is one SPMD program.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class PipelineState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


def _check_mesh(mesh: Mesh):
    if "pp" not in mesh.shape or mesh.shape["pp"] < 2:
        raise ValueError("pipeline needs a pp axis of size >= 2")


def _manual_axes(mesh: Mesh) -> frozenset:
    """pp always; dp when present. Everything else (tp/sp/...) stays auto so
    XLA partitions the per-stage compute and inserts its collectives."""
    manual = {"pp"}
    if mesh.shape.get("dp", 1) >= 1 and "dp" in mesh.shape:
        manual.add("dp")
    return frozenset(manual)


def build_pipeline_loss(
    embed_fn: Callable,
    layer_fn: Callable,
    head_loss_fn: Callable,
    mesh: Mesh,
    num_microbatches: int,
    param_specs: Any = None,
):
    """Build `loss(params, tokens, targets) -> scalar`, pipelined over `pp`.

    params: {"embed": pytree, "layers": pytree with layers STACKED on dim 0
    (length divisible by pp), "head": pytree}.
    embed_fn(embed_params, tokens[b, T]) -> x[b, T, E]
    layer_fn(one_layer_params, x) -> x
    head_loss_fn(head_params, x, targets[b, T]) -> scalar mean loss

    param_specs (optional): {"embed","layers","head"} pytrees of
    PartitionSpecs giving AUTO-axis shardings (e.g. tp on feature dims; the
    leading "pp" stacking dim of layer leaves is implied and must be omitted).
    With tp in the mesh, place params via place_pipeline_params(...,
    param_specs=...) and the per-stage matmuls run tensor-parallel inside the
    pipeline.
    """
    _check_mesh(mesh)
    S = mesh.shape["pp"]
    M = num_microbatches
    perm = [(i, (i + 1) % S) for i in range(S)]

    def staged_loss(params, tokens, targets):
        stage = lax.axis_index("pp")
        b = tokens.shape[0]
        if b % M:
            raise ValueError(f"batch {b} not divisible by num_microbatches {M}")
        mb_tokens = tokens.reshape(M, b // M, *tokens.shape[1:])
        mb_targets = targets.reshape(M, b // M, *targets.shape[1:])
        # Embeddings for every microbatch (used at stage 0 only; masked elsewhere).
        embeds = jax.vmap(lambda t: embed_fn(params["embed"], t))(mb_tokens)

        def local_apply(x):
            def body(c, layer_params):
                return layer_fn(layer_params, c), None

            x, _ = lax.scan(body, x, params["layers"])
            return x

        def tick(carry, t):
            prev, outs = carry
            recv = lax.ppermute(prev, "pp", perm)
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, embeds[mb_idx], recv)
            out = local_apply(x_in)
            collect = t - (S - 1)
            cidx = jnp.clip(collect, 0, M - 1)
            # Stash the tick's output into the collect buffer; fill ticks
            # (collect < 0) leave slot 0 untouched. The head runs ONCE on the
            # stacked buffer after the scan — M head evaluations instead of
            # M+S-1 per stage (the round-4 "masked head skip" TODO), and every
            # device executes the identical collective sequence (a per-stage
            # lax.cond skip deadlocks: the replicated head params' gradient
            # psum would run inside a branch only the last stage takes).
            upd = jnp.where(collect >= 0, out, outs[cidx])
            outs = lax.dynamic_update_index_in_dim(outs, upd, cidx, 0)
            return (out, outs), None

        # The scan carry becomes varying across pp (stage-dependent layers and
        # ppermute) and dp (sharded data); the initial carry must carry the same
        # varying-manner type or shard_map's typed scan rejects it.
        vary = tuple(a for a in manual if mesh.shape.get(a, 1) > 1)

        def ensure_vary(x):
            missing = tuple(a for a in vary if a not in jax.typeof(x).vma)
            return lax.pcast(x, missing, to="varying") if missing else x

        x0 = ensure_vary(jnp.zeros_like(embeds[0]))
        outs0 = ensure_vary(jnp.zeros_like(embeds))  # [M, b, T, E]
        (_, outs), _ = lax.scan(tick, (x0, outs0), jnp.arange(M + S - 1))
        # One head pass over the M collected microbatches; only the last
        # stage's buffer holds real pipeline outputs, so mask the rest
        # (uniform compute + collectives across stages; the gradient wrt the
        # replicated head params psums at the shard_map boundary). lax.map —
        # not vmap — so a single microbatch's [b, T, V] logits are live at a
        # time: a vmapped head materializes all M logit tensors at once
        # (M=8, T=2048, V=128k bf16 ~ 4 GB per stage).
        per_mb = lax.map(
            lambda ot: head_loss_fn(params["head"], ot[0], ot[1]),
            (outs, mb_targets),
        )
        loss_sum = jnp.where(stage == S - 1, jnp.sum(per_mb), 0.0)
        # Share the last stage's loss with every pp rank, then average the
        # per-dp-shard means into the global mean.
        total = lax.psum(loss_sum, "pp") / M
        if mesh.shape.get("dp", 1) > 1:
            total = lax.pmean(total, "dp")
        return total

    manual = _manual_axes(mesh)
    # Manual in_specs name ONLY the manual axes (pytree prefixes): layer
    # stacking over pp, data over dp. Auto-axis (tp/sp) shardings ride in on
    # the arguments themselves (place_pipeline_params) and flow through the
    # body for XLA to partition. `param_specs` only affects placement — the
    # manual view is the same either way.
    in_param_specs = {"embed": P(), "layers": P("pp"), "head": P()}
    data_spec = P(("dp",)) if mesh.shape.get("dp", 1) > 1 else P()
    sharded = jax.shard_map(
        staged_loss,
        mesh=mesh,
        in_specs=(in_param_specs, data_spec, data_spec),
        out_specs=P(),
        axis_names=manual,
    )

    def loss(params, tokens, targets):
        return sharded(params, tokens, targets)

    return loss


def place_pipeline_params(params, mesh: Mesh, param_specs: Any = None):
    """Device-put pipeline params: layer stack split over pp, the rest
    replicated across pp. param_specs (see build_pipeline_loss) adds AUTO-axis
    shardings: each leaf's spec is composed with the pipeline's own placement —
    layer leaves get ("pp", *leaf_spec), embed/head leaves get leaf_spec.
    Specs may be pytree prefixes (a single P for a whole subtree)."""

    from jax.tree_util import tree_map_with_path

    def compose(kind, tree, specs):
        def resolve(path):
            # Walk the (possibly prefix) spec tree along the leaf's path; a P
            # anywhere on the way covers the whole subtree below it.
            node = specs
            for k in path:
                if isinstance(node, P) or node is None:
                    break
                key = getattr(k, "key", getattr(k, "idx", None))
                if isinstance(node, dict):
                    node = node.get(key)
                elif (isinstance(node, (list, tuple))
                      and isinstance(key, int) and key < len(node)):
                    node = node[key]
                else:
                    node = None
            return node if isinstance(node, P) else None

        def put(path, x):
            spec = resolve(path)
            parts = tuple(spec) if spec is not None else ()
            full = P("pp", *parts) if kind == "layers" else P(*parts)
            return jax.device_put(x, NamedSharding(mesh, full))

        return tree_map_with_path(put, tree)

    specs = param_specs or {}
    return {
        "embed": compose("embed", params["embed"], specs.get("embed")),
        "layers": compose("layers", params["layers"], specs.get("layers")),
        "head": compose("head", params["head"], specs.get("head")),
    }


def build_pipeline_train_step(
    embed_fn, layer_fn, head_loss_fn, optimizer, mesh: Mesh,
    num_microbatches: int, param_specs: Any = None,
):
    """Jitted (state, batch{tokens,targets}) -> (state, metrics) over the pipeline."""
    loss_fn = build_pipeline_loss(
        embed_fn, layer_fn, head_loss_fn, mesh, num_microbatches,
        param_specs=param_specs,
    )

    def step(state: PipelineState, batch: dict):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, batch["tokens"], batch["targets"]
        )
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            PipelineState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            {"loss": loss, "grad_norm": optax.global_norm(grads)},
        )

    batch_spec = P(("dp",)) if mesh.shape.get("dp", 1) > 1 else P()
    batch_shardings = {
        "tokens": NamedSharding(mesh, batch_spec),
        "targets": NamedSharding(mesh, batch_spec),
    }
    return jax.jit(step, donate_argnums=(0,)), batch_shardings


def init_pipeline_state(params, optimizer, mesh: Mesh,
                        param_specs: Any = None) -> PipelineState:
    placed = place_pipeline_params(params, mesh, param_specs=param_specs)
    return PipelineState(
        step=jnp.zeros((), jnp.int32),
        params=placed,
        opt_state=optimizer.init(placed),
    )


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe fill/drain overhead: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def sequential_reference_loss(embed_fn, layer_fn, head_loss_fn):
    """The unpipelined equivalent (for tests: pipeline must match this exactly)."""

    def loss(params, tokens, targets):
        x = embed_fn(params["embed"], tokens)

        def body(c, layer_params):
            return layer_fn(layer_params, c), None

        x, _ = lax.scan(body, x, params["layers"])
        return head_loss_fn(params["head"], x, targets)

    return loss
