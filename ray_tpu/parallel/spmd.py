"""SPMD training: sharded init and train-step construction over a named mesh.

This replaces the reference's torch DDP/FSDP wiring (reference:
python/ray/train/torch/config.py process groups + torch FSDP inside the user loop) with
the XLA-native form: parameters are initialized *already sharded* (jit with out_shardings
— no host-memory spike), the train step is one jitted program whose gradients are
all-reduced/resharded by XLA over the mesh axes, and activation sharding follows the
model's logical constraints. bfloat16 compute, float32 params/optimizer, donated state.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel import mesh as mesh_lib


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


def _rules_list(rules: dict | None):
    merged = dict(mesh_lib.DEFAULT_RULES, **(rules or {}))
    out = []
    for logical, phys in merged.items():
        if phys is None:
            out.append((logical, None))
        elif isinstance(phys, str):
            out.append((logical, phys))
        else:
            out.append((logical, tuple(phys)))
    return out


def state_shardings(model, cfg, optimizer, mesh: Mesh, rules=None,
                    sample_shape=(1, 128)):
    """Compute NamedShardings for a TrainState without materializing parameters."""
    rng = jax.random.PRNGKey(0)
    tokens = jnp.zeros(sample_shape, jnp.int32)
    with mesh, nn.logical_axis_rules(_rules_list(rules)):
        abs_vars = jax.eval_shape(model.init, rng, tokens)
    param_shardings = mesh_lib.param_shardings(abs_vars["params"], mesh, rules)
    params_sh_unboxed = mesh_lib.unbox(param_shardings)
    abs_params = mesh_lib.unbox(abs_vars["params"])
    abs_opt = jax.eval_shape(optimizer.init, abs_params)

    # Optimizer slots mirror parameter pytrees (adam mu/nu) -> reuse the param
    # shardings for any sub-tree that structurally matches; replicate scalars/rest.
    param_treedef = jax.tree_util.tree_structure(abs_params)

    def recurse(node):
        if jax.tree_util.tree_structure(node) == param_treedef:
            return params_sh_unboxed
        if isinstance(node, jax.ShapeDtypeStruct):
            return NamedSharding(mesh, PartitionSpec())
        if isinstance(node, tuple) and type(node) is not tuple:  # NamedTuple (optax)
            return type(node)(*(recurse(x) for x in node))
        if isinstance(node, tuple):
            return tuple(recurse(x) for x in node)
        if isinstance(node, list):
            return [recurse(x) for x in node]
        if isinstance(node, dict):
            return {k: recurse(v) for k, v in node.items()}
        return NamedSharding(mesh, PartitionSpec())

    opt_sh = recurse(abs_opt)
    return TrainState(
        step=NamedSharding(mesh, PartitionSpec()),
        params=params_sh_unboxed,
        opt_state=opt_sh,
    )


def init_state(model, cfg, optimizer, mesh: Mesh, rules=None, rng=None,
               sample_shape=(1, 128)) -> tuple[TrainState, TrainState]:
    """Sharded-init a TrainState; returns (state, state_shardings)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    shardings = state_shardings(model, cfg, optimizer, mesh, rules, sample_shape)
    tokens = jnp.zeros(sample_shape, jnp.int32)
    rules_list = _rules_list(rules)

    def make(rng):
        with nn.logical_axis_rules(rules_list):
            variables = model.init(rng, tokens)
        params = mesh_lib.unbox(variables["params"])
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    with mesh:
        # One-shot by design: sharded init runs once per training run, and
        # out_shardings is what prevents the host-memory spike — caching the
        # wrapper would only pin a program that is never called again.
        state = jax.jit(make, out_shardings=shardings)(rng)  # raylint: disable=RL601 (one-shot sharded-init program)
    return state, shardings


def build_train_step(model, optimizer, mesh: Mesh, rules=None,
                     loss_fn: Callable | None = None, donate: bool = True,
                     fused_ce: bool | None = None, with_grad_norm: bool = True):
    """One jitted SPMD train step: (state, batch{tokens,targets,mask?}) -> (state, metrics).

    fused_ce (default: auto): compute the LM head + cross-entropy in sequence
    chunks so [B,S,V] logits are never materialized (fused_cross_entropy_loss).
    Auto: for Transformer models with no custom loss_fn, once whole float32
    logits would pass 2 GB; under that the plain loss over whole logits runs.
    The fused loss is called under this step's logical rules and, as the step
    is, under the caller's `with mesh:`. From them it reads which mesh axes
    split the batch, and where some do it runs its chunk loop on each device's
    own sequences and moves the head once a step (its docstring has the rest).
    """
    from ray_tpu.models.transformer import (
        Transformer,
        cross_entropy_loss,
        fused_cross_entropy_loss,
    )

    rules_list = _rules_list(rules)
    auto_fused = fused_ce is None
    if auto_fused:
        fused_ce = loss_fn is None and isinstance(model, Transformer)
    loss_fn = loss_fn or cross_entropy_loss

    def step(state: TrainState, batch: dict):
        use_fused = fused_ce
        if auto_fused and use_fused:
            # Fused CE trades an extra head matmul (checkpoint recompute) for
            # never materializing [B,S,V] f32 logits. At small batch the plain
            # path is faster; past ~2 GB of logits it is the difference between
            # compiling and OOM — switch on size (static at trace time).
            b, s = batch["tokens"].shape
            use_fused = b * s * model.cfg.vocab_size * 4 > 2_000_000_000
        def compute_loss(params):
            with nn.logical_axis_rules(rules_list):
                # "losses" collects sown auxiliary losses (MoE load balance, or
                # any custom model's); empty collection sums to 0 for dense models.
                if use_fused:
                    hidden, extra = model.apply(
                        {"params": params}, batch["tokens"],
                        return_hidden=True, mutable=["losses"],
                    )
                else:
                    logits, extra = model.apply(
                        {"params": params}, batch["tokens"], mutable=["losses"]
                    )
            aux = sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(extra))
            # flax names the model's operations by module; `loss` and `optimizer`
            # name the rest of the step in a device trace (PERF.md §3).
            # The fused loss reads the rules too: on a mesh it splits its loop by them.
            with jax.named_scope("loss"), nn.logical_axis_rules(rules_list):
                if use_fused:
                    if model.cfg.tie_embeddings:
                        table, cdim = params["embedding"], 1
                    else:
                        table, cdim = params["lm_head"]["kernel"], 0
                    return fused_cross_entropy_loss(
                        hidden, table, batch["targets"], batch.get("mask"),
                        contract_dim=cdim, compute_dtype=model.cfg.dtype,
                    ) + aux
                return loss_fn(logits, batch["targets"], batch.get("mask")) + aux

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "step": state.step + 1}
        if with_grad_norm:
            # Optional: a full extra pass over every gradient buffer — perf
            # harnesses that don't consume it can turn it off.
            metrics["grad_norm"] = optax.global_norm(grads)
        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            metrics,
        )

    batch_spec = mesh_lib.logical_to_spec(("batch", "seq"), rules)
    batch_shardings = {
        "tokens": NamedSharding(mesh, batch_spec),
        "targets": NamedSharding(mesh, batch_spec),
    }
    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    return jax.jit(step, **jit_kwargs), batch_shardings


def eval_logits_fn(model, rules=None):
    rules_list = _rules_list(rules)

    def forward(params, tokens):
        with nn.logical_axis_rules(rules_list):
            return model.apply({"params": params}, tokens)

    return jax.jit(forward)
