"""The control of the train cells' comparison: the plain reference put in the program's place
and computed one precision below the one the configurations state. They state bfloat16
matmuls with float32 accumulation, so the control rounds both operands of every matrix
product to float8 (e4m3, each tensor scaled to its largest entry, the usual fp8 recipe): the
step that would tempt a later PR. It has to come out as not correct. Here at a size a test
run holds; on the chip at the cells' own sizes by `control_rms` below (PERF.md §6, PR 27).
The benchmark's own runs do not run it."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import reference

HERE = os.path.dirname(os.path.abspath(__file__))


def fp8(a):
    """`a` rounded to float8 e4m3 and back, scaled so that its largest entry is e4m3's (448)."""
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def control_rms(params, model: dict, tokens, targets, operand=fp8):
    """(rms over the tokens of the difference between the control's loss at a token and the
    reference's, the difference of their means): what the train driver reads for the program's
    forward pass and for its step, read for the control. tokens, targets: [n, S]."""
    ref = jax.jit(lambda p, x, y: reference.token_losses(p, model, x, y))
    ctl = jax.jit(lambda p, x, y: reference.token_losses(p, model, x, y, operand=operand))
    d = np.stack([np.asarray(ctl(params, x, y)) - np.asarray(ref(params, x, y)) for x, y in zip(tokens, targets)])
    return float(np.sqrt(np.mean(d ** 2))), float(d.mean())


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models.transformer import ModelConfig, Transformer

    with open(os.path.join(HERE, "configs", "tiny.json")) as f:
        model = json.load(f)["model"]
    fields = {k: getattr(jnp, v) if k in ("dtype", "param_dtype") else v for k, v in model.items()}
    cfg = ModelConfig(**dict(fields, attention="reference"))
    params = jax.jit(Transformer(cfg).init)(jax.random.PRNGKey(5), jnp.zeros((1, 256), jnp.int32))["params"]
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, size=(8, 257), dtype=np.int32)
    return reference.plain_tree(params), model, ids[:, :-1], ids[:, 1:]


def test_the_control_is_not_correct_and_the_stated_precision_is(tiny):
    params, model, tokens, targets = tiny
    rms8, _ = control_rms(params, model, tokens, targets)
    rms16, mean16 = control_rms(params, model, tokens, targets, operand=bf16)
    # the precision the configurations state passes both limits; one below it fails the token limit
    assert rms16 <= reference.TOKEN_LOSS_RMS_TOL and abs(mean16) <= reference.LOSS_ABS_TOL
    assert rms8 > reference.TOKEN_LOSS_RMS_TOL and rms8 > 3 * rms16


def test_without_an_operand_the_reference_is_what_it_was(tiny):
    params, model, tokens, targets = tiny
    assert control_rms(params, model, tokens[:1], targets[:1], operand=None) == (0.0, 0.0)
    assert float(reference.loss(params, model, tokens[0], targets[0])) == pytest.approx(
        float(jnp.mean(reference.token_losses(params, model, tokens[0], targets[0]))))
