"""Compile the Pallas flash kernels for a described TPU v5e, without the chip.

The TPU's compiler is installed where the tests run, and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2). It refuses
what interpret mode lets through: a slice off the tiling, too much fast memory, a
kernel it cannot place. Kernels only, at the shapes the repo really runs; whole-step
compiles build a 152M-parameter model and stay scratch scripts.

Everything that touches the topology lives in the module-scoped fixtures below:
nothing here describes it at import, in a `skipif` or in `parametrize`, because the
process that loads the TPU's library keeps it, and every xdist worker imports every
test file. One file only, for the same reason.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.attention import _flash_backward, _flash_forward

# (batch, heads, seq, head_dim) as the repo's configurations run the kernel, bf16.
SHAPES = {
    "gpt2-125m": (8, 12, 1024, 64),
    "llama3-1b": (1, 32, 8192, 64),
    "llama3-8b": (2, 32, 2048, 128),
}


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one device of a described v5e:2x2. The persistent compilation
    cache is off while this module runs: a compile for a described chip is written
    to it but cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises where there is no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operand(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _laid_out(model: str, layout: str):
    b, h, s, d = SHAPES[model]
    return ((b, h, s, d) if layout == "bhsd" else (b, s, h, d)), (b, h, s), d


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
@pytest.mark.parametrize("model", sorted(SHAPES))
def test_flash_forward_compiles_for_v5e(one_chip, model, layout):
    shape, _, d = _laid_out(model, layout)
    x = _operand(shape, one_chip)

    def fwd(q, k, v):
        return _flash_forward(q, k, v, causal=True, scale=1.0 / math.sqrt(d),
                              block_q=256, block_k=1024, interpret=False, layout=layout)

    text = jax.jit(fwd).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name, in both layouts: a device trace shows the instruction's
    # name, and the reduction finds the kernel by it (PERF.md §3)
    assert re.search(r"%flash_fwd(\.\d+)? = ", text) and 'flash_fwd/pallas_call"' in text


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
@pytest.mark.parametrize("model", sorted(SHAPES))
def test_flash_backward_compiles_for_v5e(one_chip, model, layout):
    shape, lse_shape, d = _laid_out(model, layout)
    x = _operand(shape, one_chip)
    lse = _operand(lse_shape, one_chip, jnp.float32)

    def bwd(q, k, v, out, lse, g):
        return _flash_backward(q, k, v, out, lse, g, causal=True, scale=1.0 / math.sqrt(d),
                               block_q=512, block_k=1024, interpret=False, layout=layout)

    text = jax.jit(bwd).lower(x, x, x, x, lse, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%flash_bwd(\.\d+)? = ", text) and 'flash_bwd/pallas_call"' in text
