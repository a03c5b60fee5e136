"""The plain reference against the program's own model at a tiny size, in float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import reference

TINY = dict(vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=2, mlp_dim=128,
            max_seq=128, rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False)


@pytest.mark.parametrize("scan_layers", [False, True])
def test_reference_matches_the_programs_forward(scan_layers):
    from ray_tpu.models.transformer import ModelConfig, Transformer, cross_entropy_loss

    cfg = ModelConfig(**TINY, dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
                      scan_layers=scan_layers, attention="reference")
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, 256)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    want = model.apply({"params": params}, tokens)[0]
    tree = reference.plain_tree(params)
    with jax.default_matmul_precision("highest"):
        got = reference.forward(tree, TINY, tokens[0], q_block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    targets = jnp.roll(tokens, -1, axis=1)
    np.testing.assert_allclose(
        float(reference.loss(tree, TINY, tokens[0], targets[0])),
        float(cross_entropy_loss(want[None], targets)), rtol=1e-6)


def test_greedy_by_full_passes_matches_stepwise_argmax():
    from ray_tpu.models.transformer import ModelConfig, Transformer

    cfg = ModelConfig(**TINY, dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
                      scan_layers=False, attention="reference")
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (12,), 0, 256)
    params = model.init(jax.random.PRNGKey(0), prompt[None])["params"]
    tree = reference.plain_tree(params)
    ids, margins = jax.jit(lambda p, x: reference.greedy(p, TINY, x, 5))(tree, prompt)
    seq = list(np.asarray(prompt))
    for j in range(5):
        logits = model.apply({"params": params}, jnp.asarray(seq)[None])[0, -1]
        assert int(jnp.argmax(logits)) == int(ids[j])
        top = np.sort(np.asarray(logits))[-2:]
        assert float(margins[j]) == pytest.approx(top[1] - top[0], abs=1e-4)
        seq.append(int(ids[j]))


def test_compare_greedy_lets_the_ids_part_only_at_a_near_tie():
    assert reference.compare_greedy([1, 2, 3], [1.0, 1.0, 1.0], [1, 2, 3]) == (True, 3)
    assert reference.compare_greedy([1, 2, 3], [1.0, 0.01, 1.0], [1, 9, 9]) == (True, 1)
    assert reference.compare_greedy([1, 2, 3], [1.0, 0.01, 1.0], [1, 2, 3]) == (True, 2)  # equal there: the walk goes on
    assert reference.compare_greedy([1, 2, 3], [1.0, 1.0, 1.0], [1, 9, 3]) == (False, 1)
    assert reference.compare_greedy([1, 2, 3], [1.0, 0.149, 1.0], [1, 9, 3])[0]
    assert not reference.compare_greedy([1, 2, 3], [1.0, 0.151, 1.0], [1, 9, 3])[0]
