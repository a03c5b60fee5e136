"""The decode programs sample on the device (PERF.md §6, PR 37 and PR 44): `rt_decode` ends in a
sampler (`_engine.py:_sample_device`), a round pulls `[B]` token ids, and the logits leave the
device only for the rows the host has to draw (`_host_drawn`: a guided slot, a top-k filter at
a temperature). The multi-step programs draw each step's token as it does (`_sample_device_flat`), so
a plan runs its eight steps at a plain temperature too and pulls `[n, B]` ids. Engines at test
sizes on the CPU; what the sampler compiles to for the chip is `tests/test_chip_compile.py`'s."""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, SamplingParams
from ray_tpu.llm._engine import _sample_device, _sample_host


def _model(block):
    """(cfg, params) of a block at its test size."""
    key = jax.random.PRNGKey(2)
    if block == "llama":
        from ray_tpu.models.transformer import Transformer, get_config

        cfg = get_config("test-tiny", scan_layers=False, remat=False)
        return cfg, Transformer(cfg).init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    from ray_tpu import models

    if block == "dots3":
        from tests.test_dots3 import tiny

        cfg = tiny(n_routed_experts=4, first_expert=8)
    elif block == "granite_hybrid":
        from tests.test_granite_hybrid import tiny

        cfg = tiny()
    else:
        from tests.test_lfm2 import tiny

        cfg = tiny()
    return cfg, models.block_module(cfg).init_params(cfg, key)


@pytest.fixture(scope="module")
def dense():
    return _model("llama")


def _run(engine, requests):
    """Submit every (prompt, sampling, constraint) at once; {index: tokens} when all are done."""
    out = {i: [] for i in range(len(requests))}
    done = [threading.Event() for _ in requests]
    for i, (prompt, sampling, constraint) in enumerate(requests):
        engine.submit(prompt, sampling, lambda tok, fin, i=i: (out[i].append(tok), fin and done[i].set()),
                      request_id=f"r{i}", constraint=constraint)
    for event in done:
        assert event.wait(240), engine.error
    return out


def _recording(engine):
    """Wrap the engine's decode program: every round's (rid of each stepping slot, tokens,
    logits) as the program returned them, read on the stepper's own thread."""
    rounds, real = [], engine._jit_decode

    def decode(*args):
        out = real(*args)
        rids = {i: engine._sched.slots[i].rid for i in np.flatnonzero(np.asarray(args[6]))}
        rounds.append((rids, np.asarray(out[0]), np.asarray(out[1])))
        return out

    engine._jit_decode = decode
    return rounds


@pytest.mark.parametrize("block", ["llama", "dots3", "granite_hybrid", "lfm2"])
def test_a_greedy_single_step_emits_what_the_host_draws_from_the_same_logits(block):
    """Two greedy requests on two slots, one step a round: the ids the program returns are
    `np.argmax` of the logits it returns beside them (what `_sample_host` gave when the round
    pulled them), row for row, and they are the ids the requests were sent."""
    cfg, params = _model(block)
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=1, prefix_cache=False)
    try:
        rounds = _recording(engine)
        out = _run(engine, [(list(range(1, 12)), SamplingParams(max_tokens=7), None),
                            ([9, 4, 30, 2, 7], SamplingParams(max_tokens=5), None)])
        stats = engine.scheduler_stats()
    finally:
        engine.shutdown()
    sent = {f"r{i}": [] for i in out}
    for rids, tokens, logits in rounds:
        assert tokens.dtype == np.int32 and logits.dtype == np.float32 and logits.shape == (2, cfg.vocab_size)
        for slot, rid in rids.items():
            assert tokens[slot] == np.argmax(logits[slot]) == _sample_host(logits[slot], SamplingParams(), None)
            sent[rid].append(int(tokens[slot]))
    assert rounds and {f"r{i}": toks[1:] for i, toks in out.items()} == sent  # a first token is its prefill's
    assert [len(out[0]), len(out[1])] == [7, 5]
    assert (stats["rows_sampled_device"], stats["rows_sampled_host"]) == (6 + 4, 0)


@pytest.fixture
def spans(monkeypatch):
    """Every `xprof.span` the process opens from here on, as (name, attributes), in order."""
    from ray_tpu.util import xprof

    seen, real = [], xprof.span

    def span(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(xprof, "span", span)
    return seen


@pytest.mark.parametrize("guided", [False, True], ids=["no host row", "one guided slot"])
def test_a_round_pulls_the_tokens_and_the_logits_only_for_a_row_the_host_draws(dense, spans, guided):
    """A greedy (or guided) slot beside a slot at a temperature, B = 2 and V = 256. With no
    host-bound slot every round reads back 4 x B bytes and says `host_rows=0`; a guided slot's
    rounds pull the `[B, V]` logits too and draw that one row on the host, the other on the
    device, and the guided output still matches its pattern."""
    cfg, params = dense
    constraint = None
    if guided:
        from ray_tpu.llm import ByteTokenizer
        from ray_tpu.llm.generate import compile_constraint

        constraint = compile_constraint("[0-9]{4}", ByteTokenizer(), cfg.vocab_size)
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=1, prefix_cache=False)
    try:
        out = _run(engine, [(list(b"n = "), SamplingParams(max_tokens=8), constraint),
                            ([9, 4, 30, 2, 7], SamplingParams(max_tokens=12, temperature=0.8), None)])
        stats = engine.scheduler_stats()
    finally:
        engine.shutdown()
    pulls = [attrs["bytes"] for name, attrs in spans if name == "rt.engine.readback"]
    rounds = [attrs for name, attrs in spans if name == "rt.engine.sample" and "host_rows" in attrs]
    draws = sum(1 for name, _ in spans if name == "rt.engine.sample.draw")
    row, tokens, logits = 4 * cfg.vocab_size, 4 * 2, 4 * 2 * cfg.vocab_size
    assert pulls.count(row) == 2 and pulls.count(tokens) == len(rounds) > 0  # two first tokens; B ids a round
    drawn = len(out[0]) + len(out[1]) - 2
    if not guided:
        assert set(pulls) == {row, tokens} and {r["host_rows"] for r in rounds} == {0} and draws == 0
        assert (stats["rows_sampled_device"], stats["rows_sampled_host"]) == (drawn, 0)
    else:
        with_host = [r for r in rounds if r["host_rows"]]
        assert {r["host_rows"] for r in with_host} == {1} and len(with_host) == len(out[0]) - 1 == draws
        assert pulls.count(logits) == len(with_host) and set(pulls) == {row, tokens, logits}
        assert (stats["rows_sampled_device"], stats["rows_sampled_host"]) == (drawn - len(with_host), len(with_host))
        assert re.fullmatch(r"[0-9]{4}", bytes(out[0]).decode())
    assert len(out[1]) == 12


@pytest.mark.parametrize("temperature", [0.7, 1.3])
def test_the_devices_draws_follow_the_softmax_of_the_scaled_logits(temperature):
    """20,000 rows of the same 12 logits in one call of the sampler: the counts of the drawn
    ids against `softmax(logits / T)`, by a chi-square statistic under the 99.9th percentile
    of its 11 degrees of freedom (31.26), at a fixed key."""
    n, logits = 20_000, np.random.default_rng(0).normal(0.0, 1.5, (12,)).astype(np.float32)
    tokens, _ = jax.jit(_sample_device)(jnp.tile(logits, (n, 1)), jnp.full((n,), temperature, jnp.float32),
                                        jnp.ones((n,), bool), jax.random.PRNGKey(37))
    expected = np.exp(logits / temperature - np.max(logits / temperature))
    expected *= n / expected.sum()
    counts = np.bincount(np.asarray(tokens), minlength=12)
    assert counts.sum() == n and np.sum((counts - expected) ** 2 / expected) < 31.26, counts


def test_a_seeded_engine_repeats_its_tokens_and_another_seed_does_not(dense):
    cfg, params = dense

    def tokens(seed):
        engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=1, prefix_cache=False, seed=seed)
        try:
            out = _run(engine, [([5, 9, 17, 3], SamplingParams(max_tokens=16, temperature=0.9), None)])
            assert engine.scheduler_stats()["rows_sampled_device"] == 15
            return out[0]
        finally:
            engine.shutdown()

    first = tokens(11)
    assert tokens(11) == first and tokens(12)[1:] != first[1:]  # past the first token: the device's key


def test_a_greedy_row_is_the_argmax_whatever_its_neighbours_temperatures(dense):
    """The sampler alone: rows at temperature 0 between rows at a temperature take the first
    maximum of their logits, ties and all, and a row outside the round's gate does not make
    the round pay for noise (the key stands). Then two engines: a greedy request emits the
    same ids beside a greedy neighbour as beside one at a temperature."""
    logits = np.random.default_rng(1).normal(0.0, 2.0, (8, 64)).astype(np.float32)
    logits[2, [7, 40]] = logits[2].max() + 1.0  # a tie: the first maximum
    temps = np.array([0.0, 0.9, 0.0, 1.3, 0.0, 0.7, 0.0, 0.0], np.float32)
    key = jax.random.PRNGKey(3)
    tokens, carried = jax.jit(_sample_device)(logits, temps, jnp.ones((8,), bool), key)
    cold = temps == 0
    assert np.array_equal(np.asarray(tokens)[cold], np.argmax(logits, axis=-1)[cold]) and tokens[2] == 7
    assert not np.array_equal(np.asarray(carried), np.asarray(key))
    tokens, carried = jax.jit(_sample_device)(logits, temps, jnp.asarray(cold), key)  # the hot rows sit out
    assert np.array_equal(np.asarray(tokens), np.argmax(logits, axis=-1)) and np.array_equal(np.asarray(carried), np.asarray(key))

    cfg, params = dense
    prompt, other = [5, 9, 17, 3, 11], [8, 2, 44, 7]

    def greedy_beside(**neighbour):
        engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=1, prefix_cache=False)
        try:
            return _run(engine, [(prompt, SamplingParams(max_tokens=10), None),
                                 (other, SamplingParams(max_tokens=10, **neighbour), None)])[0]
        finally:
            engine.shutdown()

    assert greedy_beside() == greedy_beside(temperature=1.1)


def _hot_run(dense, spans, requests, *, multi_step, num_slots=2, seed=5):
    """The requests' ids through a fresh seeded engine, with its stats, its rounds' `rt.engine.dispatch`
    attributes and its pulls' bytes."""
    cfg, params = dense
    engine = DecodeEngine(cfg, params, num_slots=num_slots, max_seq=64, multi_step=multi_step, prefix_cache=False, seed=seed)
    del spans[:]
    try:
        out, stats = _run(engine, requests), engine.scheduler_stats()
        rounds = [a for n, a in spans if n == "rt.engine.dispatch"]
        return out, stats, rounds, [a["bytes"] for n, a in spans if n == "rt.engine.readback"]
    finally:
        engine.shutdown()


@pytest.mark.parametrize("neighbour", [False, True], ids=["alone", "beside a greedy slot"])
def test_eight_steps_a_round_at_a_temperature_emit_the_single_step_paths_ids(dense, spans, neighbour):
    """One seed, one request at T = 0.7 with no stop token (alone, then with a greedy request on
    the slot beside it): with `multi_step=8` its plans run eight steps in one program, which draws
    each step's token itself and consumes the key as eight single-step rounds do, so every id is
    the `multi_step=1` engine's. The rounds say `hot=1`, pull `[n, B]` int32 and never the logits,
    and count their rows as the device's."""
    cfg, _ = dense
    requests = [([5, 9, 17, 3], SamplingParams(max_tokens=30, temperature=0.7), None)]
    if neighbour:
        requests.append(([8, 2, 44, 7, 19], SamplingParams(max_tokens=21), None))
    single, _, one_step, _ = _hot_run(dense, spans, requests, multi_step=1)
    multi, stats, rounds, pulls = _hot_run(dense, spans, requests, multi_step=8)
    assert multi == single and [len(multi[i]) for i in multi] == [30, 21][:len(requests)]
    assert {r["steps"] for r in one_step} == {1} and {r["hot"] for r in one_step} <= {0, 1}
    steps = [r["steps"] for r in rounds]
    assert steps.count(8) >= 2 and len(rounds) < len(one_step) / 2, steps
    assert all(r["hot"] == 1 for r in rounds if r["steps"] == 8 and r["slots"] == len(requests)), rounds
    # (d) a round of n steps pulls 4 x n x B bytes; a first token its one row; nothing pulls `[B, V]`
    row, B = 4 * cfg.vocab_size, 2
    assert sorted(set(pulls) - {row}) == sorted({4 * n * B for n in steps}) and 4 * B * cfg.vocab_size not in pulls
    assert pulls.count(row) == len(requests)
    drawn = sum(len(multi[i]) - 1 for i in multi)
    assert (stats["rows_sampled_device"], stats["rows_sampled_host"]) == (drawn, 0)
    assert stats["plans"]["by_limit"]["sampling"]["iterations"] == 0 < stats["plans"]["by_limit"]["none"]["iterations"]


def test_a_multi_step_programs_results_are_ids_and_the_next_key_and_no_logits(dense):
    """`rt_decode_multi_n8` as the engine builds it, one program whatever the temperatures it is
    given (they are an argument, as the key is): `[8, B]` int32 ids, the caches, the lengths, the
    sampler's next key, and nothing of `[B, V]`. Greedy rows leave the key where it was; a round
    with a row at a temperature moves it, and that row alone may part from the argmax."""
    cfg, params = dense
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=8, prefix_cache=False, decode_loop=False)
    try:
        multi = jax.jit(lambda *a: engine._decode_multi(*a, n=8))
        vec, key = jnp.zeros((2,), jnp.int32), jax.random.PRNGKey(7)

        def call(temps):
            caches = engine._block.init_caches(cfg, 2, 64)
            return multi(engine.params, None, vec, jnp.asarray([3, 4], jnp.int32), caches, jnp.asarray([1, 2], jnp.int32),
                         jnp.ones((2,), bool), jnp.asarray(temps, jnp.float32), key)

        greedy, hot = call([0.0, 0.0]), call([0.0, 5.0])
        assert multi._cache_size() == 1  # the temperatures are data: no second program
        # one scan over the step, and in its body no control flow: no `cond` a step, no loop over the rows
        caches = engine._block.init_caches(cfg, 2, 64)
        (scan,) = [e for e in jax.make_jaxpr(lambda *a: engine._decode_multi(*a, n=8))(
            engine.params, None, vec, vec, caches, vec, jnp.ones((2,), bool), jnp.zeros((2,), jnp.float32), key).eqns
            if e.primitive.name in ("scan", "while", "cond")]
        body = str(scan.params["jaxpr"])
        assert scan.primitive.name == "scan" and "random_bits" in body
        assert not re.search(r"\b(cond|while|scan)\[", body)
        for toks, _, lens, carried, *_ in (greedy, hot):
            assert (toks.shape, toks.dtype, lens.tolist()) == ((8, 2), jnp.int32, [9, 10])
            assert (carried.shape, carried.dtype) == (key.shape, key.dtype)
        assert not any(getattr(x, "shape", ())[-1:] == (cfg.vocab_size,) for x in jax.tree_util.tree_leaves(hot))
        assert np.array_equal(np.asarray(greedy[3]), np.asarray(key)) and not np.array_equal(np.asarray(hot[3]), np.asarray(key))
        assert np.array_equal(np.asarray(greedy[0])[:, 0], np.asarray(hot[0])[:, 0])  # the greedy row beside it: its argmax
        assert not np.array_equal(np.asarray(greedy[0])[:, 1], np.asarray(hot[0])[:, 1])
    finally:
        engine.shutdown()


def test_a_drawn_stop_token_inside_an_eight_step_round_ends_the_slot_there(dense, spans):
    """The id a seeded request at T = 0.7 draws at the fourth step of its first eight-step round,
    given to the same request on the same seed as its stop token: the slot emits up to it and
    ends, the rows the program wrote past it are rolled back (`lens` is what the slot consumed,
    not eight more), and the slot's next occupant emits what it emits on a cold engine."""
    cfg, params = dense
    prompt, later = [5, 9, 17, 3], ([8, 2, 44, 7, 19], SamplingParams(max_tokens=12), None)
    free, _, _, _ = _hot_run(dense, spans, [(prompt, SamplingParams(max_tokens=30, temperature=0.7), None)],
                             multi_step=8, num_slots=1)
    at = next(p for p in range(2, 8) if free[0][p] not in free[0][:p])  # inside the first round (ids 1 to 8), not its last
    cold, _, _, _ = _hot_run(dense, spans, [later], multi_step=8, num_slots=1)
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=64, multi_step=8, prefix_cache=False, seed=5)
    del spans[:]
    try:
        stopped = _run(engine, [(prompt, SamplingParams(max_tokens=30, temperature=0.7, stop_token_id=free[0][at]), None)])
        rounds = [a for n, a in spans if n == "rt.engine.dispatch"]
        assert stopped[0] == free[0][:at + 1] and [(r["steps"], r["hot"]) for r in rounds] == [(8, 1)]
        assert not engine._sched.slots[0].active and engine._lens[0] == len(prompt) + at  # not + 8
        assert engine.scheduler_stats()["rows_sampled_device"] == at  # what was emitted, not the eight steps
        assert _run(engine, [later]) == cold
    finally:
        engine.shutdown()
