"""ray_tpu.llm tests: decode engine correctness + OpenAI-compatible serving.

Shape parity: reference python/ray/llm tests — engine generation, server
deployment, router request shapes, multi-request batching.
"""

import json
import threading
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module", autouse=True)
def _cluster(ray_start_regular):
    yield
    serve.shutdown()


@pytest.fixture(autouse=True)
def _fresh_apps():
    yield
    for app in list(serve.status()):
        serve.delete(app)


def _attn_layer(cfg, key):
    """One attention layer's kernels as the flax tree holds them, float32, drawn at
    1 / sqrt(fan-in) so that outputs are of order 1 at every width."""
    import jax

    kq, kk, kv, ko = jax.random.split(key, 4)
    H, Hkv, D, M = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden
    scale = M ** -0.5
    return {
        "q": {"kernel": scale * jax.random.normal(kq, (M, H, D))},
        "k": {"kernel": scale * jax.random.normal(kk, (M, Hkv, D))},
        "v": {"kernel": scale * jax.random.normal(kv, (M, Hkv, D))},
        "o": {"kernel": scale * jax.random.normal(ko, (H, D, M))},
    }


def _attn_repeat_reference(layer, x, positions, cache_k, cache_v, write_at, kv_mask, cfg,
                           write_gate=None):
    """`_attn_cached` spelled out slot by slot with every KV head copied to its
    G query heads (`jnp.repeat`): what the grouped products have to equal."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import _rope

    B, S, M = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rope((x @ layer["q"]["kernel"].reshape(M, -1)).reshape(B, S, H, D),
              positions, cfg.rope_theta)
    k = _rope((x @ layer["k"]["kernel"].reshape(M, -1)).reshape(B, S, Hkv, D),
              positions, cfg.rope_theta)
    v = (x @ layer["v"]["kernel"].reshape(M, -1)).reshape(B, S, Hkv, D)
    outs = []
    for b in range(B):
        if write_gate is None or bool(write_gate[b]):
            at = int(write_at[b])
            cache_k = cache_k.at[b, at:at + S].set(k[b])
            cache_v = cache_v.at[b, at:at + S].set(v[b])
        kk = jnp.repeat(cache_k[b], H // Hkv, axis=1)  # [T, H, D]
        vv = jnp.repeat(cache_v[b], H // Hkv, axis=1)
        scores = jnp.einsum("shd,thd->hst", q[b], kk) / jnp.sqrt(float(D))
        scores = jnp.where(kv_mask[b][None], scores, -1e30)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        outs.append(jnp.einsum("hst,thd->shd", w, vv).reshape(S, H * D))
    return jnp.stack(outs) @ layer["o"]["kernel"].reshape(-1, M), cache_k, cache_v


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "mixed_gate"])
@pytest.mark.parametrize("S", [1, 5])
@pytest.mark.parametrize("heads", [(16, 8), (8, 8), (32, 8), (4, 1)],
                         ids=lambda hk: f"h{hk[0]}kv{hk[1]}")
def test_attn_cached_equals_repeat_reference(heads, S, gated):
    """Grouped-query products over the slab as it lies against an explicit copy of
    every KV head to its query heads: output and both caches, float32, slots of
    different lengths, the verify program's gated write among them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import _attn_cached
    from ray_tpu.models.transformer import ModelConfig

    H, Hkv = heads
    D, B, T = 8, 3, 24
    cfg = ModelConfig(hidden=H * D, n_heads=H, n_kv_heads=Hkv, dtype=jnp.float32,
                      rope_theta=10000.0)
    keys = jax.random.split(jax.random.PRNGKey(H * 100 + Hkv * 10 + S), 4)
    layer = _attn_layer(cfg, keys[0])
    x = jax.random.normal(keys[1], (B, S, cfg.hidden))
    cache_k = jax.random.normal(keys[2], (B, T, Hkv, D))
    cache_v = jax.random.normal(keys[3], (B, T, Hkv, D))
    lens = jnp.asarray([0, 7, 17], jnp.int32)
    positions = lens[:, None] + jnp.arange(S)[None]
    # causal over the slot's own rows: query s sees rows 0 .. lens + s, which `_attn_cached`
    # reads from `lens`; the reference takes it spelled out
    kv_mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]
    gate = jnp.asarray([True, False, True]) if gated else None

    got = _attn_cached(layer, x, positions, cache_k, cache_v, lens, cfg,
                       write_gate=gate)
    want = _attn_repeat_reference(layer, x, positions, cache_k, cache_v, lens, kv_mask,
                                  cfg, write_gate=gate)
    for g, w, what in zip(got, want, ("out", "cache_k", "cache_v")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5,
                                   err_msg=what)
    if gated:  # the slot whose gate is off keeps its rows
        np.testing.assert_array_equal(np.asarray(got[1][1]), np.asarray(cache_k[1]))


@pytest.mark.parametrize("side", ["keys", "values"])
@pytest.mark.parametrize("heads", [(16, 8), (32, 8), (4, 1), (4, 2)],
                         ids=lambda hk: f"h{hk[0]}kv{hk[1]}")
def test_attn_cached_head_reads_its_own_kv_head(heads, side):
    """Query head h reads KV head h // G and no other. Values: KV head k holds k + 1
    in every row, so head h's output is h // G + 1 whatever it attends to. Keys: KV
    head k's only non-zero key is row k and row t's value is t + 1 in every KV head,
    so head h attends to row h // G alone and again reads h // G + 1."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import _attn_cached
    from ray_tpu.models.transformer import ModelConfig

    H, Hkv = heads
    D, T = 8, 12
    M = H * D
    cfg = ModelConfig(hidden=M, n_heads=H, n_kv_heads=Hkv, dtype=jnp.float32)
    layer = {
        "q": {"kernel": jnp.full((M, H, D), 3.0 / M)},  # x of ones -> q of threes
        "k": {"kernel": jnp.zeros((M, Hkv, D))},
        "v": {"kernel": jnp.zeros((M, Hkv, D))},
        "o": {"kernel": jnp.eye(M).reshape(H, D, M)},
    }
    if side == "values":
        cache_k = jnp.zeros((1, T, Hkv, D))
        per_kv_head = jnp.arange(1.0, Hkv + 1)[None, None, :, None]
        cache_v = jnp.broadcast_to(per_kv_head, (1, T, Hkv, D))
    else:
        own_row = jnp.arange(T)[:, None] == jnp.arange(Hkv)[None, :]  # [T, Hkv]
        cache_k = jnp.broadcast_to(3.0 * own_row[None, :, :, None], (1, T, Hkv, D))
        per_row = jnp.arange(1.0, T + 1)[None, :, None, None]
        cache_v = jnp.broadcast_to(per_row, (1, T, Hkv, D))
    # positions 0 leave the rotation the identity; the gate keeps the slab as built
    out, _, _ = _attn_cached(
        layer, jnp.ones((1, 1, M)), jnp.zeros((1, 1), jnp.int32), cache_k, cache_v,
        jnp.asarray([T - 1], jnp.int32), cfg,  # a slot of T - 1 rows: the query sees all T
        write_gate=jnp.asarray([False]))
    want = np.repeat(np.arange(1.0, Hkv + 1), (H // Hkv) * D)
    np.testing.assert_allclose(np.asarray(out[0, 0]), want, atol=1e-5)


def test_engine_matches_full_forward():
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def greedy_full(prompt, n):
        toks = list(prompt)
        for _ in range(n):
            logits = model.apply({"params": params}, jnp.asarray([toks]))
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks[len(prompt):]

    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=128)
    try:
        results = {}
        done = threading.Event()

        def cb_for(key):
            acc = []

            def cb(tok, fin):
                acc.append(tok)
                if fin:
                    results[key] = acc
                    if len(results) == 2:
                        done.set()

            return cb

        p1, p2 = [5, 9, 17, 3], [8, 2, 44, 7, 19, 21, 6]
        engine.submit(p1, SamplingParams(max_tokens=6), cb_for("a"))
        engine.submit(p2, SamplingParams(max_tokens=6), cb_for("b"))
        assert done.wait(180), results
        assert results["a"] == greedy_full(p1, 6)
        assert results["b"] == greedy_full(p2, 6)
    finally:
        engine.shutdown()


def test_multi_step_decode_stop_rollback_and_slot_reuse():
    """Multi-step decode (N tokens per dispatch, on-device argmax): a
    stop_token firing mid-chunk must roll the slot's device state back to the
    consumed prefix, and the slot's next occupant must decode correctly from
    the rolled-back cache rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    # PRNGKey(1), not 0: seed 0's greedy output from this prompt is the
    # constant 121 121 121..., which makes stop == the FIRST token and the
    # engine (correctly) halts at one token while the rollback assertion
    # expects three — the test then "fails" without testing anything. Seed 1
    # gives a non-degenerate reference (asserted below), so the stop really
    # fires mid-chunk and the rollback is exercised for real.
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]

    def greedy_full(prompt, n):
        toks = list(prompt)
        for _ in range(n):
            logits = model.apply({"params": params}, jnp.asarray([toks]))
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks[len(prompt):]

    def generate(engine, prompt, **sp):
        acc, done = [], threading.Event()

        def cb(tok, fin):
            acc.append(tok)
            if fin:
                done.set()

        engine.submit(prompt, SamplingParams(**sp), cb)
        assert done.wait(180)
        return acc

    prompt = [5, 9, 17, 3]
    ref = greedy_full(prompt, 12)
    stop = ref[2]  # fires mid-chunk for multi_step=8
    assert stop not in ref[:2], (
        "degenerate reference: the stop token must not appear before the "
        "position the rollback assertion depends on"
    )
    engine = DecodeEngine(cfg, params, num_slots=1, max_seq=128, multi_step=8)
    try:
        out = generate(engine, prompt, max_tokens=12, stop_token_id=stop)
        assert out == ref[:3], (out, ref)  # stop token emitted, then halt
        # Slot reuse after the rollback: fresh request, full budget.
        prompt2 = [8, 2, 44, 7]
        assert generate(engine, prompt2, max_tokens=10) == greedy_full(prompt2, 10)
    finally:
        engine.shutdown()


def test_llm_server_deployment_generate():
    from ray_tpu.llm import LLMConfig, build_llm_deployment

    app = build_llm_deployment(LLMConfig(model_id="test-tiny", num_slots=2))
    handle = serve.run(app, name="llm", route_prefix=None, _timeout_s=240)
    out = handle.generate.remote("hi", max_tokens=8).result(timeout_s=240)
    assert len(out["token_ids"]) == 8
    assert out["usage"]["prompt_tokens"] == 2
    assert isinstance(out["text"], str)
    # deterministic: same prompt, greedy -> same tokens
    out2 = handle.generate.remote("hi", max_tokens=8).result(timeout_s=120)
    assert out2["token_ids"] == out["token_ids"]
    # concurrent requests share the batch
    rs = [handle.generate.remote(f"p{i}", max_tokens=4) for i in range(6)]
    outs = [r.result(timeout_s=240) for r in rs]
    assert all(len(o["token_ids"]) == 4 for o in outs)


def test_openai_app_http():
    from ray_tpu.llm import LLMConfig, build_openai_app

    app = build_openai_app([LLMConfig(model_id="test-tiny", num_slots=2)])
    serve.run(app, name="openai", route_prefix="/", _timeout_s=240)
    port = serve.get_proxy_port()

    def post(path, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=240) as resp:
            return json.loads(resp.read())

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/v1/models", timeout=120) as r:
        models = json.loads(r.read())
    assert models["data"][0]["id"] == "test-tiny"

    out = post("/v1/completions",
               {"model": "test-tiny", "prompt": "ab", "max_tokens": 5})
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] == 5

    chat = post("/v1/chat/completions",
                {"model": "test-tiny",
                 "messages": [{"role": "user", "content": "hello"}],
                 "max_tokens": 5})
    assert chat["object"] == "chat.completion"
    assert chat["choices"][0]["message"]["role"] == "assistant"

    # SSE streaming: "stream": true yields text/event-stream data: events
    # terminated by [DONE] (reference: router.py StreamingResponse path).
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps({"model": "test-tiny",
                         "messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 5, "stream": True}).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=240) as resp:
        assert resp.headers.get("Content-Type", "").startswith("text/event-stream")
        raw = resp.read().decode()
    events = [ln[len("data: "):] for ln in raw.splitlines() if ln.startswith("data: ")]
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    streamed = "".join(c["choices"][0]["delta"].get("content", "") for c in chunks)
    assert streamed  # tokens actually arrived incrementally


def test_pd_disagg_matches_monolithic():
    """Prefill-elsewhere + decode must produce the same greedy tokens as the
    monolithic engine (KV prefix transfer is lossless)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    prompt = [5, 9, 17, 3, 42, 8]
    n = 6

    mono = DecodeEngine(cfg, params, num_slots=1, max_seq=128)
    prefiller = DecodeEngine(cfg, params, num_slots=1, max_seq=128, decode_loop=False)
    decoder = DecodeEngine(cfg, params, num_slots=2, max_seq=128)
    try:
        def run(engine, submit):
            out = []
            done = threading.Event()

            def cb(tok, fin):
                out.append(tok)
                if fin:
                    done.set()

            submit(cb)
            assert done.wait(180)
            return out

        expect = run(mono, lambda cb: mono.submit(
            prompt, SamplingParams(max_tokens=n), cb))

        first_logits, kv, plen = prefiller.prefill_detached(prompt)
        assert plen == len(prompt)
        got = run(decoder, lambda cb: decoder.submit_prefilled(
            kv, plen, first_logits, SamplingParams(max_tokens=n), cb))
        assert got == expect
    finally:
        mono.shutdown()
        prefiller.shutdown()
        decoder.shutdown()


def test_lora_adapters_batch_independently():
    """Index-0 (base) requests are unchanged by loaded adapters; a nonzero
    adapter alters generation; both kinds batch together in one engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = [7, 21, 3, 9]
    n = 5

    def run(engine, lora=""):
        out = []
        done = threading.Event()

        def cb(tok, fin):
            out.append(tok)
            if fin:
                done.set()

        engine.submit(prompt, SamplingParams(max_tokens=n), cb, lora=lora)
        assert done.wait(180)
        return out

    base_engine = DecodeEngine(cfg, params, num_slots=2, max_seq=128)
    lora_engine = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128,
        lora_config={"max_loras": 2, "rank": 4},
    )
    try:
        base_out = run(base_engine)
        assert run(lora_engine) == base_out  # engine with lora enabled, base request

        # A strong random adapter on q/v of layer 0 must change the output.
        rng = np.random.default_rng(0)
        r = 4
        w = {0: {
            "q_A": rng.normal(size=(cfg.hidden, r)).astype(np.float32) * 2.0,
            "q_B": rng.normal(size=(r, cfg.n_heads * cfg.head_dim)).astype(np.float32) * 2.0,
            "v_A": rng.normal(size=(cfg.hidden, r)).astype(np.float32) * 2.0,
            "v_B": rng.normal(size=(r, cfg.n_kv_heads * cfg.head_dim)).astype(np.float32) * 2.0,
        }}
        lora_engine.add_lora("tuned", w, alpha=8.0)
        tuned_out = run(lora_engine, lora="tuned")
        assert tuned_out != base_out
        # Base requests remain unaffected after the adapter loaded.
        assert run(lora_engine) == base_out
    finally:
        base_engine.shutdown()
        lora_engine.shutdown()


def test_pd_disagg_app_end_to_end():
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.pd_disagg import build_pd_openai_app

    app = build_pd_openai_app(
        LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128),
        num_prefill=1, num_decode=1,
    )
    handle = serve.run(app, name="pd_app", route_prefix=None)
    resp = handle.generate.remote("hello world", max_tokens=8).result(timeout_s=300)
    assert len(resp["token_ids"]) == 8
    assert resp["usage"]["completion_tokens"] == 8
    assert resp["prefill_s"] > 0
    serve.delete("pd_app")


def test_speculative_decode_correct_and_faster():
    """Spec decode (draft-k scan + single verify) emits exactly the greedy
    sequence and beats plain decode tokens/s at batch 1 (VERDICT r2 #9;
    reference: vLLM speculative decoding). A self-draft makes every proposal
    accepted, so the speedup bound is deterministic: k+1 tokens for ~2-3
    dispatches vs one per token."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    # The plain engine is the greedy reference: test_engine_matches_full_forward
    # already proves it bit-exact against the unjitted full forward.
    prompt, N = [5, 9, 17, 3], 96

    def run(engine):
        out, done, marks = [], threading.Event(), []

        def cb(tok, fin):
            if not out:
                marks.append(time.monotonic())  # first token: decode begins
            out.append(tok)
            if fin:
                marks.append(time.monotonic())
                done.set()

        # warm the programs with one full generation, then take best-of-3
        # timings (this 1-core CI host runs cluster daemons concurrently;
        # min-time is the standard noise-robust estimator)
        engine.submit(prompt, SamplingParams(max_tokens=N), cb)
        assert done.wait(300)
        first = list(out)
        times, last = [], None
        for _ in range(3):
            out.clear(); done.clear(); marks.clear()
            engine.submit(prompt, SamplingParams(max_tokens=N), cb)
            assert done.wait(300)
            # decode tokens/s: first-token -> done (prefill/admit excluded)
            times.append(marks[-1] - marks[0])
            last = list(out)
        return first, last, min(times)

    # multi_step=1: the spec-decode claim is against per-token dispatch (its
    # design point). Multi-step greedy decode is a separate optimization that
    # reaches similar dispatch savings without a draft model.
    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=128, multi_step=1)
    try:
        _, plain_toks, plain_t = run(plain)
    finally:
        plain.shutdown()
    spec = DecodeEngine(
        cfg, params, num_slots=2, max_seq=128,
        spec_config={"num_spec_tokens": 6},  # self-draft: all accepted
    )
    try:
        spec_first, spec_toks, spec_t = run(spec)
    finally:
        spec.shutdown()

    expected = plain_toks
    assert len(expected) == N
    assert spec_first == expected and spec_toks == expected
    speedup = plain_t / spec_t
    assert speedup >= 1.5, f"spec decode {speedup:.2f}x (plain {plain_t:.2f}s, spec {spec_t:.2f}s)"


def test_dp_serving_routes_across_replicas():
    """Data-parallel serving: dp_size=2 engine replicas claim distinct ranks
    and concurrent requests reach BOTH (VERDICT r2 #9; reference:
    deployments/data_parallel/dp_server.py + dp_rank_assigner.py)."""
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.dp_serve import build_dp_openai_app

    app = build_dp_openai_app(
        LLMConfig(model_id="test-tiny", num_slots=2), dp_size=2
    )
    handle = serve.run(app, name="dp-llm", route_prefix=None, _timeout_s=300)

    ranks = handle.ranks.remote().result(timeout_s=120)
    assert sorted(ranks.values()) == [0, 1], ranks

    rs = [handle.generate.remote(f"req {i}", max_tokens=4) for i in range(12)]
    outs = [r.result(timeout_s=300) for r in rs]
    assert all(len(o["token_ids"]) == 4 for o in outs)
    seen = {o["dp_rank"] for o in outs}
    assert seen == {0, 1}, f"requests reached only ranks {seen}"
    # determinism across ranks: same prompt, greedy -> same tokens everywhere
    a = handle.generate.remote("same", max_tokens=6).result(timeout_s=120)
    b = handle.generate.remote("same", max_tokens=6).result(timeout_s=120)
    assert a["token_ids"] == b["token_ids"]
