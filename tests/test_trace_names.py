"""The names a device trace shows (PERF.md §3): every engine program has a module
name of its own that no Python function's name decides, the multi-step program's
says how many steps it holds, and every operation of the engine's model and of the
train step carries one of one list of scopes. Checked by lowering at `test-tiny`
size on the CPU; the Pallas kernels' names are checked where the kernels compile
(`tests/test_chip_compile.py`)."""

import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

MODEL_SCOPES = ("embedding", "attn_norm", "attn", "mlp_norm", "mlp", "final_norm", "lm_head")


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)


def _parts(op_name):
    """The scopes of an `op_name`, each without what a transformation wrapped round it:
    `jit(step)/transpose(jvp(loss))/mul` -> [step, loss, mul]."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", part) for part in op_name.split("/")]


def _lowered(prog, *args):
    """(module name, every scope of every operation) of the lowered program."""
    text = prog.lower(*_abstract(args)).as_text(dialect="hlo", debug_info=True)
    (module,) = re.findall(r"^HloModule (\w+)", text, flags=re.M)
    return module, {part for name in re.findall(r'op_name="([^"]+)"', text) for part in _parts(name)}


@pytest.fixture(scope="module")
def engines():
    """One engine that has run chunked prefill, a prefix-cache insert and attach, single
    and multi-step decode and both detached prefills; one with a model draft."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.llm.kvcache import PrefixCacheManager
    from ray_tpu.models.transformer import Transformer, get_config

    cfg = get_config("test-tiny", scan_layers=False, remat=False)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def generate(engine, prompt, **sp):
        done = threading.Event()
        engine.submit(prompt, SamplingParams(**sp), lambda tok, fin: fin and done.set())
        assert done.wait(180), engine.error

    saved = {k: CONFIG._cache.get(k) for k in ("llm_prefill_bucket_min",)}
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    plain = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=4, token_budget=8,
                         prefix_cache=PrefixCacheManager(4, 1 << 20, name="names"))
    spec = DecodeEngine(cfg, params, num_slots=2, max_seq=64, prefix_cache=False,
                        spec_config={"num_spec_tokens": 2})
    try:
        prompt = list(range(1, 14))
        generate(plain, prompt, max_tokens=6)                           # prefill b8, multi-step
        generate(plain, prompt + [40, 41], max_tokens=3, temperature=0.8)  # attach, two steps drawn in one program
        plain.prefill_detached(list(range(20, 27)))                     # detached
        plain.prefill_detached(prompt + [50, 51, 52])                   # detached suffix
        generate(spec, prompt, max_tokens=8)                            # draft prefill, propose, verify
        yield plain, spec
    finally:
        plain.shutdown()
        spec.shutdown()
        for k, v in saved.items():  # a flag never read before has no cached value to put back
            CONFIG._cache.pop(k) if v is None else CONFIG._cache.update({k: v})


def _sampler_args(engine):
    """What `rt_decode` and `rt_decode_multi_n<n>` take after the gate: the slots' temperatures and the sampler's key."""
    return (np.zeros((engine.B,), np.float32), np.zeros((2,), np.uint32))


def _engine_programs(plain, spec):
    """(program, its arguments) for every program the two engines built."""
    cfg, B, T = plain.cfg, plain.B, plain.T
    kv = lambda rows: np.zeros((cfg.n_layers, 2, rows, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)  # noqa: E731
    i32, vec = np.int32(0), np.zeros((B,), np.int32)
    step = (plain.params, None, vec, vec, plain._caches, vec, np.ones((B,), bool))
    out = [(plain._jit_decode, step + _sampler_args(plain))]
    out += [(prog, step + _sampler_args(plain)) for prog in plain._jit_decode_multi.values()]
    for key, prog in plain._jit_prefill.items():
        if isinstance(key, int):
            out.append((prog, (plain.params, None, np.zeros((1, key), np.int32), plain._caches, i32, i32, i32, i32)))
        elif key[0] == "attach":
            out.append((prog, (plain._caches, kv(key[1]), i32)))
        elif key[0] == "detached":
            out.append((prog, (plain.params, None, np.zeros((1, key[1]), np.int32), i32)))
        elif key[0] == "detached_suffix":
            out.append((prog, (plain.params, None, kv(key[1]), np.zeros((1, key[2]), np.int32), i32, i32)))
    out += [(prog, (plain._caches, i32)) for prog in plain._jit_kv_gather.values()]
    for key, prog in spec._jit_spec_verify.items():
        S = key[1]
        out.append((prog, (spec.params, None, vec, np.zeros((B, S), np.int32), spec._caches, vec,
                           np.ones((B,), bool), np.zeros((B, S, cfg.vocab_size), np.float32))))
    draft = spec._draft
    for key, prog in draft._progs.items():
        if key[0] == "propose":
            out.append((prog, (draft.params, draft.caches, i32, i32, i32, i32)))
        else:
            out.append((prog, (draft.params, draft.caches, np.zeros((1, key[1]), np.int32), i32)))
    return out


def test_every_engine_program_has_a_name_of_its_own_and_the_models_scopes(engines):
    plain, spec = engines
    names = {}
    for prog, args in _engine_programs(plain, spec):
        module, scopes = _lowered(prog, *args)
        names[module] = scopes
    patterns = {
        r"jit_rt_decode": 1, r"jit_rt_decode_multi_n\d+": 1, r"jit_rt_prefill_b\d+": 1,
        r"jit_rt_attach_b\d+": 1, r"jit_rt_kv_gather_b\d+": 1, r"jit_rt_prefill_detached_b\d+": 1,
        r"jit_rt_prefill_detached_suffix_b\d+_\d+": 1, r"jit_rt_verify_s3": 1,
        r"jit_rt_draft_prefill_b\d+": 1, r"jit_rt_draft_propose_k2(_catchup)?": 1,
    }
    for pattern, at_least in patterns.items():
        found = [n for n in names if re.fullmatch(pattern, n)]
        assert len(found) >= at_least, (pattern, sorted(names))
    assert all(any(re.fullmatch(p, n) for p in patterns) for n in names), sorted(names)
    # a program that runs the model carries every scope of the list; the decode, multi-step
    # and verify programs also `sample` on the device; an attach and the prefix-cache
    # insert's gather run no model
    for module, scopes in names.items():
        if "attach" in module or "kv_gather" in module:
            continue
        want = set(MODEL_SCOPES)
        if "draft_prefill" in module:
            want -= {"final_norm", "lm_head"}  # it keeps the KV rows and drops the logits
        assert want <= scopes, (module, want - scopes)
        assert {"layer_0", "layer_1"} <= scopes, module
        assert ("sample" in scopes) == bool(re.search(r"rt_decode|verify", module)), module
    # the steps of a multi-step program are in its name
    assert {f"jit_rt_decode_multi_n{key[-1]}" for key in plain._jit_decode_multi} <= set(names)


def test_the_registry_counts_each_multi_step_size(engines):
    plain, _ = engines
    rows = {r["key"]: r for r in plain._xprof.report(owner=plain._xprof_owner)["programs"]}
    multi = [k for k in rows if isinstance(k, tuple) and k[0] == "decode_multi"]
    assert multi and all(len(k) == 2 and rows[k]["compiles"] == 1 for k in multi), rows.keys()
    assert ("decode",) in rows


@pytest.mark.parametrize("fused_ce", [False, True])
def test_the_train_step_names_model_loss_and_optimizer(fused_ce):
    import optax

    from ray_tpu.models.transformer import Transformer, get_config
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel.spmd import build_train_step, init_state

    cfg = get_config("test-tiny")
    model = Transformer(cfg)
    mesh = mesh_lib.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    optimizer = optax.adamw(1e-3)
    state, _ = init_state(model, cfg, optimizer, mesh, sample_shape=(2, 32))
    step_fn, _ = build_train_step(model, optimizer, mesh, fused_ce=fused_ce, with_grad_norm=False)
    batch = {"tokens": np.zeros((2, 32), np.int32), "targets": np.zeros((2, 32), np.int32)}
    with mesh:
        text = step_fn.lower(*_abstract((state, batch))).as_text(dialect="hlo", debug_info=True)
    op_names = set(re.findall(r'op_name="([^"]+)"', text))
    scopes = {part for name in op_names for part in _parts(name)}
    want = {"embedding", "attn_norm", "attn", "mlp_norm", "mlp", "final_norm", "loss", "optimizer"}
    if not fused_ce:
        want.add("lm_head")  # the fused loss multiplies by the head itself, under `loss`
    assert want <= scopes, want - scopes
    # forward and backward copies of a scope both keep it
    assert any("transpose(jvp(Transformer))" in n and "/mlp/" in n for n in op_names)
    assert any("jvp(Transformer)" in n and "transpose" not in n and "/mlp/" in n for n in op_names)
    # of the operations the step itself names, those under no scope do no arithmetic of the
    # loss or the optimizer
    bare = {n for n in op_names if n.startswith("jit(step)/")
            and not (set(_parts(n)) & (want | {"lm_head"}))}
    assert not any(re.search(r"log|exp|sqrt|dot_general", n.rsplit("/", 1)[-1]) for n in bare), sorted(bare)


# -- the dots3 block: the same outer scopes, its mechanisms named inside them ------------

DOTS3_INNER = {"prefill": {"indexer", "latent", "window", "router", "experts", "shared_expert"},
               "decode": {"indexer", "select", "latent", "window", "router", "experts", "shared_expert"}}


@pytest.fixture(scope="module")
def dots3_engine():
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models import dots3
    from tests.test_dots3 import tiny

    cfg = tiny(n_routed_experts=4, first_expert=8)
    params = dots3.init_params(cfg, jax.random.PRNGKey(2))
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=4, token_budget=8)
    try:
        done = threading.Event()
        engine.submit(list(range(1, 20)), SamplingParams(max_tokens=7), lambda tok, fin: fin and done.set())
        assert done.wait(180), engine.error
        yield engine
    finally:
        engine.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


def test_the_dots3_blocks_programs_keep_the_names_and_name_their_mechanisms(dots3_engine):
    engine = dots3_engine
    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    names = dict(_lowered(prog, *args) for prog, args in programs)
    assert {"jit_rt_decode", "jit_rt_prefill_b8"} <= set(names), sorted(names)
    assert any(re.fullmatch(r"jit_rt_decode_multi_n\d+", n) for n in names)
    for module, scopes in names.items():
        assert set(MODEL_SCOPES) <= scopes, (module, set(MODEL_SCOPES) - scopes)
        inner = DOTS3_INNER["prefill" if "prefill" in module else "decode"]
        assert inner <= scopes, (module, inner - scopes)
        assert ("select" in scopes) == ("prefill" not in module), module  # a chunk masks, a step gathers
        assert ("sample" in scopes) == ("decode" in module), module  # the single step draws on the device too


def test_scheduler_stats_name_the_block_and_count_its_expert_pairs(dots3_engine):
    stats = dots3_engine.scheduler_stats()
    experts = stats["experts"]
    assert stats["model"]["block"] == "dots3" and (experts["held"], experts["of"], experts["first"]) == (4, 16, 8)
    # 19 prompt tokens and 6 fed-back tokens through 4 expert layers, 4 experts a token
    assert experts["pairs_routed"] == (19 + 6) * 4 * 4 > experts["pairs_held"] > 0
    assert set(experts["window"]) == {"pairs_routed", "pairs_held", "max_load", "mean_load"}
    assert {"rt.engine.prefill", "rt.engine.dispatch", "rt.engine.iter"} <= set(stats["loop"])


def test_the_dense_blocks_stats_say_so_and_have_no_expert_counts(engines):
    stats = engines[0].scheduler_stats()
    assert stats["model"]["block"] == "llama" and "experts" not in stats


# -- the granite_hybrid block: a state's scopes inside `attn`, and its counts -------------------

GRANITE_INNER = {"in_proj", "conv", "ssm", "gate_norm", "out_proj", "kv_attn"}


@pytest.fixture(scope="module")
def granite_engine():
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models import granite_hybrid
    from tests.test_granite_hybrid import tiny

    cfg = tiny()
    params = granite_hybrid.init_params(cfg, jax.random.PRNGKey(2))
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=4, token_budget=8)
    try:
        done = threading.Event()
        engine.submit(list(range(1, 20)), SamplingParams(max_tokens=7), lambda tok, fin: fin and done.set())
        assert done.wait(180), engine.error
        yield engine
    finally:
        engine.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


def test_the_granite_hybrid_blocks_programs_keep_the_names_and_name_a_layers_parts(granite_engine):
    engine = granite_engine
    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    names = dict(_lowered(prog, *args) for prog, args in programs)
    assert {"jit_rt_decode", "jit_rt_prefill_b8"} <= set(names), sorted(names)
    assert any(re.fullmatch(r"jit_rt_decode_multi_n\d+", n) for n in names)
    for module, scopes in names.items():
        assert set(MODEL_SCOPES) <= scopes, (module, set(MODEL_SCOPES) - scopes)
        assert GRANITE_INNER <= scopes, (module, GRANITE_INNER - scopes)
        assert ("sample" in scopes) == ("decode" in module), module  # the single step draws on the device too


def test_scheduler_stats_count_a_states_positions_padding_resets_and_steps(granite_engine):
    stats = granite_engine.scheduler_stats()
    state = stats["state"]
    assert stats["model"]["block"] == "granite_hybrid" and stats["model"]["cache_bytes"] > 0 and "experts" not in stats
    # 19 prompt tokens in chunks of 8, 8 and 3 (bucket 4): one reset; 6 fed-back tokens on one slot
    assert {k: state[k] for k in ("prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps")} == {
        "prefill_positions": 20, "prefill_padding": 1, "states_reset": 1, "decode_slot_steps": 6}
    assert set(state["window"]) == {"prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps"}
    assert state["bytes_per_slot"] > 0


def test_the_dense_blocks_cached_products_are_named_too(engines):
    """`kv_attn` is written by `llama._attn_cached`, which both blocks' attention layers run."""
    plain, _ = engines
    vec = np.zeros((plain.B,), np.int32)
    _, scopes = _lowered(plain._jit_decode, plain.params, None, vec, vec, plain._caches, vec, np.ones((plain.B,), bool),
                         *_sampler_args(plain))
    assert "kv_attn" in scopes and not (GRANITE_INNER - {"kv_attn"}) & scopes


def test_the_cached_attention_kernel_is_named_under_kv_attn(monkeypatch):
    """As the TPU's process traces a decode program, the attention against the slab is the Pallas
    kernel `cached_attn` (`pallas_call(name=...)`) inside the scope `kv_attn` of every layer: the
    scope readers (`kv_attn_dev_ms_per_step.sessions`, `kv_attn_roofline.serve`) sum the kernel's
    time with the write's by that scope, and a trace's own listing names the kernel. Heads of
    128, as the kernel asks of a slab kept a head a row (`cached_attention_takes`)."""
    from ray_tpu.models import llama
    from ray_tpu.models.transformer import ModelConfig
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import unbox

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    cfg = ModelConfig(vocab_size=64, hidden=256, n_layers=2, n_heads=2, n_kv_heads=1, mlp_dim=64,
                      scan_layers=False, remat=False)
    params = unbox(jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: llama.init_caches(cfg, 2, 64))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, last, c, lens, gate: llama.decode(p, cfg, last, c, lens, gate, None, None))(
        params, vec, caches, vec, jax.ShapeDtypeStruct((2,), jnp.bool_))

    def kernels(jaxpr, outer):
        """(kernel name, scopes) of every `pallas_call`, through the jitted functions that hold them."""
        for eqn in jaxpr.eqns:
            scopes = outer + [part for part in str(eqn.source_info.name_stack).split("/") if part]
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], scopes
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernels(sub, scopes)

    calls = list(kernels(jaxpr.jaxpr, []))
    assert len(calls) == cfg.n_layers, calls
    for i, (name, scopes) in enumerate(calls):
        assert name == "cached_attn" and scopes[-1] == "cached_attn" and "kv_attn" in scopes and f"layer_{i}" in scopes, (name, scopes)


# -- the lfm2 block: a conv layer's and an attention layer's scopes, the experts', both counts ----

LFM2_INNER = {"in_proj", "conv", "out_proj", "qk_norm", "kv_attn", "router", "experts"}


@pytest.fixture(scope="module")
def lfm2_engine():
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models import lfm2
    from tests.test_lfm2 import tiny

    cfg = tiny()
    params = lfm2.init_params(cfg, jax.random.PRNGKey(2))
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=4, token_budget=8)
    try:
        done = threading.Event()
        engine.submit(list(range(1, 20)), SamplingParams(max_tokens=7), lambda tok, fin: fin and done.set())
        assert done.wait(180), engine.error
        yield engine
    finally:
        engine.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


def test_the_lfm2_blocks_programs_keep_the_names_and_name_a_layers_parts(lfm2_engine):
    """granite's names in a conv layer (`lib/scope_trace_state.py` reads them), dots3's in an expert
    layer (`lib/scope_trace.py`), `qk_norm` and `kv_attn` in an attention layer, under `layer_<i>`."""
    engine = lfm2_engine
    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    names = dict(_lowered(prog, *args) for prog, args in programs)
    assert {"jit_rt_decode", "jit_rt_prefill_b8"} <= set(names), sorted(names)
    assert any(re.fullmatch(r"jit_rt_decode_multi_n\d+", n) for n in names)
    for module, scopes in names.items():
        assert set(MODEL_SCOPES) <= scopes, (module, set(MODEL_SCOPES) - scopes)
        assert LFM2_INNER <= scopes, (module, LFM2_INNER - scopes)
        assert {f"layer_{i}" for i in range(6)} <= scopes and not {"ssm", "gate_norm", "shared_expert"} & scopes
        assert ("sample" in scopes) == ("decode" in module), module  # the single step draws on the device too


def test_scheduler_stats_count_the_lfm2_blocks_experts_and_its_state(lfm2_engine):
    stats = lfm2_engine.scheduler_stats()
    experts, state = stats["experts"], stats["state"]
    assert stats["model"]["block"] == "lfm2" and (experts["held"], experts["of"], experts["first"]) == (8, 8, 0)
    # 19 prompt tokens and 6 fed-back tokens through 5 expert layers, 2 experts a token, every pair held
    assert experts["pairs_routed"] == experts["pairs_held"] == (19 + 6) * 5 * 2
    counted = {"pairs_routed", "pairs_held", "experts_hit", "tiles_run", "layer_steps", "decode_experts_hit", "decode_layer_steps"}
    assert counted <= set(experts) and set(experts["window"]) == counted | {"max_load", "mean_load"}
    assert experts["decode_layer_steps"] == 6 * 5 and experts["decode_experts_hit"] == 6 * 5 * 2 < experts["experts_hit"] <= experts["tiles_run"]
    # chunks of 8, 8 and 3 (bucket 4): one reset; 6 fed-back tokens on one slot
    assert {k: state[k] for k in ("prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps")} == {
        "prefill_positions": 20, "prefill_padding": 1, "states_reset": 1, "decode_slot_steps": 6}
    assert set(state["window"]) == {"prefill_positions", "prefill_padding", "states_reset", "decode_slot_steps"}
    assert state["bytes_per_slot"] == 4 * 2 * 64 * 4


# -- the engine loop says why: a seeded run through the two-pass sample, and the readers of the new names ----


def _row_by_row_decode_round(self, decode_slots):
    """`DecodeEngine._decode_round` as it was before a round drew every row and then emitted
    every token, over the rows the host still draws: a row is drawn from the pulled logits and
    its token emitted before the next row is looked at."""
    from ray_tpu.llm._engine import _sample_host

    lora, adapter_ids, last_token, lens, gate = self._step_args(decode_slots)
    _, logits, self._caches, _, self._sample_key, *stats = self._jit_decode(
        self.params, lora, adapter_ids, last_token, self._caches, lens, gate, self._temps_dev, self._sample_key)
    self._note_stats(stats)
    logits_np = np.asarray(logits)
    for i in decode_slots:
        s = self._sched.slots[i]
        self._lens[i] += 1
        if not s.active:
            continue
        token = _sample_host(logits_np[i], s.params, self._np_rng)
        s.generated += 1
        s.host_len += 1
        s.tokens.append(token)
        s.history.append(token)
        self._last_token[i] = token
        self._emit(i, token)


def test_a_seeded_temperature_run_emits_the_tokens_of_draw_then_emit_row_by_row(engines, monkeypatch):
    """The host's fallback keeps its generator's order. Two slots with a top-k filter at a
    temperature (rows the decode program's sampler does not draw: `_host_drawn`) share the
    engine's generator; one ends three tokens before the other. Drawing a round's host rows
    and then emitting every token asks the generator in the order that drawing and emitting
    row by row did, so a seeded run gives those tokens."""
    import types

    from ray_tpu.llm import SamplingParams

    plain, _ = engines

    def run(seed):
        """Both requests submitted while the stepper is held at its next plan, so that every run plans alike."""
        gate, plans = threading.Event(), plain._sched.next_plan
        monkeypatch.setattr(plain._sched, "next_plan", lambda **kw: gate.wait(60) and plans(**kw))
        time.sleep(0.05)  # the stepper is inside the patched call, or will enter it: either way it waits
        plain._np_rng = np.random.default_rng(seed)
        out, done = {"a": [], "b": []}, {"a": threading.Event(), "b": threading.Event()}
        for key, prompt, n in (("a", [5, 9, 17], 4), ("b", [3, 8, 2], 7)):  # under a block of 4: never cached
            plain.submit(prompt, SamplingParams(max_tokens=n, temperature=0.9, top_k=40),
                         lambda tok, fin, key=key: (out[key].append(tok), fin and done[key].set()))
        gate.set()
        assert done["a"].wait(120) and done["b"].wait(120), plain.error
        monkeypatch.setattr(plain._sched, "next_plan", plans)
        return out

    host = plain.scheduler_stats()["rows_sampled_host"]
    two_pass = run(11)
    assert plain.scheduler_stats()["rows_sampled_host"] == host + 3 + 6  # every row after a first token
    monkeypatch.setattr(plain, "_decode_round", types.MethodType(_row_by_row_decode_round, plain))
    row_by_row = run(11)
    monkeypatch.undo()
    assert two_pass == row_by_row and [len(two_pass[k]) for k in "ab"] == [4, 7]
    assert run(12) != two_pass  # the seed, not the prompts, decided the tokens


LOOP_METRICS = ("plan_full_steps_share.decode", "plan_held_by_prefill_share.decode", "plan_held_by_tail_share.decode",
                "readback_wake_ms_p50.serve", "readback_copy_ms_p50.serve", "dispatch_args_ms_p50.serve",
                "dispatch_call_ms_p50.serve", "emit_ms_p50.serve")


@pytest.fixture
def loop_trace_cases(monkeypatch):
    """`benchmark/tests/test_loop_trace.py`, for its hand-made events and its loader of a reader,
    with `benchmark/` importable as `benchmark/run.py` makes it."""
    import importlib.util
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    for name in [n for n in sys.modules if n == "lib" or n.startswith("lib.")]:
        monkeypatch.delitem(sys.modules, name)
    spec = importlib.util.spec_from_file_location("bench_test_loop_trace", os.path.join(bench, "tests", "test_loop_trace.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    yield module
    for name in [n for n in sys.modules if n == "lib" or n.startswith("lib.")]:
        sys.modules.pop(name)


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_a_loop_reader_reads_the_new_names_and_nothing_on_a_parents_trace(loop_trace_cases, monkeypatch, metric):
    """Three decode iterations (a chunk beside a single step that waits 3 ms and copies 4.4 MB in 1.5 ms;
    eight steps; four under a slot's tail) and one that ran a chunk alone: a third of the decode
    iterations each way, the medians of the rounds' parts, and the pull that ends the chunk left out.
    The parent's trace has the older spans and attributes alone: every reader None, none raises."""
    cases = loop_trace_cases
    reader = cases._reader(metric)
    want = {"readback_wake_ms_p50.serve": 3.0, "readback_copy_ms_p50.serve": 0.1, "dispatch_args_ms_p50.serve": 1.0,
            "dispatch_call_ms_p50.serve": 2.0, "emit_ms_p50.serve": 1.0}.get(metric, 100 / 3)
    for parts, expected in ((True, pytest.approx(want)), (False, None)):
        monkeypatch.setattr(cases.pt, "for_record", lambda record, parts=parts: cases.events_of(parts=parts))
        assert reader.read({"cell": "c", "trace": {}}) == expected, (metric, parts)
    monkeypatch.setattr(cases.pt, "for_record", lambda record: None)  # an untraced run
    assert reader.read({"cell": "c"}) is None


# -- the pangu_moe block: dots3's names where the mathematics is dots3's, the post-norms' own ----

PANGU_INNER = {"latent", "attn_post_norm", "router", "experts", "shared_expert", "mlp_post_norm"}


@pytest.fixture(scope="module")
def pangu_engine():
    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm import DecodeEngine, SamplingParams
    from ray_tpu.models import pangu_moe
    from tests.test_pangu_moe import tiny

    cfg = tiny(n_routed_experts=8, first_expert=8)
    params = pangu_moe.init_params(cfg, jax.random.PRNGKey(2))
    saved = CONFIG._cache.get("llm_prefill_bucket_min")
    CONFIG._cache["llm_prefill_bucket_min"] = 4
    engine = DecodeEngine(cfg, params, num_slots=2, max_seq=64, multi_step=4, token_budget=8)
    try:
        done = threading.Event()
        engine.submit(list(range(1, 20)), SamplingParams(max_tokens=7), lambda tok, fin: fin and done.set())
        assert done.wait(180), engine.error
        yield engine
    finally:
        engine.shutdown()
        CONFIG._cache.pop("llm_prefill_bucket_min") if saved is None else CONFIG._cache.update(llm_prefill_bucket_min=saved)


def test_the_pangu_moe_blocks_programs_keep_the_names_and_name_their_mechanisms(pangu_engine):
    """`latent` inside `attn` and `router`, `experts`, `shared_expert` inside `mlp` are the names
    `lib/scope_trace.py` reads for `dots3`; the two post-norms are this block's, each inside its
    sub-layer's scope; nothing selects, indexes or slides."""
    engine = pangu_engine
    B, i32, vec = engine.B, np.int32(0), np.zeros((engine.B,), np.int32)
    step = (engine.params, None, vec, vec, engine._caches, vec, np.ones((B,), bool))
    programs = [(engine._jit_decode, step + _sampler_args(engine))] + [(p, step + _sampler_args(engine)) for p in engine._jit_decode_multi.values()]
    programs += [(p, (engine.params, None, np.zeros((1, k), np.int32), engine._caches, i32, i32, i32, i32))
                 for k, p in engine._jit_prefill.items()]
    names = dict(_lowered(prog, *args) for prog, args in programs)
    assert {"jit_rt_decode", "jit_rt_prefill_b8"} <= set(names), sorted(names)
    assert any(re.fullmatch(r"jit_rt_decode_multi_n\d+", n) for n in names)
    for module, scopes in names.items():
        assert set(MODEL_SCOPES) <= scopes, (module, set(MODEL_SCOPES) - scopes)
        assert PANGU_INNER <= scopes, (module, PANGU_INNER - scopes)
        assert {"layer_0", "layer_1", "layer_2"} <= scopes and not {"indexer", "select", "window", "kv_attn"} & scopes
        assert ("sample" in scopes) == ("decode" in module), module  # the single step draws on the device too


def test_scheduler_stats_count_the_pangu_moe_blocks_pairs_and_its_slabs_rows(pangu_engine):
    stats = pangu_engine.scheduler_stats()
    experts, latent = stats["experts"], stats["latent"]
    assert stats["model"]["block"] == "pangu_moe" and (experts["held"], experts["of"], experts["first"]) == (8, 64, 8)
    # 19 prompt tokens and 6 fed-back tokens through 2 expert layers, 8 experts a token, an eighth of them held
    assert experts["pairs_routed"] == (19 + 6) * 2 * 8 > experts["pairs_held"] > 0
    assert set(experts["window"]) == {"pairs_routed", "pairs_held", "max_load", "mean_load"}  # `dots3`'s keys alone
    # six decode steps of one slot at lengths 19 to 24: rows 20 to 25 visible, both slots' 64 rows read
    assert latent["rows_visible"] == sum(range(20, 26)) and latent["rows_read"] == 6 * 2 * 64
    assert set(latent["window"]) == {"rows_visible", "rows_read"} and latent["bytes_per_row"] == 128 * 4
