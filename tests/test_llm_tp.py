"""Tensor-parallel sharded serving (docs/serving_tp.md): mesh-sharded decode
plane over the forced multi-device CPU harness.

The contract under test: greedy output is TOKEN-IDENTICAL across TP=1/2/4
mesh shapes (same prompts, same seeds) with zero mid-serve recompiles —
including speculative-verify, adapter-paging churn, and a PD-disaggregated
handoff between a TP prefill replica and a TP decode replica — and a
retired TP replica provably frees every mesh-resident shard (leaksan).
The token-identity sweep runs through the subprocess-spawned multi-device
group (conftest.run_multi_device_subprocess), so it holds even when the
parent interpreter's jax initialized under different XLA flags.
"""

import threading

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve

import jax

NUM_DEVICES = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    NUM_DEVICES < 4,
    reason="TP tests need the 8-virtual-device CPU harness "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def _model(n_kv_heads=None, seed=0):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Transformer, get_config

    kw = {"scan_layers": False, "remat": False}
    if n_kv_heads is not None:
        kw["n_kv_heads"] = n_kv_heads
    cfg = get_config("test-tiny", **kw)
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def _generate(engine, prompt, n=10, lora=""):
    from ray_tpu.llm import SamplingParams

    acc, done = [], threading.Event()

    def cb(tok, fin):
        acc.append(tok)
        if fin:
            done.set()

    engine.submit(prompt, SamplingParams(max_tokens=n), cb, lora=lora)
    assert done.wait(240), acc
    return acc


# -- token identity across mesh shapes (subprocess-spawned group) -------------

_SWEEP_SNIPPET = r"""
import json, threading
import numpy as np
import jax, jax.numpy as jnp
from ray_tpu.models.transformer import Transformer, get_config
from ray_tpu.llm._engine import DecodeEngine, SamplingParams

cfg = get_config("test-tiny", scan_layers=False, remat=False, n_kv_heads=__N_KV_HEADS__)
model = Transformer(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
prompts = [[5, 9, 17, 3], [8, 2, 44, 7, 19, 21, 6], [5, 9, 17, 3]]

def generate(engine, prompt, n=12):
    acc, done = [], threading.Event()
    def cb(tok, fin):
        acc.append(tok)
        if fin:
            done.set()
    engine.submit(prompt, SamplingParams(max_tokens=n), cb)
    assert done.wait(240)
    return acc

def program_count(e):
    n = len(e._jit_prefill) + len(e._jit_spec_verify) + len(e._jit_decode_multi)
    for prog in (e._jit_decode, *e._jit_decode_multi.values()):
        try:
            n += prog._cache_size()
        except Exception:
            pass
    return n

def attn_collectives(tp):
    # one layer's cached attention, sharded as the engine shards it (heads and KV
    # heads over tp): which collectives the partitioner puts into it
    import re
    from jax.sharding import NamedSharding
    from ray_tpu.llm import tp as tp_plan
    from ray_tpu.models.llama import _attn_cached
    mesh = tp_plan.build_tp_mesh(tp)
    rep = tp_plan.replicated(mesh)
    H, Hkv, D, M, B, T = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden, 2, 64
    def arg(shape, sharding=rep, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    layer = {
        name: {"kernel": arg(shape, NamedSharding(
            mesh, tp_plan.param_spec(("layer_0", "attn", name, "kernel"), shape, mesh)))}
        for name, shape in [("q", (M, H, D)), ("k", (M, Hkv, D)), ("v", (M, Hkv, D)),
                            ("o", (H, D, M))]
    }
    slab = arg((B, T, Hkv, D), tp_plan.kv_cache_sharding(mesh, Hkv))
    text = jax.jit(
        lambda layer, x, pos, ck, cv, at: _attn_cached(layer, x, pos, ck, cv, at, cfg)
    ).lower(layer, arg((B, 1, M)), arg((B, 1), dtype=jnp.int32), slab, slab,
            arg((B,), dtype=jnp.int32)).compile().as_text()
    return sorted(re.findall(
        r" (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)(?:-start)?\(", text))

def sampler_collectives(e):
    # the decode program's sampler over a head that leaves its logits split by vocabulary:
    # (kind, elements) of every collective under the scope `sample`
    import math, re
    vec = jnp.zeros((e.B,), jnp.int32)
    text = e._jit_decode.lower(e.params, None, vec, vec, e._caches, vec, jnp.ones((e.B,), bool),
                               e._temps_dev, e._sample_key).compile().as_text()
    found = re.findall(r"= \(?\w+\[([\d,]*)\]\S* (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
                       r"(?:-start)?\([^\n]*op_name=\"[^\"]*/sample/", text)
    return sorted((kind, math.prod(int(n) for n in dims.split(",") if n)) for dims, kind in found)

out = {"devices": len(jax.devices()), "tokens": {}, "programs_flat": {}, "attn_collectives": {}, "sampler_collectives": {}}
for tp in __TPS__:
    if tp > 1:
        out["attn_collectives"][str(tp)] = attn_collectives(tp)
    eng = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=tp,
                       spec_config={"method": "ngram", "num_spec_tokens": 4})
    warm = [generate(eng, p) for p in prompts]   # warmup compiles everything
    n0 = program_count(eng)
    again = [generate(eng, p) for p in prompts]  # steady state: zero compiles
    n1 = program_count(eng)
    assert warm == again, (tp, warm, again)
    out["tokens"][str(tp)] = warm
    out["programs_flat"][str(tp)] = (n0 == n1, n0, n1)
    spec = eng.scheduler_stats().get("spec", {})
    out.setdefault("spec_rounds", {})[str(tp)] = spec.get("rounds", 0)
    if tp > 1:
        out["sampler_collectives"][str(tp)] = sampler_collectives(eng)
    eng.shutdown()
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("n_kv_heads,tps", [(4, (1, 2, 4)), (2, (1, 2))],
                         ids=["mha_kv4", "grouped_kv2"])
def test_greedy_token_identity_across_tp_meshes(multi_device_run, n_kv_heads, tps):
    """TP=1/2/4 greedy output bitwise token-identical, spec-verify included,
    program caches flat after warmup (zero mid-serve recompiles) — on the
    subprocess-spawned 8-device CPU group, i.e. CI without TPUs. With two KV
    heads under four query heads each tp=2 shard holds one KV head and the two
    query heads that read it: the grouped products split where the pool does,
    and the layer's only collective stays the output projection's all-reduce."""
    snippet = _SWEEP_SNIPPET.replace("__N_KV_HEADS__", str(n_kv_heads)).replace(
        "__TPS__", repr(tps))
    out = multi_device_run(snippet, timeout=900)
    assert out["devices"] >= 8, out["devices"]
    assert len({tuple(map(tuple, out["tokens"][str(tp)])) for tp in tps}) == 1, out
    assert out["attn_collectives"] == {str(tp): ["all-reduce"] for tp in tps if tp > 1}, out
    for tp, (flat, n0, n1) in out["programs_flat"].items():
        assert flat, f"tp={tp}: program cache grew {n0} -> {n1} after warmup"
    # The spec phase really ran (the identity claim covers the verify path).
    assert all(r > 0 for r in out["spec_rounds"].values()), out["spec_rounds"]
    # The decode program's sampler moves no logits between chips: the head leaves them split
    # by vocabulary, each chip reduces its own columns, and what crosses is a maximum and an
    # index a slot a chip (two slots: `[B, tp]`), as in the multi-step program's argmax.
    for tp, found in out["sampler_collectives"].items():
        assert found and all(kind == "all-gather" and n <= 2 * int(tp) for kind, n in found), (tp, found)


# -- sharding plan ------------------------------------------------------------

@needs_mesh
def test_decode_plane_is_mesh_sharded():
    """Params, per-slot KV pool, and program-cache keys all carry the mesh:
    the q/gate projections shard their output dims, o/down their input dims,
    the KV pool its kv-head axis — per-device bytes drop accordingly."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.llm.tp import per_device_bytes

    cfg, params = _model(n_kv_heads=4)
    eng = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=4)
    try:
        p = eng.params
        assert p["layer_0"]["attn"]["q"]["kernel"].sharding.spec == P(None, "tp", None)
        assert p["layer_0"]["attn"]["o"]["kernel"].sharding.spec == P("tp", None, None)
        assert p["layer_0"]["mlp"]["gate"]["kernel"].sharding.spec == P(None, "tp")
        assert p["layer_0"]["mlp"]["down"]["kernel"].sharding.spec == P("tp", None)
        assert p["embedding"].sharding.spec == P("tp", None)
        # norms replicate
        assert p["final_norm"]["scale"].sharding.spec == P()
        ck, _cv = eng._caches[0]
        assert ck.sharding.spec == P(None, None, "tp", None)
        # HBM accounting: the sharded plane puts ~1/tp of params+KV per chip.
        total = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(eng.params)
        ) + sum(ck.nbytes + cv.nbytes for ck, cv in eng._caches)
        per_dev = per_device_bytes(eng.params) + per_device_bytes(eng._caches)
        assert per_dev < total / 2, (per_dev, total)
        # Program-cache keys carry the mesh signature: a different sharding
        # regime can never silently alias an existing program.
        _generate(eng, [5, 9, 17], n=2)
        assert all(
            isinstance(k, tuple) and k[0][0] == "mesh"
            for k in eng._jit_prefill
        ), list(eng._jit_prefill)
    finally:
        eng.shutdown()


@needs_mesh
def test_prefix_insert_under_a_mesh_gathers_sharded_and_pulls_once(monkeypatch):
    """The prefix-cache insert of a TP engine is the same one path: the gather
    program's key carries the mesh, its output stays sharded on kv heads
    (kv_prefix_sharding), the one host pull assembles it, and the pool gets the
    slot's rows bit for bit, so the prompt's second send attaches them."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu._private.config import CONFIG
    from ray_tpu.llm._engine import DecodeEngine
    from ray_tpu.llm.kvcache import PrefixCacheManager

    monkeypatch.setitem(CONFIG._cache, "llm_prefill_bucket_min", 4)
    cfg, params = _model(n_kv_heads=4)
    eng = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=2,
                       prefix_cache=PrefixCacheManager(4, 1 << 20, name="tp-insert"))
    prompt = list(range(1, 14))
    try:
        first = _generate(eng, prompt, n=3)
        ((key, gather),) = eng._jit_kv_gather.items()
        assert key[0][0] == "mesh" and key[1] == ("kv_gather", 16), key
        kv = gather(eng._caches, np.int32(0))
        assert kv.shape == (cfg.n_layers, 2, 16, 4, cfg.head_dim)
        assert kv.sharding.spec == P(None, None, None, "tp", None)
        lease = eng.lease_prefix(prompt)
        assert lease is not None and lease.matched_tokens == 12
        try:
            got = lease.kv()
        finally:
            lease.release()
        want = np.stack([np.stack([np.asarray(ck[0, :12]), np.asarray(cv[0, :12])])
                         for ck, cv in eng._caches])
        np.testing.assert_array_equal(got, want)
        assert _generate(eng, prompt, n=3) == first
        assert eng.last_attach["cached_tokens"] == 12
        stats = eng.prefix_cache_stats()
        assert stats["inserts_issued"] == stats["inserts_completed"] == 1
    finally:
        eng.shutdown()


# -- adapter paging churn under TP -------------------------------------------

@needs_mesh
def test_adapter_paging_churn_token_identical_across_tp():
    """LoRA adapter tables shard with the model and the AdapterCache paging
    path stays token-identical: 6 adapters churning through 2 device slots
    on a TP=2 engine emit exactly what the TP=1 engine emits."""
    from ray_tpu.llm._engine import DecodeEngine

    cfg, params = _model(n_kv_heads=4)
    rng = np.random.default_rng(7)
    r = 4

    def adapter(scale):
        return {0: {
            "q_A": rng.normal(size=(cfg.hidden, r)).astype(np.float32) * scale,
            "q_B": rng.normal(size=(r, cfg.n_heads * cfg.head_dim)).astype(np.float32),
            "v_A": rng.normal(size=(cfg.hidden, r)).astype(np.float32) * scale,
            "v_B": rng.normal(size=(r, cfg.n_kv_heads * cfg.head_dim)).astype(np.float32),
        }}

    weights = {f"a{i}": adapter(1.0 + i) for i in range(6)}
    prompt = [7, 21, 3, 9]
    outs = {}
    stats = {}
    for tp in (1, 2):
        eng = DecodeEngine(
            cfg, params, num_slots=2, max_seq=64, tp=tp,
            lora_config={"max_loras": 8, "rank": r, "cache_slots": 2},
        )
        try:
            for name, w in weights.items():
                eng.add_lora(name, w, alpha=4.0)
            # Two churn passes: every adapter pages in, out, and back in.
            outs[tp] = [
                _generate(eng, prompt, n=6, lora=name)
                for _ in range(2) for name in weights
            ]
            stats[tp] = eng.adapter_stats()
        finally:
            eng.shutdown()
    assert outs[1] == outs[2], (outs[1][:2], outs[2][:2])
    # Distinct adapters really produce distinct generations (not a no-op).
    assert len({tuple(o) for o in outs[2][:6]}) > 1
    # The churn actually paged: evictions happened on both engines alike.
    assert stats[2]["evictions"] > 0 and stats[2]["install_programs"] in (1, None)


# -- PD disaggregation: TP prefill replica -> TP decode replica ---------------

@needs_mesh
def test_pd_handoff_tp_prefill_to_tp_decode_engine_level():
    """prefill_detached on a TP mesh keeps the KV prefix mesh-resident
    (sharded jax Array — no host gather), and a TP decode engine continues
    it to exactly the monolithic TP=1 output."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm._engine import DecodeEngine

    cfg, params = _model(n_kv_heads=4)
    prompt = [8, 2, 44, 7, 19, 21, 6]
    mono = DecodeEngine(cfg, params, num_slots=1, max_seq=64)
    pre = DecodeEngine(cfg, params, num_slots=1, max_seq=64, tp=2,
                       decode_loop=False)
    dec = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=2)
    try:
        expect = _generate(mono, prompt, n=8)
        first_logits, kv, plen = pre.prefill_detached(prompt)
        assert isinstance(kv, jax.Array), type(kv)  # stayed device-resident
        assert len(kv.sharding.device_set) == 2, kv.sharding
        acc, done = [], threading.Event()
        dec.submit_prefilled(
            kv, plen, first_logits, SamplingParams(max_tokens=8),
            lambda t, f: (acc.append(t), done.set() if f else None),
            token_ids=prompt,
        )
        assert done.wait(240)
        assert acc == expect, (acc, expect)
    finally:
        mono.shutdown()
        pre.shutdown()
        dec.shutdown()


@needs_mesh
def test_sharded_kv_streams_per_shard_over_device_channel():
    """The PD transport half: a mesh-sharded array streams as per-shard
    frames (each shard's bytes leave its own device — the plan has one entry
    per shard, no global gather) and the consumer can reassemble either
    host-side or straight onto ITS mesh layout per-shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.experimental.device_channel import DeviceChannel, _shard_plan
    from ray_tpu.llm.tp import build_tp_mesh

    mesh = build_tp_mesh(4)
    ns = NamedSharding(mesh, P(None, None, None, "tp", None))
    x = np.arange(2 * 2 * 6 * 4 * 3, dtype=np.float32).reshape(2, 2, 6, 4, 3)
    xs = jax.device_put(x, ns)
    plan = _shard_plan(xs)
    assert plan is not None and len(plan) == 4  # one frame group per shard

    ch = DeviceChannel.create(same_node=True, chunk_bytes=96)
    try:
        t = threading.Thread(target=lambda: ch.send(xs, timeout=60))
        t.start()
        got = ch.recv(timeout=60)
        t.join(timeout=60)
        np.testing.assert_array_equal(got, x)
    finally:
        ch.destroy()

    # Matching target layout: per-shard device staging, no host assembly of
    # the whole array, sharding preserved end to end.
    ch2 = DeviceChannel.create(same_node=True, chunk_bytes=96)
    try:
        t = threading.Thread(target=lambda: ch2.send(xs, timeout=60))
        t.start()
        got_dev = ch2.recv_device(timeout=60, sharding=ns)
        t.join(timeout=60)
        assert got_dev.sharding == ns
        np.testing.assert_array_equal(np.asarray(got_dev), x)
    finally:
        ch2.destroy()

    # Mismatched layout (a TP=2 consumer of a TP=4 producer) still lands
    # correctly — one explicit resharding copy, never corruption.
    ns2 = NamedSharding(build_tp_mesh(2), P(None, None, None, "tp", None))
    ch3 = DeviceChannel.create(same_node=True, chunk_bytes=96)
    try:
        t = threading.Thread(target=lambda: ch3.send(xs, timeout=60))
        t.start()
        got2 = ch3.recv_device(timeout=60, sharding=ns2)
        t.join(timeout=60)
        assert got2.sharding == ns2
        np.testing.assert_array_equal(np.asarray(got2), x)
    finally:
        ch3.destroy()


# -- checkpoint restore straight to mesh layout -------------------------------

@needs_mesh
def test_from_sharded_checkpoint_restores_to_mesh_layout(tmp_path):
    """from_sharded_checkpoint hands LAYOUTS to the resharding restore: TP
    leaves arrive already mesh-sharded, TP=1 leaves arrive device-resident
    (no intermediate host pytree), and generation matches the host-loaded
    engine token for token."""
    from jax.sharding import PartitionSpec as P, SingleDeviceSharding

    from ray_tpu import checkpoint as ckpt
    from ray_tpu.llm._engine import DecodeEngine

    cfg, params = _model(n_kv_heads=4)
    path = str(tmp_path / "w")
    ckpt.save(path, {"params": params})

    ref = DecodeEngine(cfg, params, num_slots=2, max_seq=64)
    eng4 = DecodeEngine.from_sharded_checkpoint(
        cfg, path, tp=4, num_slots=2, max_seq=64)
    eng1 = DecodeEngine.from_sharded_checkpoint(
        cfg, path, num_slots=2, max_seq=64)
    try:
        q4 = eng4.params["layer_0"]["attn"]["q"]["kernel"]
        assert q4.sharding.spec == P(None, "tp", None), q4.sharding
        q1 = eng1.params["layer_0"]["attn"]["q"]["kernel"]
        assert isinstance(q1, jax.Array)
        assert isinstance(q1.sharding, SingleDeviceSharding), q1.sharding
        prompt = [5, 9, 17, 3]
        expect = _generate(ref, prompt, n=8)
        assert _generate(eng4, prompt, n=8) == expect
        assert _generate(eng1, prompt, n=8) == expect
    finally:
        ref.shutdown()
        eng4.shutdown()
        eng1.shutdown()


# -- device-memory ledger: per-shard attribution ------------------------------

_LEDGER_SNIPPET = r"""
import json
import jax, jax.numpy as jnp
from ray_tpu.models.transformer import Transformer, get_config
from ray_tpu.llm._engine import DecodeEngine
from ray_tpu.util import xprof

cfg = get_config("test-tiny", scan_layers=False, remat=False, n_kv_heads=4)
model = Transformer(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
eng = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=2)
rep = xprof.device_memory_report()
row = rep["owners"][eng._xprof_owner]
out = {
    "pool_bytes": eng._kv_pool.total_bytes,
    "kv_slots": row["components"]["kv_slots"],
    "per_device": row.get("per_device", {}),
    "tracked_total": rep["tracked_bytes_total"],
}
eng.shutdown()
out["owners_after"] = [o for o in xprof.device_memory_report()["owners"]
                       if o.startswith("engine-")]
print("RESULT " + json.dumps(out))
"""


def test_device_memory_report_attributes_tp2_shards(multi_device_run):
    """The ledger's TP contract: on a TP=2 mesh, device_memory_report()
    attributes the engine's KV bytes per DEVICE (shard shape metadata only —
    per_device_byte_map never pulls), the per-device rows sum exactly to the
    pool's tracked total, split evenly across the mesh, and the owner row
    vanishes on shutdown."""
    out = multi_device_run(_LEDGER_SNIPPET, timeout=600)
    assert out["pool_bytes"] > 0
    assert out["kv_slots"] == out["pool_bytes"]
    assert out["tracked_total"] >= out["pool_bytes"]
    per_device = {k: int(v) for k, v in out["per_device"].items()}
    assert len(per_device) == 2, per_device      # exactly the TP=2 mesh
    assert sum(per_device.values()) == out["pool_bytes"], per_device
    lo, hi = sorted(per_device.values())
    assert lo == hi, per_device                  # heads shard evenly
    assert out["owners_after"] == []             # shutdown unregisters


# -- drain-and-retire frees every shard ---------------------------------------

@needs_mesh
def test_tp_shutdown_frees_every_shard():
    """leaksan: a TP engine registers its mesh-resident allocations
    (kv_shard_pool + tp_param_shards) and shutdown — the PR 9
    prepare_shutdown path every serve replica funnels through — balances the
    books exactly. The suite-wide leaksan_guard enforces the same invariant
    on every other test here."""
    from ray_tpu.devtools import leaksan
    from ray_tpu.llm._engine import DecodeEngine

    leaksan.enable()
    cfg, params = _model(n_kv_heads=4)
    before = leaksan.live_counts()
    eng = DecodeEngine(cfg, params, num_slots=2, max_seq=64, tp=2)
    during = leaksan.live_counts()
    assert during.get("kv_shard_pool", 0) == before.get("kv_shard_pool", 0) + 1
    assert during.get("tp_param_shards", 0) == before.get("tp_param_shards", 0) + 1
    eng.shutdown()
    eng.shutdown()  # idempotent: the second release must not go negative
    after = leaksan.live_counts()
    assert after.get("kv_shard_pool", 0) == before.get("kv_shard_pool", 0)
    assert after.get("tp_param_shards", 0) == before.get("tp_param_shards", 0)


# -- DP x TP serve composition ------------------------------------------------

@pytest.fixture(scope="module")
def tpu_cluster():
    """Single node advertising TPU:4 — room for a dp=2 x tp=2 fleet."""
    ray_tpu.init(num_cpus=4, num_tpus=4, worker_env=_WORKER_ENV)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _fresh_apps(request):
    yield
    if "tpu_cluster" in request.fixturenames:
        for app in list(serve.status()):
            serve.delete(app)


@needs_mesh
def test_dp_tp_replicas_compose(tpu_cluster):
    """DP x TP: dp_size=2 replicas, each a TP=2 mesh engine whose device
    gang is reserved atomically ({"TPU": 2} per replica). Both ranks serve,
    greedy output is identical across ranks, and the fleet consumes exactly
    the cluster's 4 chips."""
    from ray_tpu.llm import LLMConfig, replica_resources
    from ray_tpu.llm.dp_serve import build_dp_openai_app

    config = LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128, tp=2,
                       accelerator_resources={"TPU": 1})
    assert replica_resources(config) == {"TPU": 2.0}
    app = build_dp_openai_app(config, dp_size=2)
    handle = serve.run(app, name="dp-tp-llm", route_prefix=None, _timeout_s=300)

    ranks = handle.ranks.remote().result(timeout_s=120)
    assert sorted(ranks.values()) == [0, 1], ranks
    rs = [handle.generate.remote(f"req {i}", max_tokens=4) for i in range(10)]
    outs = [r.result(timeout_s=300) for r in rs]
    assert {o["dp_rank"] for o in outs} == {0, 1}
    a = handle.generate.remote("same prompt", max_tokens=6).result(timeout_s=120)
    b = handle.generate.remote("same prompt", max_tokens=6).result(timeout_s=120)
    assert a["token_ids"] == b["token_ids"]
    serve.delete("dp-tp-llm")


@needs_mesh
def test_pd_disagg_app_tp_replicas(tpu_cluster):
    """PD disaggregation with TP on both sides: a TP=2 prefill replica hands
    its mesh-sharded KV to a TP=2 decode replica and the end-to-end output
    matches a plain single-device LLM server's greedy output."""
    from ray_tpu.llm import LLMConfig, build_llm_deployment
    from ray_tpu.llm.pd_disagg import build_pd_openai_app

    config = LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128, tp=2)
    app = build_pd_openai_app(config, num_prefill=1, num_decode=1)
    handle = serve.run(app, name="pd-tp", route_prefix=None, _timeout_s=300)
    resp = handle.generate.remote("hello world", max_tokens=8).result(
        timeout_s=300)
    assert len(resp["token_ids"]) == 8

    ref_app = serve.run(
        build_llm_deployment(
            LLMConfig(model_id="test-tiny", num_slots=2, max_seq=128)),
        name="pd-tp-ref", route_prefix=None, _timeout_s=300)
    ref = ref_app.generate.remote("hello world", max_tokens=8).result(
        timeout_s=300)
    assert resp["token_ids"] == ref["token_ids"], (resp, ref)
    serve.delete("pd-tp")
    serve.delete("pd-tp-ref")


@needs_mesh
def test_reserve_tp_slice_placement_group(tpu_cluster):
    """cluster_utils.reserve_tp_slice gang-reserves one bundle per replica:
    a 2 x TPU:2 fleet fits TPU:4 and actors schedule into their bundles; an
    oversized fleet is refused loudly instead of wedging half-acquired."""
    from ray_tpu.cluster_utils import reserve_tp_slice
    from ray_tpu.util.placement_group import remove_placement_group

    pg = reserve_tp_slice(2, resource="TPU", replicas=2)
    try:
        assert len(pg.bundles) == 2

        @ray_tpu.remote(num_cpus=0, num_tpus=2, placement_group=pg,
                        placement_group_bundle_index=0)
        class Rep:
            def ping(self):
                return "ok"

        rep = Rep.remote()
        assert ray_tpu.get(rep.ping.remote(), timeout=60) == "ok"
        del rep
    finally:
        remove_placement_group(pg)

    with pytest.raises(TimeoutError):
        reserve_tp_slice(8, resource="TPU", replicas=2, ready_timeout_s=3.0)


# -- tiered hot tier on a mesh (docs/kvcache.md) -------------------------------

_TIER_SNIPPET = r"""
import json, threading
import numpy as np
import jax, jax.numpy as jnp
from ray_tpu.models.transformer import Transformer, get_config
from ray_tpu.llm._engine import DecodeEngine, SamplingParams

cfg = get_config("test-tiny", scan_layers=False, remat=False, n_kv_heads=4)
model = Transformer(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
rng = np.random.default_rng(13)
prompt = list(map(int, rng.integers(0, cfg.vocab_size, 40))) + [3, 1]

def generate(engine, p, n=8):
    acc, done = [], threading.Event()
    def cb(tok, fin):
        acc.append(tok)
        if fin:
            done.set()
    engine.submit(p, SamplingParams(max_tokens=n), cb)
    assert done.wait(240)
    return acc

ref_eng = DecodeEngine(cfg, params, num_slots=2, max_seq=128, tp=1,
                       prefix_cache=False)
# RAY_TPU_LLM_KV_DEVICE_BYTES (env) makes this engine build the TIERED cache
# with its hot tier sharded over the tp=2 mesh via kv_prefix_sharding.
eng = DecodeEngine(cfg, params, num_slots=2, max_seq=128, tp=2)
ref = generate(ref_eng, prompt)
cold = generate(eng, prompt)
warm_host = generate(eng, prompt)   # host tier; promotes to device
warm_dev = generate(eng, prompt)    # device tier: mesh-resident, zero H2D
mgr = eng._prefix_cache
shard_degrees = [
    len(dev.sharding.device_set) for dev, _nb in mgr._device._blocks.values()
]
out = {
    "ref": ref, "cold": cold, "host": warm_host, "dev": warm_dev,
    "tier": eng.last_attach["tier"], "shard_degrees": shard_degrees,
    "tiers": eng.prefix_cache_stats()["tiers"],
}
eng.shutdown()
ref_eng.shutdown()
print("RESULT " + json.dumps(out))
"""


def test_tiered_hot_tier_is_mesh_resident_tp2(multi_device_run):
    """TP=2 engine with the flag-driven tiered cache: device-warm greedy
    output is token-identical to a TP=1 cache-disabled reference, the warm
    attach reports tier=device, and every hot-tier block is SHARDED over
    the 2-device mesh (kv_prefix_sharding) — mesh-resident, so the attach
    pays zero host->device copies (docs/kvcache.md)."""
    out = multi_device_run(
        _TIER_SNIPPET,
        env_extra={"RAY_TPU_LLM_KV_DEVICE_BYTES": str(32 << 20)},
    )
    assert out["ref"] == out["cold"] == out["host"] == out["dev"], out
    assert out["tier"] == "device", out["tier"]
    assert out["shard_degrees"] and all(d == 2 for d in out["shard_degrees"])
    assert out["tiers"]["hits_device"] >= 1
