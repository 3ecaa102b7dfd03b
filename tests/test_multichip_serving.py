"""Stage-sharded tp×pp serving (ISSUE 14): the StageShardedEngine's
decomposed per-stage programs + microbatched MPMD decode must be
byte-exact against the single-program engine — including the edge
geometries (pp=1 degenerate, uneven layer/microbatch splits,
stage-count > wave-width) — and its observability surfaces (mesh_info,
pipeline bubble accounting, stage-keyed radix store) must hold their
contracts. Heavy combinations (prefix cache + chunked + int8, runtime
config e2e) ride the slow lane."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine
from kubeflow_tpu.serving.multichip import StageShardedEngine

# f32 + xla attention: byte parity across DIFFERENT program shapes is
# the contract under test; bf16 accumulation-order drift would make the
# comparison about dtype, not the machinery (the dryrun parity's choice)
CFG = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=4, n_heads=8,
                        n_kv_heads=4, d_ff=128, max_seq_len=64,
                        attention_impl="xla", remat=False,
                        dtype=jnp.float32)
KW = dict(n_slots=2, max_len=48, buckets=(8,), decode_chunk=4)
PROMPT = [5, 9, 2, 44, 17]


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.key(7), CFG)


@pytest.fixture(scope="module")
def reference(params):
    """Single-program outputs for the shared probes (greedy + seeded),
    computed once."""
    eng = LLMEngine(params, CFG, **KW)
    greedy = eng.generate(PROMPT, 12)
    rid = eng.submit(PROMPT, 10, temperature=0.9, top_k=8, seed=123)
    eng.run_until_idle()
    seeded = eng.result(rid)
    seeded_lps = eng.result_logprobs(rid)
    eng.release(rid)
    out = {"greedy": greedy, "seeded": seeded, "seeded_lps": seeded_lps,
           "greedy_lps": None}
    rid = eng.submit(PROMPT, 12)
    eng.run_until_idle()
    out["greedy_lps"] = eng.result_logprobs(rid)
    eng.close()
    return out


def _staged(params, **geo):
    kw = dict(KW)
    kw.update({k: geo.pop(k) for k in list(geo)
               if k in ("n_slots", "max_len", "buckets")})
    return StageShardedEngine(params, CFG, **geo, **kw)


def test_pp1_degenerate_byte_matches_single_program(params, reference):
    """stage=1 must byte-match the single-program engine — tokens AND
    logprobs, greedy and seeded — the degenerate-geometry contract."""
    eng = _staged(params, stage=1)
    rid = eng.submit(PROMPT, 12)
    eng.run_until_idle()
    assert eng.result(rid) == reference["greedy"]
    assert eng.result_logprobs(rid) == reference["greedy_lps"]
    eng.release(rid)
    rid = eng.submit(PROMPT, 10, temperature=0.9, top_k=8, seed=123)
    eng.run_until_idle()
    assert eng.result(rid) == reference["seeded"]
    assert eng.result_logprobs(rid) == reference["seeded_lps"]
    eng.close()


def test_pp2_tp2_parity_and_mesh_info(params, reference):
    """The flagship tp×pp layout on the real 8-device test mesh:
    concurrent greedy slots + a seeded request are byte-exact, and
    mesh_info reports the placed geometry."""
    eng = _staged(params, stage=2, tensor=2)
    rids = [eng.submit(PROMPT, 12) for _ in range(2)]
    eng.run_until_idle()
    for r in rids:
        assert eng.result(r) == reference["greedy"]
        eng.release(r)
    rid = eng.submit(PROMPT, 10, temperature=0.9, top_k=8, seed=123)
    eng.run_until_idle()
    assert eng.result(rid) == reference["seeded"]

    info = eng.mesh_info()
    assert info["layout"] == "tp2xpp2"
    assert info["axes"] == {"stage": 2, "tensor": 2}
    assert info["device_count"] == 4
    assert not info["virtual_stages"]
    assert info["stage_layers"] == [2, 2]
    assert len(info["per_stage_params_bytes"]) == 2
    assert info["params_bytes"] == sum(info["per_stage_params_bytes"])
    # metrics carries both the mesh section (healthz passthrough) and
    # the pipeline accounting
    m = eng.metrics()
    assert m["mesh"]["layout"] == "tp2xpp2"
    assert m["pipeline"]["stages"] == 2
    assert m["pipeline"]["schedule_bubble_frac"] is not None
    eng.close()


def test_uneven_layer_and_microbatch_split(params):
    """n_layers=3 over pp=2 (slab sizes [2, 1]) with n_slots=3 over 2
    microbatches (sizes [2, 1]): both uneven splits at once, byte-exact
    with three concurrent requests."""
    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=3,
                            n_heads=8, n_kv_heads=4, d_ff=128,
                            max_seq_len=64, attention_impl="xla",
                            remat=False, dtype=jnp.float32)
    p3 = llama.init(jax.random.key(3), cfg)
    single = LLMEngine(p3, cfg, n_slots=3, max_len=48, buckets=(8,))
    prompts = [PROMPT, [7, 7, 3], [1, 2, 3, 4, 5, 6, 7]]
    want = [single.generate(p, 8) for p in prompts]
    single.close()
    eng = StageShardedEngine(p3, cfg, stage=2, n_slots=3, max_len=48,
                             buckets=(8,))
    assert eng.mesh_info()["stage_layers"] == [2, 1]
    assert eng.mesh_info()["microbatches"] == [[0, 2], [2, 1]]
    rids = [eng.submit(p, 8) for p in prompts]
    eng.run_until_idle()
    got = [eng.result(r) for r in rids]
    assert got == want
    eng.close()


def test_stage_count_exceeds_wave_width(params, reference):
    """pp=4 with only 2 decode slots: microbatches cap at one slot each
    and the schedule still drains byte-exact."""
    eng = _staged(params, stage=4)
    assert eng.mesh_info()["microbatches"] == [[0, 1], [1, 1]]
    rids = [eng.submit(PROMPT, 12) for _ in range(2)]
    eng.run_until_idle()
    for r in rids:
        assert eng.result(r) == reference["greedy"]
    eng.close()


def test_pipeline_bubble_accounting(params):
    """stage_timing arms measured per-stage busy wall: bubble_frac lands
    in [0, 1], busy never exceeds stages × window, and the schedule
    fraction matches (S-1)/(M+S-1)."""
    eng = _staged(params, stage=2, stage_timing=True)
    rids = [eng.submit(PROMPT, 8) for _ in range(2)]
    eng.run_until_idle()
    pp = eng.pipeline_perf()
    assert pp["steps"] > 0
    assert pp["bubble_frac"] is not None
    assert 0.0 <= pp["bubble_frac"] <= 1.0
    assert sum(pp["stage_busy_s"]) <= pp["stages"] * pp["window_s"] + 1e-6
    # M=2 microbatches over S=2 stages -> (S-1)/(M+S-1) = 1/3
    assert pp["schedule_bubble_frac"] == pytest.approx(1 / 3, abs=1e-3)
    # reset clears the window
    eng.pipeline_perf(reset=True)
    assert eng.pipeline_perf()["steps"] == 0
    for r in rids:
        eng.release(r)
    eng.close()


def test_constructor_rejections(params):
    with pytest.raises(ValueError, match="speculative"):
        StageShardedEngine(params, CFG, stage=2, speculative=4, **KW)
    with pytest.raises(ValueError, match="adapter"):
        StageShardedEngine(params, CFG, stage=2,
                           adapters={"a": {}}, **KW)
    with pytest.raises(ValueError, match="mesh"):
        StageShardedEngine(params, CFG, stage=2, mesh=object(), **KW)
    with pytest.raises(ValueError, match="n_stages"):
        StageShardedEngine(params, CFG, stage=5, **KW)   # > n_layers
    with pytest.raises(ValueError, match="n_kv_heads"):
        StageShardedEngine(params, CFG, stage=2, tensor=3, **KW)
    with pytest.raises(ValueError, match="devices"):
        # tensor sharding cannot degrade to virtual staging
        StageShardedEngine(params, CFG, stage=2, tensor=2,
                           devices=jax.devices()[:2], **KW)


def test_single_engine_mesh_info(params):
    """The base engine reports the healthz mesh section too (layout
    'single' on one device) — the fleet surface is uniform."""
    eng = LLMEngine(params, CFG, **KW)
    info = eng.mesh_info()
    assert info["layout"] == "single"
    assert info["device_count"] == 1
    assert info["params_bytes"] > 0
    assert eng.metrics()["mesh"] == info
    eng.close()


def test_healthz_mesh_section_passthrough():
    """ModelServer.health() surfaces a model's mesh (+ pipeline) metrics
    as the /healthz `mesh` section — the EngineSupervisor passthrough
    route, exercised without building an engine."""
    from kubeflow_tpu.serving.model import Model, ModelRepository
    from kubeflow_tpu.serving.server import ModelServer

    class FakeModel(Model):
        def __init__(self):
            super().__init__("m")
            self._mark_ready()

        def load(self):
            pass

        def predict(self, payload):
            return payload

        def metrics(self):
            return {"mesh": {"layout": "tp2xpp2",
                             "axes": {"stage": 2, "tensor": 2},
                             "device_count": 4},
                    "pipeline": {"stages": 2, "bubble_frac": 0.25}}

    repo = ModelRepository()
    repo.register(FakeModel(), load=False)
    srv = ModelServer(repo).start()   # stop() joins serve_forever, so
    try:                              # the loop must be running
        body = srv.health()
        assert body["mesh"]["m"]["layout"] == "tp2xpp2"
        assert body["mesh"]["m"]["axes"] == {"stage": 2, "tensor": 2}
        assert body["mesh"]["m"]["pipeline"]["stages"] == 2
    finally:
        srv.stop()


def test_stage_partitioned_kvcache_units():
    """Stage-keyed radix facade: per-stage namespaces, min-across-stage
    matching under uneven eviction, logical accounting."""
    from kubeflow_tpu.kvcache import RadixKVCache, StagePartitionedKVCache

    inner = RadixKVCache(2, 64)
    c = StagePartitionedKVCache(inner, 2)
    toks = [1, 2, 3, 4, 5, 6]
    new = c.insert(toks, lambda i, a, b: ((0, i), (1, i)))
    assert new == 3                      # logical new blocks
    assert inner.n_blocks == 6           # physical: one per stage
    m = c.match(toks)
    assert m.tokens == 6
    assert m.payloads[1] == ((0, 1), (1, 1))   # per-stage tuple
    c.release(m)
    assert c.cached_prefix_len(toks) == 6
    st = c.stats()
    assert st["stages"] == 2 and st["logical_blocks"] == 3
    c.check_invariants()

    # uneven chains (one stage's tail evicted) truncate to the common
    # prefix — match must never hand out a block a stage cannot back
    victim = inner.match(toks, namespace=(None, 1))
    inner.release(victim)
    # manually evict stage 1's last block by filling capacity... simpler:
    # insert a longer chain only under stage 0 and confirm min() rules
    inner.insert([1, 2, 3, 4, 5, 6, 7, 8],
                 lambda i, a, b: ("only0", i), namespace=(None, 0))
    m = c.match([1, 2, 3, 4, 5, 6, 7, 8])
    assert m.tokens == 6   # stage 1 holds only 3 blocks
    c.release(m)
    c.clear()
    assert c.n_blocks == 0


@pytest.mark.slow
def test_prefix_cache_chunked_int8_parity(params):
    """The full correctness gauntlet under pp: int8 KV + radix prefix
    cache + chunked long-prompt prefill, replayed twice (miss then hit)
    — byte-exact against the single-program engine, with the stage-keyed
    store actually hitting."""
    kw = dict(n_slots=3, max_len=160, buckets=(8, 16, 32), decode_chunk=4,
              prefix_cache=True, prefix_cache_blocks=64,
              kv_quantize="int8")
    single = LLMEngine(params, CFG, **kw)
    eng = StageShardedEngine(params, CFG, stage=2, tensor=2, **kw)
    shared = [(i * 7) % 250 + 1 for i in range(20)]
    long_prompt = [(i * 11) % 250 + 1 for i in range(70)]   # chunked
    probes = [shared + [17, 23, 5], shared + [101, 9], long_prompt,
              [3, 7, 11]]
    for _pass in range(2):   # cold, then cache-hit
        for p in probes:
            assert eng.generate(p, 10) == single.generate(p, 10), \
                (_pass, p[:4])
    m = eng.metrics()
    assert m["prefix_hits"] >= 3
    assert m["prefix_cache"]["stages"] == 2
    assert m["prefix_cache"]["logical_blocks"] > 0
    single.close()
    eng.close()


@pytest.mark.slow
def test_runtime_parallel_config_e2e():
    """config.parallel {tensor, stage} builds the stage-sharded engine
    inside the supervisor factory: predict round-trips byte-exact vs a
    single-program engine on the same seed-0 init, and metrics carry
    mesh + pipeline + supervisor sections (the /healthz inputs)."""
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    overrides = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=8,
                     n_kv_heads=4, d_ff=128, max_seq_len=64,
                     attention_impl="xla", remat=False,
                     dtype=jnp.float32)
    model = LLMModel("m", model=overrides, n_slots=2, max_len=48,
                     buckets=(8,), parallel={"tensor": 2, "stage": 2},
                     supervisor={"rewarm": False})
    model.load()
    try:
        # LLMModel inits params from seed 0 over the same cfg — the
        # reference engine reproduces them exactly
        cfg = llama.LlamaConfig(**overrides)
        single = LLMEngine(llama.init(jax.random.key(0), cfg), cfg, **KW)
        want = single.generate(PROMPT, 8)
        single.close()
        out = model.predict({"prompt_tokens": PROMPT,
                             "max_new_tokens": 8})
        assert out["output_tokens"] == want
        m = model.metrics()
        assert m["mesh"]["layout"] == "tp2xpp2"
        assert m["pipeline"]["stages"] == 2
        assert "supervisor" in m
    finally:
        model.unload()


def test_runtime_parallel_config_validation():
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    with pytest.raises(ValueError, match="disaggregated"):
        LLMModel("m", parallel={"stage": 2}, disaggregated=True)
    with pytest.raises(ValueError, match="not both"):
        LLMModel("m", parallel={"stage": 2}, mesh={"tensor": 2})
    with pytest.raises(ValueError, match="not both"):
        # a silently-dropped tensor request must reject too
        LLMModel("m", parallel={"tensor": 2}, mesh={"data": 2})
    with pytest.raises(ValueError, match=">= 1"):
        LLMModel("m", parallel={"stage": 0})


@pytest.mark.slow
def test_stage_sharded_parity_with_flash_decode_impl(params):
    """ISSUE 15 acceptance: the stage-sharded engine inherits the
    decode-attention impl for free through the shared layer bodies
    (llama.verify_inner) — with `decode_attention_impl: flash`
    (interpret mode on CPU) the pp2 engine stays byte-exact against
    the single-program FLASH engine: tokens AND logprobs, greedy and
    seeded, int8 KV. (Flash-vs-flash: the suite's contract is the
    stage machinery's exactness; the flash-vs-xla contract is
    tests/test_flash_decode.py.)"""
    import dataclasses

    cfg = dataclasses.replace(CFG, decode_attention_impl="flash")
    ref = LLMEngine(params, cfg, kv_quantize="int8", **KW)
    eng = StageShardedEngine(params, cfg, stage=2, kv_quantize="int8",
                             **KW)
    try:
        assert eng.metrics()["decode_attention_impl"] == "flash"
        for kwargs in (dict(),
                       dict(temperature=0.9, top_k=8, seed=123)):
            rid_r = ref.submit(list(PROMPT), 10, **kwargs)
            ref.run_until_idle()
            rid_s = eng.submit(list(PROMPT), 10, **kwargs)
            eng.run_until_idle()
            assert eng.result(rid_s) == ref.result(rid_r), kwargs
            assert eng.result_logprobs(rid_s) \
                == ref.result_logprobs(rid_r), kwargs
            ref.release(rid_r)
            eng.release(rid_s)
    finally:
        ref.close()
        eng.close()
