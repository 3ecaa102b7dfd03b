"""Weight-only int8 serving quantization (ops/quant.py +
llama.quantize_params): per-out-channel symmetric int8 with bf16 compute.
Pinned properties: small quantization error end-to-end, 4x weight shrink
(f32 master -> int8), identical engine plumbing (sharded included), and
training params untouched.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import quant


def test_quantize_int8_roundtrip_error():
    w = jax.random.normal(jax.random.key(0), (64, 128), jnp.float32)
    qd = quant.quantize_int8(w)
    assert qd["q"].dtype == jnp.int8 and qd["s"].shape == (128,)
    deq = qd["q"].astype(jnp.float32) * qd["s"]
    # symmetric per-channel: error bounded by half a step of each channel
    step = np.asarray(qd["s"])
    err = np.abs(np.asarray(deq) - np.asarray(w))
    assert (err <= 0.5 * step[None, :] + 1e-7).all()


def test_quantized_matmul_close():
    x = jax.random.normal(jax.random.key(1), (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (64, 32), jnp.float32)
    ref = np.asarray(x @ w)
    out = np.asarray(quant.matmul(x, quant.quantize_int8(w), jnp.float32))
    # per-channel int8: error accumulates over the 64-dim contraction but
    # stays well under 1% of the output scale (measured ~0.6%)
    assert np.abs(out - ref).max() <= 0.01 * np.abs(ref).max()


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    cfg = llama.LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32,
                               "attention_impl": "xla", "remat": False})
    params = llama.init(jax.random.key(0), cfg)
    return params, cfg


def test_quantized_logits_close_and_4x_smaller(tiny):
    params, cfg = tiny
    qparams = llama.quantize_params(params)
    tokens = jnp.asarray([[3, 5, 7, 11, 13, 17, 19, 23]], jnp.int32)
    ref = np.asarray(llama.apply(params, tokens, cfg))
    got = np.asarray(llama.apply(qparams, tokens, cfg))
    # int8 weights: logits track fp within a few percent of their scale
    scale = np.abs(ref).max()
    np.testing.assert_allclose(got, ref, atol=0.05 * scale)

    raw = sum(params["layers"][k].nbytes for k in llama.QUANT_LEAVES)
    q = sum(qparams["layers"][k]["q"].nbytes
            + qparams["layers"][k]["s"].nbytes
            for k in llama.QUANT_LEAVES)
    assert q < raw / 3.5  # f32 -> int8 (+small scales): ~4x


@pytest.mark.slow
def test_int8_engine_serves_and_matches_shapes(tiny):
    from kubeflow_tpu.serving.llm import LLMEngine

    params, cfg = tiny
    eng = LLMEngine(params, cfg, n_slots=2, max_len=64, buckets=(16,),
                    quantize="int8")
    eng.warmup()
    out = eng.generate(list(range(1, 10)), 6)
    assert len(out) == 6 and all(0 <= t < cfg.vocab_size for t in out)
    # greedy decode over int8 weights still matches the fp engine's tokens
    # for a tiny model MOST of the time; assert only validity + that the
    # engine really runs int8 leaves
    assert eng.params["layers"]["wq"]["q"].dtype == jnp.int8


@pytest.mark.slow
def test_int8_engine_sharded(tiny, devices8):
    from kubeflow_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_tpu.serving.llm import LLMEngine

    params, cfg = tiny
    mesh = make_mesh(MeshConfig(tensor=2), devices=devices8[:2])
    eng = LLMEngine(params, cfg, n_slots=2, max_len=64, buckets=(16,),
                    quantize="int8", mesh=mesh)
    eng.warmup()
    out = eng.generate(list(range(1, 10)), 6)
    assert len(out) == 6
    wq = eng.params["layers"]["wq"]
    # int8 blocks shard over tensor on the qkv axis; scales follow
    assert wq["q"].sharding.shard_shape(wq["q"].shape)[-1] == \
        wq["q"].shape[-1] // 2
    assert wq["s"].sharding.shard_shape(wq["s"].shape)[-1] == \
        wq["s"].shape[-1] // 2


# -- int8 KV cache ------------------------------------------------------------


def test_quantize_kv_roundtrip_and_idempotence():
    x = jax.random.normal(jax.random.key(3), (4, 16, 2, 32), jnp.float32)
    q, s = llama.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (4, 16, 2)
    deq = llama.dequantize_kv(q, s, jnp.float32)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    assert (err <= 0.5 * np.asarray(s)[..., None] + 1e-7).all()
    # idempotence: re-quantizing a dequantized value is exact (the max
    # element maps to +/-127 so the recomputed scale is identical) — this
    # is what keeps the prefix-cache hit path byte-identical under kv int8
    q2, s2 = llama.quantize_kv(deq)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=1e-6)


def test_kv_int8_decode_logits_close(tiny):
    params, cfg = tiny
    b, s = 2, 12
    toks = jax.random.randint(jax.random.key(4), (b, s), 0, cfg.vocab_size,
                              jnp.int32)
    _, ks, vs = llama.prefill(params, toks, cfg)
    lengths = jnp.full((b,), s, jnp.int32)
    last = toks[:, -1]

    cache_f = llama.init_cache(cfg, b, 32)
    cache_f = {"k": cache_f["k"].at[:, :, :s].set(ks),
               "v": cache_f["v"].at[:, :, :s].set(vs)}
    lo_f, _ = llama.decode_step(params, last, cache_f, lengths, cfg)

    kq, ksc = llama.quantize_kv(ks)
    vq, vsc = llama.quantize_kv(vs)
    cache_q = llama.init_cache(cfg, b, 32, kv_quantize="int8")
    cache_q = {"k": cache_q["k"].at[:, :, :s].set(kq),
               "v": cache_q["v"].at[:, :, :s].set(vq),
               # scale planes are lane-major: [L, slots, kv, max_len]
               "k_s": cache_q["k_s"].at[:, :, :, :s].set(
                   jnp.swapaxes(ksc, 2, 3)),
               "v_s": cache_q["v_s"].at[:, :, :, :s].set(
                   jnp.swapaxes(vsc, 2, 3))}
    lo_q, new_cache = llama.decode_step(params, last, cache_q, lengths, cfg)
    assert new_cache["k"].dtype == jnp.int8
    a, bq = np.asarray(lo_f), np.asarray(lo_q)
    # int8 KV error stays a small fraction of the logit scale
    assert np.abs(a - bq).max() <= 0.05 * np.abs(a).max() + 1e-3


def test_kv_int8_engine_generates(tiny):
    from kubeflow_tpu.serving.llm import LLMEngine
    params, cfg = tiny
    eng = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8, 16),
                    kv_quantize="int8")
    assert eng.cache["k"].dtype == jnp.int8
    out = eng.generate([3, 17, 42, 9, 55], max_new_tokens=6)
    assert len(out) == 6 and all(0 <= t < cfg.vocab_size for t in out)
    # continuous batching across quantized slots
    rids = [eng.submit([1 + i, 7, 11], 4) for i in range(4)]
    eng.run_until_idle()
    assert all(eng.is_done(r) for r in rids)


@pytest.mark.slow
def test_kv_int8_prefix_cache_hit_deterministic(tiny):
    """Under kv int8 the radix store keeps blocks QUANTIZED (int8 rows +
    f32 scales, the residency half of the int8-aware contract), hits are
    deterministic, and requantizing a stored block is idempotent — the
    continuation's re-quantize-on-write reproduces the identical int8
    rows the miss path wrote, which is why the hit path stays exact."""
    from kubeflow_tpu.serving.llm import LLMEngine
    params, cfg = tiny
    eng = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8, 16),
                    prefix_cache=True, kv_quantize="int8")
    prompt = [3, 17, 42, 9, 55, 2, 8, 13, 21, 34]  # 10 tokens: 1 block
    eng.generate(prompt, max_new_tokens=5)
    assert eng.metrics()["prefix_misses"] >= 1
    hit1 = eng.generate(prompt, max_new_tokens=5)
    assert eng.metrics()["prefix_hits"] >= 1
    hit2 = eng.generate(prompt, max_new_tokens=5)
    assert hit1 == hit2  # hits are deterministic
    # the stored block is int8 and byte-stable: re-quantizing its
    # dequantized rows reproduces the identical int8 payload
    root = eng.kvcache._roots[0]
    node = next(iter(root.children.values()))
    kq1, ks1, _vq, _vs = node.block.payload
    assert kq1.dtype == jnp.int8
    kq2, ks2 = llama.quantize_kv(
        llama.dequantize_kv(kq1, ks1, jnp.float32))
    np.testing.assert_array_equal(np.asarray(kq1), np.asarray(kq2))
