"""The laguna family (ISSUE 34) at a toy size on the CPU, against the plain
reference `benchmark/reference/laguna.py` (which imports nothing of the
program): the plain forward pass; prefill then decode through BOTH cache
slabs past the window and round the ring; the continuation chain of a
prompt longer than the largest bucket; `LLMEngine` with the family end to
end, and an InferenceService with `modelFormat: laguna` through
`Platform.apply` and the router; every load-time refusal; both serving
flash kernels with a window under the Pallas interpreter against the XLA
mask; the rotary variants against hand arithmetic; the router against
`sigmoid_route`; and that `llama`'s lowered programs are the parent's.

Toy: hidden 64, heads 6 (full) and 8 (sliding) over 2 KV heads of 16,
window 8, ring 16 or 24, 16 experts top 4, scale 2.5, 5 layers, vocabulary
128, YaRN factor 4 over 32 positions. Float32 weights, so that a flipped
expert is a fault and not rounding."""

import functools
import hashlib
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import laguna, llama
from kubeflow_tpu.ops import flash_decode, flash_prefill, moe, rope
from kubeflow_tpu.serving.llm import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import laguna as ref  # noqa: E402

TOY = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "toy_laguna.json")))["config"]
PUBLISHED = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "laguna-xs.2-serve.json")))
#: the reference's configuration: the published file under the toy's sizes
RCFG = {**PUBLISHED, **{k: v for k, v in TOY.items() if k != "system"}}
KEYS = PUBLISHED["system"]["model_keys"]
SEED = 11


def _cfg(**kw):
    return laguna.LagunaConfig(**{k: RCFG[k] for k in KEYS},
                               dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def params():
    return laguna.init(jax.random.key(SEED), _cfg())


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """A KV block of 8 rows: the toy's ring is then window + chunk rows,
    several blocks long, and the Pallas kernels run in the interpreter."""
    monkeypatch.setattr(flash_decode, "DEFAULT_BLOCK_KV", 8)
    monkeypatch.setattr(flash_decode, "FORCE_INTERPRET", True)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", True)


def _tokens(n, seed=3, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, RCFG["vocab_size"], (batch, n)), jnp.int32)


# -- the plain forward pass ----------------------------------------------------

def test_plan_and_stacks(params):
    p = laguna.plan(_cfg())
    assert [(l.attn, l.attn_at, l.ffn, l.ffn_at) for l in p] == [
        ("full", 0, "dense_ffn", 0), ("sliding", 0, "experts", 0),
        ("sliding", 1, "experts", 1), ("sliding", 2, "experts", 2),
        ("full", 1, "experts", 3)]
    assert params["full"]["wq"].shape == (2, 64, 6 * 16)
    assert params["sliding"]["wq"].shape == (3, 64, 8 * 16)
    assert params["full"]["wg"].shape == (2, 64, 6)
    assert params["experts"]["w_gate"].shape == (4, 16, 64, 32)
    assert params["experts"]["router"].dtype == jnp.float32


def test_weights_are_the_references_bit_for_bit(params):
    theirs = ref.init_params(SEED, RCFG)
    np.testing.assert_array_equal(params["embed"], theirs["embed"])
    np.testing.assert_array_equal(params["lm_head"], theirs["lm_head"])
    for l, layer in enumerate(laguna.plan(_cfg())):
        for part, stack, at in (("attn", layer.attn, layer.attn_at),
                                ("ffn", layer.ffn, layer.ffn_at)):
            for leaf, w in theirs["layers"][l][part].items():
                np.testing.assert_array_equal(params[stack][leaf][at], w)


def test_apply_matches_the_reference(params):
    toks = _tokens(40)
    with jax.default_matmul_precision("highest"):
        mine = laguna.apply(params, toks, _cfg())
    theirs = ref.logits(SEED, toks, RCFG)
    assert float(jnp.abs(mine - theirs).max()) < 2e-4
    assert float(jnp.abs(theirs).max()) > 1.0


# -- two slabs: prefill, then decode past the window and round the ring --------

def _serve(params, cfg, toks, n0, kvq, chunk):
    """Prefill toks[:, :n0] (one chunk), then decode the rest a token at a
    time, teacher-forced; -> logits [B, T - n0 + 1... ] at every position
    from n0 - 1."""
    b = toks.shape[0]
    cache = laguna.init_cache(cfg, b, 64, kv_quantize=kvq, chunk=chunk)
    lg0, ks, vs = laguna.prefill(params, toks[:, :n0], cfg)
    for i in range(b):
        row = lambda t: jax.tree.map(lambda a: a[:, i], t)   # noqa: E731
        cache = laguna.cache_write(cache, i, 0, n0, row(ks), row(vs),
                                   kv_quantize=kvq)
    out = [lg0[:, -1]]
    lengths = jnp.full((b,), n0, jnp.int32)
    step = jax.jit(functools.partial(laguna.decode_step, cfg=cfg))
    for t in range(n0, toks.shape[1]):
        lg, cache = step(params, toks[:, t], cache, lengths)
        cache.pop("counters")
        out.append(lg)
        lengths = lengths + 1
    return jnp.stack(out, axis=1), cache, step


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_prefill_then_decode_through_both_slabs(params, impl):
    """Contexts to 44 over a ring of 16 rows (window 8 + chunk 8): every
    decode step past 16 wraps, and the logits stay the full forward
    pass's."""
    cfg = _cfg(decode_attention_impl=impl, prefill_attention_impl=impl)
    toks = _tokens(44)
    served, cache, _ = _serve(params, cfg, toks, 8, None, chunk=8)
    assert cache["kw"].shape[2] == 16 and cache["k"].shape[2] == 64
    theirs = ref.logits(SEED, toks, RCFG)[:, 7:]
    assert float(jnp.abs(served - theirs).max()) < 2e-4


def test_int8_slabs_the_kernel_and_the_einsum_agree(params):
    """Same int8 cache, two attention paths: the ring's scales stored by
    the kernel (flash) or scattered (einsum) give the same logits; and a
    slot that is not active attends nothing and harms no one."""
    toks = _tokens(26, seed=5)
    got = {}
    for impl in ("xla", "flash"):
        cfg = _cfg(decode_attention_impl=impl, prefill_attention_impl=impl)
        got[impl], cache, step = _serve(params, cfg, toks, 8, "int8",
                                        chunk=8)
        assert cache["kw_s"].shape == (3, 2, 2, 16)
    assert float(jnp.abs(got["xla"] - got["flash"]).max()) < 2e-3
    at = jnp.asarray([26, 26], jnp.int32)
    lg, _ = step(params, toks[:, 0], dict(cache), at,
                 active=jnp.asarray([True, False]))
    both, _ = step(params, toks[:, 0], dict(cache), at)
    np.testing.assert_allclose(lg[0], both[0], atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(lg)))


@pytest.mark.parametrize("impl", ["xla"])
def test_continuation_crosses_the_rings_seam(params, impl):
    """A prompt in three chunks of 12 against a ring of 24 rows: the third
    chunk's rows lie across the seam; then decode. extract_prefix hands
    back the full layers' whole prefix and the sliding layers' last
    window."""
    cfg = _cfg(decode_attention_impl=impl, prefill_attention_impl=impl)
    toks = _tokens(40, seed=9, batch=1)
    cache = laguna.init_cache(cfg, 1, 64, chunk=12)
    assert cache["kw"].shape[2] == 24
    logits = []
    for start in (0, 12, 24):
        chunk = toks[:, start:start + 12]
        if start == 0:
            lg, ks, vs = laguna.prefill(params, chunk, cfg)
        else:
            kp, vp = laguna.extract_prefix(cfg, cache, 0, start,
                                           dtype=jnp.float32)
            assert kp["full"].shape[2] == start
            assert kp["window"].shape[2] == 8
            lg, ks, vs = laguna.prefill_continue(params, chunk, kp, vp, cfg)
        row = lambda t: jax.tree.map(lambda a: a[:, 0], t)   # noqa: E731
        cache = laguna.cache_write(cache, 0, start, 12, row(ks), row(vs))
        logits.append(lg)
    lengths = jnp.asarray([36], jnp.int32)
    for t in range(36, 40):
        lg, cache = laguna.decode_step(params, toks[:, t], cache, lengths,
                                       cfg)
        cache.pop("counters")
        logits.append(lg[:, None])
        lengths = lengths + 1
    theirs = ref.logits(SEED, toks, RCFG)
    assert float(jnp.abs(jnp.concatenate(logits, 1) - theirs).max()) < 2e-4


# -- the engine, and the platform ----------------------------------------------

@pytest.fixture(scope="module")
def engine_runs(params):
    """(kv_quantize, impl) -> [(prompt, served tokens)], metrics: prompts
    shorter than a bucket, a bucket long, and longer than the largest
    bucket (the chain), decoded past the window and round the ring."""
    flash_decode.DEFAULT_BLOCK_KV, keep = 8, flash_decode.DEFAULT_BLOCK_KV
    flash_decode.FORCE_INTERPRET = flash_prefill.FORCE_INTERPRET = True
    out = {}
    try:
        for kvq, impl in ((None, "flash"),):
            eng = LLMEngine(params, _cfg(), n_slots=4, max_len=64,
                            buckets=(8, 16), decode_chunk=4,
                            kv_quantize=kvq, decode_attention_impl=impl,
                            prefill_attention_impl=impl, family=laguna)
            prompts = [list(map(int, np.random.default_rng(i).integers(
                0, 128, n))) for i, n in enumerate((5, 12, 16, 37, 29))]
            rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
            eng.run_until_idle()
            out[kvq, impl] = ([(p, eng.result(r))
                               for p, r in zip(prompts, rids)],
                              eng.metrics())
            eng.close()
    finally:
        flash_decode.DEFAULT_BLOCK_KV = keep
        flash_decode.FORCE_INTERPRET = flash_prefill.FORCE_INTERPRET = False
    return out


def _gaps(runs):
    """The widest gap of each request's served tokens under the
    reference's best: one padded pass (causal: the pad is unseen)."""
    t = max(len(p) + len(s) for p, s in runs)
    toks = jnp.asarray([(p + s + [0] * t)[:t] for p, s in runs], jnp.int32)
    r = np.asarray(ref.logits(SEED, toks, RCFG))
    out = []
    for i, (p, s) in enumerate(runs):
        pos = np.arange(len(p) - 1, len(p) + len(s) - 1)
        out.append(float((r[i, pos].max(-1) - r[i, pos, np.asarray(s)]).max()))
    return out


def test_engine_greedy_tokens_are_the_references(engine_runs):
    """Prompts of 5, 12, 16, 37 (a chain of three) and 29 tokens, 20 served
    tokens each through the flash kernels and a ring of 24 rows."""
    runs, _ = engine_runs[None, "flash"]
    assert [len(s) for _, s in runs] == [20] * 5
    assert max(_gaps(runs)) <= 1e-4


def test_engine_metrics_carry_the_slabs_and_the_experts(engine_runs):
    _, m = engine_runs[None, "flash"]
    assert m["kv_window_ring_tokens"] == 24          # window 8 + bucket 16
    assert m["kv_bytes_window"] == 2 * 3 * 4 * 24 * 2 * 16 * 4
    assert m["kv_bytes_full"] == 2 * 2 * 4 * 64 * 2 * 16 * 4
    steps = m["moe_assignments"] / (4 * 4 * 4)   # slots x top 4 x 4 layers
    assert steps == int(steps) and steps >= 20
    assert 0 < m["moe_expert_visits"] <= 16 * 4 * steps
    assert m["moe_rows_dropped"] == 0
    assert m["moe_load_max_over_mean"] >= 1.0


def test_inference_service_streams_through_the_router(tmp_path):
    """modelFormat laguna through Platform.apply: Ready, then a streamed
    completion over /openai/v1/completions."""
    import http.client
    import urllib.parse

    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.control.conditions import has_condition

    model = {k: RCFG[k] for k in KEYS}
    model["dtype"] = "float32"
    isvc = {"apiVersion": "kubeflow-tpu/v1", "kind": "InferenceService",
            "metadata": {"name": "laguna-toy"},
            "spec": {"predictor": {"minReplicas": 1, "model": {
                "modelFormat": "laguna",
                "config": {"model": model, "seed": SEED, "n_slots": 2,
                           "max_len": 64, "buckets": [8, 16],
                           "decode_chunk": 4, "kv_quantize": "int8",
                           "usage_timing": True}}}}}
    platform = Platform(n_devices=1, root=str(tmp_path),
                        components=("serving",)).start()
    try:
        platform.apply(isvc)
        obj = platform.wait(
            "InferenceService", "laguna-toy",
            lambda o: any(has_condition(o.get("status", {}), c)
                          for c in ("Ready", "Failed")), timeout=300)
        assert has_condition(obj["status"], "Ready"), obj["status"]
        u = urllib.parse.urlparse(obj["status"]["url"])
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
        conn.request("POST", "/openai/v1/completions", body=json.dumps(
            {"model": "laguna-toy", "prompt": prompt, "max_tokens": 12,
             "temperature": 0.0, "stream": True}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        toks, usage = [], None
        for line in resp.read().decode().splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                chunk = json.loads(line[6:])
                usage = chunk.get("usage") or usage
                toks += [c["token_id"] for c in chunk.get("choices", ())
                         if c.get("token_id") is not None]
        conn.close()
        assert len(toks) == 12 and _gaps([(prompt, toks)])[0] <= 0.05
        assert "moe_assignments" in usage["counters"]
    finally:
        platform.stop()


@pytest.mark.parametrize("option,value", [
    ("speculative", 2), ("prefix_cache", True), ("kv_layout", "paged"),
    ("parallel", {"tensor": 2}), ("adapters", {"a": {"checkpoint": "/x"}}),
    ("mesh", {"tensor": 2}), ("lora", {"rank": 4}), ("quantize", "int8"),
    ("disaggregated", True)])
def test_load_refuses_by_name_what_the_family_does_not_serve(option, value):
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    with pytest.raises(ValueError, match=f"does not serve `{option}`"):
        LLMModel("m", family="laguna", **{option: value})
    LLMModel("m", family="laguna", kv_layout="slab")     # what it serves


def test_verify_step_and_adapters_raise(params):
    with pytest.raises(NotImplementedError, match="verify"):
        laguna.verify_step(params, None, None, None, _cfg())
    with pytest.raises(NotImplementedError, match="adapters"):
        laguna.prefill(params, _tokens(8), _cfg(), lora={})
    with pytest.raises(NotImplementedError, match="bfloat16"):
        laguna.quantize_params(params)


# -- the window in both serving flash kernels ----------------------------------

def _ring_cache(rng, b, top, ring, nkv, hd, quantized):
    """A ring as decode has filled it up to position `top[b]`: row r of
    slot b holds the newest position <= top[b] that is r mod ring."""
    pos = np.zeros((b, ring), np.int64)
    for i in range(b):
        r = np.arange(ring)
        pos[i] = top[i] - ((top[i] - r) % ring)
    k = rng.normal(size=(b, ring, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(b, ring, nkv, hd)).astype(np.float32)
    return pos, k, v


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("quantized", [False, True])
def test_flash_decode_window_matches_the_masked_einsum(group, quantized):
    nkv, hd, ring, window, bk = 2, 16, 32, 8, 8
    rng = np.random.default_rng(group)
    lengths = np.array([3, 7, 8, 21, 40, 100])        # before, at, past, wrap
    b = len(lengths)
    _, k, v = _ring_cache(rng, b, lengths, ring, nkv, hd, quantized)
    q = jnp.asarray(rng.normal(size=(b, 1, group * nkv, hd)), jnp.float32)
    cache = {"k": jnp.asarray(k)[None], "v": jnp.asarray(v)[None]}
    if quantized:
        kq, ks = llama.quantize_kv(cache["k"])
        vq, vs = llama.quantize_kv(cache["v"])
        cache = {"k": kq, "v": vq, "k_s": jnp.swapaxes(ks, 2, 3),
                 "v_s": jnp.swapaxes(vs, 2, 3)}
    dims = laguna._AttnDims(group * nkv, nkv, hd, jnp.float32)
    positions = jnp.asarray(lengths)[:, None]
    want = llama.decode_attention(dims, q, cache, 0, positions, impl="xla",
                                  window=window)
    got = flash_decode.flash_decode_attention(
        q, cache["k"], cache["v"], jnp.asarray(lengths, jnp.int32), layer=0,
        k_scale=cache.get("k_s"), v_scale=cache.get("v_s"), block_kv=bk,
        window=window)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=2e-5)
    # and the einsum's mask is the definition: position p seen iff
    # 0 <= q - p < window, p the newest position the row can hold
    kf = (cache["k"].astype(jnp.float32) * jnp.swapaxes(
        cache["k_s"], 2, 3)[..., None] if quantized else cache["k"])[0]
    vf = (cache["v"].astype(jnp.float32) * jnp.swapaxes(
        cache["v_s"], 2, 3)[..., None] if quantized else cache["v"])[0]
    for i in (0, 3, 5):
        r = np.arange(ring)
        held = lengths[i] - ((lengths[i] - r) % ring)
        seen = (held >= 0) & (lengths[i] - held < window)
        qi = np.asarray(q[i, 0]).reshape(nkv, group, hd)
        s = np.einsum("kgh,rkh->kgr", qi, np.asarray(kf[i])) / math.sqrt(hd)
        s = np.where(seen[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("kgr,rkh->kgh", p, np.asarray(vf[i]))
        np.testing.assert_allclose(np.asarray(want[i, 0]).reshape(o.shape),
                                   o, atol=2e-5)


def test_flash_decode_window_fetches_no_block_below_the_window():
    """The kernel's own count: a window of one block's length walks two
    grid steps a slot whatever the context, where the slab walks the
    context's blocks."""
    import re

    nkv, hd, ring, window, bk = 2, 16, 48, 16, 16
    q = jnp.zeros((2, 1, 12, hd), jnp.float32)
    k = jnp.zeros((1, 2, ring, nkv, hd), jnp.float32)
    text = jax.jit(functools.partial(
        flash_decode.flash_decode_attention, layer=0, block_kv=bk,
        window=window, interpret=False)).trace(
            q, k, k, jnp.asarray([500, 3], jnp.int32)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    grids = re.findall(r"grid = array<i64: ([\d, ]+)>", text) \
        or re.findall(r'"grid": *\[([\d, ]+)\]', text)
    # 2 slots x 2 KV blocks, not 2 x (ring / block = 3) nor x context
    if grids:
        assert grids[0].replace(" ", "") == "2,2"
    with pytest.raises(ValueError, match="ring"):
        flash_decode.flash_decode_attention(
            q, k[:, :, :40], k[:, :, :40], jnp.asarray([5, 3]), layer=0,
            block_kv=bk, window=window)


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("q_offset", [0, 8, 19])
def test_flash_prefill_window_matches_the_masked_mha(group, quantized,
                                                     q_offset):
    from kubeflow_tpu.ops.attention import mha

    nkv, hd, s, window = 2, 16, 24, 8
    rng = np.random.default_rng(group + q_offset)
    t = q_offset + s
    q = jnp.asarray(rng.normal(size=(2, s, group * nkv, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, t, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, t, nkv, hd)), jnp.float32)
    kw = {}
    if quantized:
        kq, ks = llama.quantize_kv(k)
        vq, vs = llama.quantize_kv(v)
        kw = {"k_scale": ks, "v_scale": vs}
        want = mha(q, kq * ks[..., None], vq * vs[..., None],
                   q_offset=q_offset, window=window)
        k, v = kq, vq
    else:
        want = mha(q, k, v, q_offset=q_offset, window=window)
    got = flash_prefill.flash_prefill_attention(
        q, k, v, q_offset=q_offset, block_q=8, block_kv=128, window=window,
        **kw)
    np.testing.assert_allclose(got, want, atol=3e-5)
    # several KV blocks a q block: the band's clamp and the skip
    if not quantized:
        wide = jnp.concatenate([k] * 8, axis=1)[:, :128 + t]
        wide_v = jnp.concatenate([v] * 8, axis=1)[:, :128 + t]
        got = flash_prefill.flash_prefill_attention(
            q, wide, wide_v, q_offset=128 + q_offset, block_q=8,
            block_kv=128, window=window)
        want = mha(q, wide, wide_v, q_offset=128 + q_offset, window=window)
        np.testing.assert_allclose(got, want, atol=3e-5)


# -- rotary kinds ---------------------------------------------------------------

def test_rope_defaults_are_unchanged():
    f = rope.rope_frequencies(16, 10000.0)
    want = 1.0 / (10000.0 ** (np.arange(0, 16, 2, dtype=np.float32) / 16))
    np.testing.assert_array_equal(f, jnp.asarray(want, jnp.float32))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(5)
    a = rope.apply_rope(x, pos, theta=10000.0)
    b = rope.apply_rope(x, pos, theta=10000.0, rotary_dim=16, yarn=None)
    np.testing.assert_array_equal(a, b)
    ang = np.arange(5)[:, None] * want
    x1, x2 = np.asarray(x[0, :, 0, :8]), np.asarray(x[0, :, 0, 8:])
    np.testing.assert_allclose(a[0, :, 0, :8],
                               x1 * np.cos(ang) - x2 * np.sin(ang),
                               atol=1e-5)


def test_yarn_and_partial_rotary_by_hand():
    """factor 4, 32 original positions, theta 500000, over 8 rotated dims
    of a head of 16: pair i turns 32 / (2 pi theta**(i/4)) times; the ramp
    runs from floor(pair turning beta_fast = 4 times) to ceil(pair turning
    beta_slow = 1 time)."""
    y = rope.Yarn(4.0, 32, beta_fast=4.0, beta_slow=1.0)
    f = np.asarray(rope.rope_frequencies(16, 500000.0, rotary_dim=8, yarn=y))
    base = 500000.0 ** (-np.arange(4) / 4.0)

    def pair(turns):
        return 8 * math.log(32 / (turns * 2 * math.pi)) / (
            2 * math.log(500000.0))
    low, high = max(math.floor(pair(4.0)), 0), min(math.ceil(pair(1.0)), 7)
    assert (low, high) == (0, 1)
    ramp = np.clip((np.arange(4) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(f, base / 4.0 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    assert abs(y.cos_sin_scale - (0.1 * math.log(4.0) + 1)) < 1e-12
    assert rope.Yarn(64.0, 4096).cos_sin_scale == pytest.approx(
        1.4158883083359672, abs=1e-12)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 3, 1, 16)),
                    jnp.float32)
    out = np.asarray(rope.apply_rope(x, jnp.arange(3), theta=500000.0,
                                     rotary_dim=8, yarn=y))
    np.testing.assert_array_equal(out[..., 8:], np.asarray(x[..., 8:]))
    ang = np.arange(3)[:, None] * f
    x1, x2 = np.asarray(x[0, :, 0, :4]), np.asarray(x[0, :, 0, 4:8])
    s = y.cos_sin_scale
    np.testing.assert_allclose(
        out[0, :, 0, :4], (x1 * np.cos(ang) - x2 * np.sin(ang)) * s,
        atol=1e-5)
    # ... and the reference, which has its own arithmetic, agrees
    theirs = ref.rope(x[0], jnp.arange(3),
                      RCFG["rope_parameters"]["full_attention"])
    mine = rope.apply_rope(x, jnp.arange(3),
                           **_cfg().rope(laguna.FULL))[0]
    np.testing.assert_allclose(mine, theirs, atol=1e-6)


# -- the experts ------------------------------------------------------------------

def test_router_choice_and_weights_are_sigmoid_routes(params):
    cfg = _cfg()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)),
                    jnp.float32)
    w = params["experts"]["router"][1]
    idx, wt = moe.sigmoid_route(x, w, jnp.zeros((16,)), cfg.share_args)
    theirs = np.asarray(ref.route(RCFG, x, w))            # [T, E]
    assert ((theirs > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(
        np.take_along_axis(theirs, np.asarray(idx), -1), wt, rtol=1e-6)
    np.testing.assert_allclose(wt.sum(-1), 2.5, rtol=1e-6)


def test_shared_expert_counted_once(params):
    cfg = _cfg()
    layer = laguna.plan(cfg)[2]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 6, 64)),
                    jnp.float32)
    without = jax.tree.map(lambda a: a, params)
    without["experts"] = dict(params["experts"], shared_down=jnp.zeros_like(
        params["experts"]["shared_down"]))
    full, _ = laguna._ffn(cfg, layer, params, x)
    routed, _ = laguna._ffn(cfg, layer, without, x)
    p, i = params["experts"], layer.ffn_at
    h = laguna.rms_norm(x, p["mlp_norm"][i], cfg.rms_norm_eps)
    shared = laguna._swiglu(h, p["shared_gate"][i], p["shared_up"][i],
                            p["shared_down"][i], jnp.float32)
    np.testing.assert_allclose(full - routed, shared, atol=1e-5)


@pytest.mark.parametrize("rows,tile", [(256, 32), (64, 32), (2048, 256),
                                       (8192, 256), (131072, 256)])
def test_row_tile_follows_the_row_count(rows, tile):
    assert moe.row_tile(rows) == tile


def test_stacked_experts_are_read_by_layer(params):
    """`layer=` over the stack equals the layer's own slice."""
    cfg = _cfg()
    p = params["experts"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 5, 64)),
                    jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    for i in (0, 3):
        a, ca = moe.moe_share_mlp(x, p["router"][i], bias, p["w_gate"],
                                  p["w_up"], p["w_down"], cfg.share_args,
                                  jnp.float32, layer=i)
        b, cb = moe.moe_share_mlp(x, p["router"][i], bias, p["w_gate"][i],
                                  p["w_up"][i], p["w_down"][i],
                                  cfg.share_args, jnp.float32)
        np.testing.assert_allclose(a, b, atol=1e-6)
        assert float(ca["rows_dropped"]) == 0 == float(cb["rows_dropped"])
        assert float(ca["experts_touched"]) == float(cb["experts_touched"])


# -- llama is untouched ------------------------------------------------------------

#: sha256[:16] of the toy llama engine's lowered programs, taken on the
#: PARENT commit of ISSUE 34 (7f762ce) with this same code: the family seam
#: and the kernels' window argument leave llama's programs as they were
PARENT_PROGRAMS = {
    "decode/xla": "aa40fbb33f2cce94", "prefill/xla": "04d3af9b894b5298",
    "cont/xla": "3146cb01c342339d", "decode/flash": "1d6dadf36dad38f0",
    "prefill/flash": "ab3e02c0588c61c6", "cont/flash": "d96f565218edd562",
}


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_llamas_lowered_programs_are_the_parents(impl, monkeypatch):
    monkeypatch.undo()      # the parent's hashes were taken at block 512
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    eng = LLMEngine(llama.init(jax.random.key(0), cfg), cfg, n_slots=4,
                    max_len=64, buckets=(16, 32), decode_chunk=4,
                    quantize="int8", kv_quantize="int8",
                    decode_attention_impl=impl, prefill_attention_impl=impl,
                    prefix_cache=True)
    st = (eng.params, eng.cache, eng.lengths, eng.last_tokens, eng.samp,
          eng.rng_key)
    ek, ev = eng._extract_fn(16)(eng.cache, 0)
    texts = {
        "decode": jax.jit(functools.partial(
            eng._decode, steps=4, span=64)).lower(
                *st, jnp.zeros((4,), bool)).as_text(),
        "prefill": jax.jit(eng._prefill).lower(
            *st, jnp.zeros((2, 24), jnp.int32)).as_text(),
        "cont": jax.jit(eng._prefill_cont).lower(
            *st, jnp.zeros((1, 24), jnp.int32), ek, ev).as_text()}
    eng.close()
    for name, text in texts.items():
        assert (hashlib.sha256(text.encode()).hexdigest()[:16]
                == PARENT_PROGRAMS[f"{name}/{impl}"]), name


def test_serving_example_config_surface():
    """examples/laguna-xs2-serving-isvc.yaml: every config key is a real
    LLMModel knob, its `model:` block builds the configuration the
    benchmark's cell serves, and the documented values construct an
    LLMModel cleanly."""
    import inspect
    import pathlib

    import yaml

    from kubeflow_tpu.serving.llm_runtime import LLMModel

    path = pathlib.Path(ROOT) / "examples" / "laguna-xs2-serving-isvc.yaml"
    spec = yaml.safe_load(path.read_text())
    model = spec["spec"]["predictor"]["model"]
    assert model["modelFormat"] == "laguna"
    params = inspect.signature(LLMModel.__init__).parameters
    assert not set(model["config"]) - set(params)
    LLMModel("example", family="laguna", **model["config"])
    mine = laguna.LagunaConfig(**model["config"]["model"])
    theirs = laguna.LagunaConfig(**{k: PUBLISHED[k] for k in KEYS})
    assert mine == theirs
    served = PUBLISHED["system"]["config"]     # what the cell serves
    engine = {k: v for k, v in model["config"].items() if k != "model"}
    assert engine == {k: served[k] for k in engine}


def test_warm_chain_compiles_the_continuation_pairs_at_warmup():
    """`warm_chain` (an engine option, whatever the family): after
    warmup() a prompt longer than the largest bucket meets no cold
    program; `prefill_wave_max` caps the (bucket, width) menu."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    eng = LLMEngine(llama.init(jax.random.key(0), cfg), cfg, n_slots=4,
                    max_len=48, buckets=(8, 16), decode_chunk=2,
                    warm_chain=True, prefill_wave_max=2)
    eng.warmup()
    assert set(eng._cont_fns) == {(16, 8, 1), (16, 16, 1), (32, 8, 1),
                                  (32, 16, 1)}
    assert set(eng._extract_fns) == {16, 32}
    assert set(eng._prefill_fns) == {(8, 1), (8, 2), (16, 1), (16, 2)}
    before = (dict(eng._cont_fns), dict(eng._extract_fns))
    out = eng.generate(list(range(1, 38)), max_new_tokens=4)
    assert len(out) == 4
    assert (dict(eng._cont_fns), dict(eng._extract_fns)) == before
    eng.close()
