"""openPangu Ultra MoE as a served family (ISSUE 38), at a toy size on the
CPU: the weights and the forward pass against the benchmark's plain
reference, prefill then decode through the latent slab (chained
continuation chunks, the absorbed decode), the absorbed decode kernel in
the interpreter against expanded attention, the expert-parallel share, the
planted faults, and what the family refuses by name.

The toy keeps every kind of the cell: a dense layer ahead of expert layers,
a router over 16 experts of which a rank holds 4, latent rows of 32 + 8
padded to a lane tile, sandwich norms, float32 so that a gap is the code's
and not rounding's."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import pangu_ultra_moe as pg
from kubeflow_tpu.obs.metrics import render_metrics
from kubeflow_tpu.ops import flash_pallas, mla_decode
from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.serving.llm import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import pangu_ultra_moe as ref  # noqa: E402

TOY = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "toy_pangu.json")))["config"]
PUBLISHED = json.load(open(os.path.join(
    ROOT, "benchmark", "configs",
    "openpangu-ultra-moe-718b-serve-ep32.json")))
#: the reference's configuration: the cell's file under the toy's sizes
RCFG = {**PUBLISHED, **{k: v for k, v in TOY.items() if k != "system"},
        "num_hidden_layers": 3}
KEYS = PUBLISHED["system"]["model_keys"]
SEED = 7


def _cfg(**kw):
    return pg.PanguUltraMoEConfig(**{k: RCFG[k] for k in KEYS},
                                  n_router_experts=16, dtype=jnp.float32,
                                  **kw)


@pytest.fixture(scope="module")
def params():
    return pg.init(jax.random.key(SEED), _cfg())


def _tokens(n, seed=3, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, RCFG["vocab_size"], (batch, n)), jnp.int32)


def _ref_logits(toks, fault=None, lower=None):
    return np.asarray(ref.logits(SEED, toks, RCFG, lower=lower, fault=fault))


# -- the plain forward pass ---------------------------------------------------

def test_weights_are_the_references_bit_for_bit(params):
    """The program draws what the reference draws: W_UKV whole, held split
    into W_UK and W_UV; the experts held; the post-norms' gain."""
    w = ref.draw_layer(SEED, RCFG, 1)
    nope = RCFG["qk_nope_head_dim"]
    ukv = np.asarray(w["attn"]["w_ukv"]).reshape(
        RCFG["kv_lora_rank"], RCFG["num_attention_heads"], -1)
    np.testing.assert_array_equal(params["attn"]["w_uk"][1], ukv[..., :nope])
    np.testing.assert_array_equal(params["attn"]["w_uv"][1], ukv[..., nope:])
    np.testing.assert_array_equal(params["experts"]["w_gate"][0],
                                  w["ffn"]["w_gate"])
    np.testing.assert_array_equal(params["experts"]["router"][0],
                                  w["ffn"]["router"])
    assert params["experts"]["router"].shape[-1] == 16
    np.testing.assert_allclose(params["attn"]["post_attn_norm"],
                               1 / np.sqrt(61), rtol=1e-6)


def test_prefill_logits_are_the_references(params):
    toks = _tokens(24)
    got = np.asarray(pg.apply(params, toks, _cfg()))
    np.testing.assert_allclose(got, _ref_logits(toks), atol=2e-4)


def _serve(params, cfg, toks, chunks, steps):
    """Prefill the first chunk, continue chunk by chunk against the slab,
    then decode `steps` tokens (teacher-forced): the logits of every
    position after the first chunk's last."""
    b = toks.shape[0]
    cache = pg.init_cache(cfg, b, 64)
    first = chunks[0]
    lg, rows, _ = pg.prefill(params, toks[:, :first], cfg)
    out = [lg[:, -1]]
    for i in range(b):
        cache = pg.cache_write(cache, i, 0, first, rows[:, i], {})
    at = first
    for n in chunks[1:]:
        kp = jnp.concatenate([pg.extract_prefix(cfg, cache, i, at)[0]
                              for i in range(b)], axis=1)
        lg, rows, _ = pg.prefill_continue(params, toks[:, at:at + n], kp,
                                          {}, cfg)
        out.append(lg[:, -1])
        for i in range(b):
            cache = pg.cache_write(cache, i, at, n, rows[:, i], {})
        at += n
    lengths = jnp.full((b,), at, jnp.int32)
    for t in range(steps):
        lg, cache = pg.decode_step(params, toks[:, at + t], cache, lengths,
                                   cfg, active=jnp.ones((b,), bool))
        out.append(lg)
        lengths = lengths + 1
    return np.stack([np.asarray(o) for o in out], 1), cache


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_prefill_chain_then_decode_through_the_latent_slab(params, impl,
                                                           monkeypatch):
    """Chunks of 16, 16 (at q_offset 16) and 8, then 6 decode steps: every
    position's logits against the reference's one pass over the whole
    sequence."""
    monkeypatch.setattr(mla_decode, "FORCE_INTERPRET", True)
    monkeypatch.setattr(mla_decode, "DEFAULT_BLOCK_KV", 16)
    monkeypatch.setattr(flash_pallas, "FORCE_INTERPRET", True)
    cfg = _cfg(decode_attention_impl=impl,
               prefill_attention_impl="xla")
    toks = _tokens(46)
    got, cache = _serve(params, cfg, toks, (16, 16, 8), 6)
    want = _ref_logits(toks)
    at = [15, 31, 39] + list(range(40, 46))
    np.testing.assert_allclose(got, want[:, at], atol=3e-4)
    # the slab's padding lanes stay zero
    assert not np.asarray(cache["kv"][..., cfg.latent_width:]).any()


def test_flash_prefill_kernel_at_an_offset_matches_mha(monkeypatch):
    """The prefill kernel (q/k of 24 padded to 128 lanes beside values of
    16) for a chunk of 128 rows behind a cached prefix of 128."""
    monkeypatch.setattr(flash_pallas, "FORCE_INTERPRET", True)
    cfg = _cfg()
    k = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(k[0], (1, 128, 4, 24))
    kk = jax.random.normal(k[1], (1, 256, 4, 24))
    v = jax.random.normal(k[2], (1, 256, 4, 16))
    a = pg.prefill_attention(cfg, q, kk, v, 128, "flash")
    b = pg.prefill_attention(cfg, q, kk, v, 128, "xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# -- the absorbed decode kernel ----------------------------------------------

def _expanded(q_nope, q_rope, rows, lengths, w_uk, w_uv, latent, rope):
    """Per head: keys c_kv W_UK beside the shared k_rope, values c_kv
    W_UV, softmax over positions <= length; a dead slot gives zeros."""
    c_kv, k_rope = rows[..., :latent], rows[..., latent:latent + rope]
    k_nope = np.einsum("btc,chd->bthd", c_kv, w_uk)
    v = np.einsum("btc,chd->bthd", c_kv, w_uv)
    s = (np.einsum("bhd,bthd->bht", q_nope, k_nope)
         + np.einsum("bhr,btr->bht", q_rope, k_rope))
    s = s / np.sqrt(q_nope.shape[-1] + rope)
    seen = np.arange(rows.shape[1])[None, None] <= lengths[:, None, None]
    s = np.where(seen, s, -1e30)
    p = np.where(seen, np.exp(s - s.max(-1, keepdims=True)), 0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum("bht,bthd->bhd", p, v)


@pytest.mark.parametrize("span,block", [(96, 32), (64, 16), (96, 128)])
def test_absorbed_decode_kernel_matches_expanded_attention(span, block):
    """5 slots of ragged lengths (one dead, one at the span's last row)
    over a 2-layer slab: the kernel in the interpreter, then W_UV, against
    the expanded form at the same weights."""
    rng = np.random.default_rng(0)
    b, h, nope, latent, rope, width, t = 5, 4, 16, 32, 8, 128, 96
    slab = np.zeros((2, b, t, width), np.float32)
    slab[..., :latent + rope] = rng.standard_normal((2, b, t, latent + rope))
    w_uk = rng.standard_normal((latent, h, nope)) / np.sqrt(latent)
    w_uv = rng.standard_normal((latent, h, nope)) / np.sqrt(latent)
    q_nope = rng.standard_normal((b, h, nope))
    q_rope = rng.standard_normal((b, h, rope))
    lengths = np.array([0, 17, -1, span - 1, 40], np.int32)
    q = np.zeros((b, h, width), np.float32)
    q[..., :latent] = np.einsum("bhd,chd->bhc", q_nope, w_uk)
    q[..., latent:latent + rope] = q_rope
    got = mla_decode.mla_decode_attention(
        jnp.asarray(q), jnp.asarray(slab), jnp.asarray(lengths), layer=1,
        latent=latent, scale=1 / np.sqrt(nope + rope), span=span,
        block_kv=block, interpret=True)
    got = np.einsum("bhc,chd->bhd", np.asarray(got), w_uv)
    want = _expanded(q_nope, q_rope, slab[1, :, :span], lengths, w_uk, w_uv,
                     latent, rope)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert not got[2].any()                        # the dead slot
    xla = mla_decode.mla_decode_xla(
        jnp.asarray(q), jnp.asarray(slab), jnp.asarray(lengths), layer=1,
        latent=latent, scale=1 / np.sqrt(nope + rope), span=span)
    np.testing.assert_allclose(np.einsum("bhc,chd->bhd", np.asarray(xla),
                                         w_uv), want, atol=1e-4)


def test_decode_counts_the_context_rows_it_covered(params):
    cfg = _cfg()
    cache = pg.init_cache(cfg, 3, 64)
    lengths = jnp.asarray([5, 20, 63], jnp.int32)
    _, out = pg.decode_step(params, jnp.asarray([1, 2, 3]), cache, lengths,
                            cfg, active=jnp.asarray([True, False, True]))
    counts = dict(zip((n for n, _ in pg.STEP_COUNTERS),
                      np.asarray(out["counters"])))
    assert counts["mla_context_tokens"] == 3 * (6 + 64)
    assert counts["moe_rows_dropped"] == 0
    # 3 slots x top 2 over 16 experts, the 4 held here: 0..6 rows a layer
    assert 0 <= counts["moe_assignments"] <= 2 * 3 * 2
    assert pg.cache_stats(cache) == {"kv_bytes_latent": 3 * 3 * 64 * 128 * 4}


# -- the expert-parallel share ------------------------------------------------

def test_the_ranks_shares_and_the_shared_expert_add_up_to_the_layer():
    """Four ranks of 4 experts each (16 in all): their routed parts plus
    the shared expert counted once are the uncut layer's FFN."""
    rng = np.random.default_rng(5)
    d, f, e, k, n = 32, 16, 16, 2, 12
    x = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)) / np.sqrt(d),
                         jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((e, d, f)) / np.sqrt(d),
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((e, f, d)) / np.sqrt(f),
                       jnp.float32)
    sg, su = (jnp.asarray(rng.standard_normal((d, f)) / np.sqrt(d),
                          jnp.float32) for _ in range(2))
    sd = jnp.asarray(rng.standard_normal((f, d)) / np.sqrt(f), jnp.float32)
    total = 0.0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part, _ = moe_share_mlp(x, router, jnp.zeros((e,)), gate[held],
                                up[held], down[held],
                                ShareArgs(e, k, 4, 4 * rank, scale=2.5),
                                jnp.float32)
        total = total + part
    total = total + ref.swiglu(x[0], sg, su, sd)
    weight = ref.route({"num_experts_per_tok": k}, x[0], router, 2.5)
    want = sum(ref.swiglu(x[0], gate[j], up[j], down[j])
               * weight[:, j][:, None] for j in range(e))
    want = want + ref.swiglu(x[0], sg, su, sd)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=1e-4)


# -- the planted faults -------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_moves_the_logits(fault):
    toks = _tokens(40, batch=1)
    cfg = dict(RCFG)
    if fault == "rope_theta_1e4":     # the toy's own base is 1e4
        cfg["rope_theta"] = 25600000
    sound = np.asarray(ref.logits(SEED, toks, cfg))
    bad = np.asarray(ref.logits(SEED, toks, cfg, fault=fault))
    assert np.abs(sound - bad).max() > 1e-2
    low = np.asarray(ref.logits(SEED, toks, cfg, lower="fp8"))
    assert 0 < np.abs(sound - low).max() < np.abs(sound - bad).max() * 10


@pytest.mark.parametrize("fault", ["decode_drops_rope",
                                   "decode_query_at_zero"])
def test_each_program_fault_moves_the_decoded_logits(params, fault,
                                                     monkeypatch):
    """The benchmark driver's plants, on the family module: the prefill's
    logits stay, the decoded ones move."""
    from drivers import http_open_loop_latent as drv

    cfg = _cfg()
    toks = _tokens(24)
    sound, _ = _serve(params, cfg, toks, (16,), 6)
    for name in ("latent_decode", "_queries"):
        monkeypatch.setattr(pg, name, getattr(pg, name))
    drv.family.PROGRAM_FAULTS[fault](pg)
    bad, _ = _serve(params, cfg, toks, (16,), 6)
    np.testing.assert_allclose(bad[:, 0], sound[:, 0], atol=1e-5)
    assert np.abs(bad[:, 2:] - sound[:, 2:]).max() > 1e-2


# -- the engine and the InferenceService -------------------------------------

@pytest.fixture(scope="module")
def engine_run(params):
    eng = LLMEngine(params, _cfg(), n_slots=4, max_len=64, buckets=(8, 16),
                    decode_chunk=4, family=pg)
    prompts = [list(map(int, np.random.default_rng(i).integers(0, 128, n)))
               for i, n in enumerate((5, 12, 16, 37, 29))]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    eng._obs_publish()          # what a /metrics scrape runs first
    out = ([(p, eng.result(r)) for p, r in zip(prompts, rids)],
           eng.metrics(), render_metrics())
    eng.close()
    return out


def _gaps(runs):
    t = max(len(p) + len(s) for p, s in runs)
    toks = jnp.asarray([(p + s + [0] * t)[:t] for p, s in runs], jnp.int32)
    r = _ref_logits(toks)
    out = []
    for i, (p, s) in enumerate(runs):
        pos = np.arange(len(p) - 1, len(p) + len(s) - 1)
        out.append(float((r[i, pos].max(-1) - r[i, pos, np.asarray(s)]).max()))
    return out


def test_engine_greedy_tokens_are_the_references(engine_run):
    """Prompts of 5, 12, 16, 37 (a chain of three) and 29 tokens through
    the engine's slab, continuous batching and chained prefill."""
    runs, _, _ = engine_run
    assert [len(s) for _, s in runs] == [12] * 5
    assert max(_gaps(runs)) <= 1e-4


def test_engine_metrics_carry_the_latent_slab_and_the_experts(engine_run):
    _, m, text = engine_run
    assert m["kv_bytes_latent"] == 3 * 4 * 64 * 128 * 4
    assert m["mla_context_tokens"] > 0 and m["moe_rows_dropped"] == 0
    assert m["moe_assignments"] > 0
    assert 'name="kv_bytes_latent"' in text
    assert 'name="mla_context_tokens"' in text


@pytest.mark.parametrize("option,value", [
    ("speculative", 2), ("prefix_cache", True), ("kv_layout", "paged"),
    ("parallel", {"tensor": 2}), ("adapters", {"a": {"checkpoint": "/x"}}),
    ("mesh", {"tensor": 2}), ("lora", {"rank": 4}), ("quantize", "int8"),
    ("disaggregated", True)])
def test_load_refuses_by_name_what_the_family_does_not_serve(option, value):
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    with pytest.raises(ValueError, match=f"does not serve `{option}`"):
        LLMModel("m", family="pangu_ultra_moe", **{option: value})
    LLMModel("m", family="pangu_ultra_moe", kv_layout="slab")


def test_what_the_seam_does_not_serve_raises(params):
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="MTP"):
        pg.verify_step(params, None, None, None, cfg)
    with pytest.raises(NotImplementedError, match="adapters"):
        pg.prefill(params, _tokens(8), cfg, lora={})
    with pytest.raises(ValueError, match="latent cache"):
        pg.init_cache(cfg, 2, 16, kv_quantize="int8")


def test_registry_and_serving_runtime_know_the_family():
    from kubeflow_tpu.models import registry
    from kubeflow_tpu.serving.llm_runtime import FAMILIES

    assert registry.get("pangu_ultra_moe").config_cls is \
        pg.PanguUltraMoEConfig
    assert FAMILIES["pangu_ultra_moe"].module == pg.__name__
