"""Differential gauntlet for the Pallas flash chunked-prefill kernel
(ISSUE 20, ops/flash_prefill.py) — interpret-mode on the CPU lane
(FORCE_INTERPRET, the flash_decode pattern), so every claim is
byte-level testable without hardware:

- op level: kernel-vs-mha parity across GQA ratios (1:1, 4:1, 8:1),
  int8 + f32 KV, q_offset ∈ {0, bucket-edge continuation, radix-hit
  starts}, ragged chunk lengths that pad both axes, multi-q-block and
  multi-kv-block shapes, and paged block-table indirection with a
  scrambled pool — all against llama.prefill_attention's XLA reference
  on identical inputs;
- selection policy: explicit config, else the decode kernel's rule (xla
  on this CPU box);
- engine level: a warmed xla-vs-flash engine pair (int8 KV, f32 model,
  radix prefix cache ON) produces byte-identical greedy AND seeded
  outputs across full prefills, prefix-hit continuations, and chunked
  long prompts. Heavy combos (paged engine pair, big offsets) ride the
  slow lane. No cell of the benchmark reads this kernel alone yet.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import flash_prefill


@pytest.fixture(autouse=True)
def _interpret():
    flash_prefill.FORCE_INTERPRET = True
    yield
    flash_prefill.FORCE_INTERPRET = False


def _cfg(nh, nkv, hd, dtype=jnp.float32):
    return llama.LlamaConfig(vocab_size=64, d_model=nh * hd, n_layers=1,
                             n_heads=nh, n_kv_heads=nkv, d_ff=32,
                             max_seq_len=512, dtype=dtype)


def _inputs(nh, nkv, s, t, hd, quantized, *, b=1, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(b, t, nkv, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(b, t, nkv, hd)), jnp.float32)
    if quantized:
        kq, ks = llama.quantize_kv(kf)
        vq, vs = llama.quantize_kv(vf)
        return q, kq, vq, ks, vs
    return q, kf, vf, None, None


def _both(cfg, q, k, v, ks, vs, q_offset, tables=None):
    want = llama.prefill_attention(cfg, q, k, v, ks, vs,
                                   q_offset=q_offset, impl="xla",
                                   tables=tables)
    got = llama.prefill_attention(cfg, q, k, v, ks, vs,
                                  q_offset=q_offset, impl="flash",
                                  tables=tables)
    return np.asarray(want, np.float32), np.asarray(got, np.float32)


def _close(want, got, tol=1e-5):
    err = np.abs(want - got).max()
    den = max(np.abs(want).max(), 1e-6)
    assert err / den < tol, (err, den)


# -- op level -----------------------------------------------------------------

# GQA 1:1 / 4:1 / 8:1 × {f32, int8} KV × offset shapes: full prefill
# (q_offset=0, T=S), bucket-edge continuation (T = p + S), radix-hit
# starts mid-span, ragged chunks that pad the q axis, and KV spans that
# pad the KV axis — the shapes the engine's (p, t) wave grouping emits.
CASES = [
    # nh, nkv,  s,   t, q_offset, quantized
    (4,    4,  16,  16,      0, False),   # full prefill, 1:1
    (8,    1,   8,   8,      0, False),   # full prefill, 8:1
    (8,    2,   8,  16,      8, False),   # continuation after p=8
    (8,    2,  13,  45,     32, False),   # ragged radix-hit: pads q+kv
    (8,    2,   1,  33,     32, False),   # single-row chunk
    (4,    4,  16,  16,      0, True),    # int8, full prefill
    (8,    1,  13,  45,     32, True),    # int8, ragged, 8:1
]


@pytest.mark.parametrize("nh,nkv,s,t,q_offset,quantized", CASES)
def test_kernel_matches_mha(nh, nkv, s, t, q_offset, quantized):
    hd = 16
    cfg = _cfg(nh, nkv, hd)
    q, k, v, ks, vs = _inputs(nh, nkv, s, t, hd, quantized, b=2)
    want, got = _both(cfg, q, k, v, ks, vs, q_offset)
    assert want.shape == got.shape == (2, s, nh, hd)
    _close(want, got)


def test_multi_block_q_and_kv():
    """Forced small blocks: several q blocks AND several sequential KV
    blocks, so the online-softmax carry and the causal block skip both
    engage (the default blocks would fit toy dims in one step)."""
    nh, nkv, hd, s, t, p = 8, 2, 16, 72, 104, 32
    cfg = _cfg(nh, nkv, hd)
    q, k, v, _, _ = _inputs(nh, nkv, s, t, hd, False)
    want = llama.prefill_attention(cfg, q, k, v, q_offset=p, impl="xla")
    got = flash_prefill.flash_prefill_attention(
        q, k, v, q_offset=p, block_q=16, block_kv=16)
    _close(np.asarray(want, np.float32), np.asarray(got, np.float32))


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_tables_match_slab(quantized):
    """Block-table indirection: a scrambled pool whose tables
    reconstruct the slab span must match the contiguous-slab kernel
    run AND the XLA gather twin bit-for-bit in ordering semantics."""
    nh, nkv, hd, s, bt, nb = 8, 2, 16, 8, 16, 3
    b, t = 2, bt * nb
    p = t - s
    cfg = _cfg(nh, nkv, hd)
    q, k, v, ks, vs = _inputs(nh, nkv, s, t, hd, quantized, b=b)

    # scatter the slab's blocks into a larger pool at permuted slots
    rng = np.random.default_rng(3)
    n_pool = b * nb + 5
    perm = rng.permutation(n_pool - 1)[:b * nb] + 1   # block 0 reserved
    pool_k = np.zeros((n_pool, bt, nkv, hd), np.asarray(k).dtype)
    pool_v = np.zeros_like(pool_k)
    pool_ks = np.zeros((n_pool, bt, nkv), np.float32)
    pool_vs = np.zeros_like(pool_ks)
    tables = np.zeros((b, nb), np.int32)
    for bi in range(b):
        for j in range(nb):
            bid = int(perm[bi * nb + j])
            pool_k[bid] = np.asarray(k)[bi, j * bt:(j + 1) * bt]
            pool_v[bid] = np.asarray(v)[bi, j * bt:(j + 1) * bt]
            if quantized:
                pool_ks[bid] = np.asarray(ks)[bi, j * bt:(j + 1) * bt]
                pool_vs[bid] = np.asarray(vs)[bi, j * bt:(j + 1) * bt]
            tables[bi, j] = bid
    pk, pv = jnp.asarray(pool_k), jnp.asarray(pool_v)
    pks = jnp.asarray(pool_ks) if quantized else None
    pvs = jnp.asarray(pool_vs) if quantized else None
    tbl = jnp.asarray(tables)

    want, got = _both(cfg, q, pk, pv, pks, pvs, p, tables=tbl)
    _close(want, got)
    # and the paged kernel must agree with the slab kernel on the same
    # logical span
    slab = llama.prefill_attention(cfg, q, k, v, ks, vs, q_offset=p,
                                   impl="flash")
    _close(np.asarray(slab, np.float32), got)


def test_fully_masked_pad_rows_are_finite():
    """Chunk pad rows (s not a block multiple) compute garbage the
    wrapper slices off — but the REAL rows next to them must stay exact,
    and nothing may go NaN even when a whole KV block is causally
    skipped."""
    nh, nkv, hd = 4, 2, 16
    cfg = _cfg(nh, nkv, hd)
    q, k, v, _, _ = _inputs(nh, nkv, 3, 40, hd, False)
    want, got = _both(cfg, q, k, v, None, None, 16)
    assert np.isfinite(got).all()
    _close(want, got)


def test_q_offset_must_be_static_and_nonnegative():
    q, k, v, _, _ = _inputs(4, 2, 4, 8, 16, False)
    with pytest.raises(ValueError):
        flash_prefill.flash_prefill_attention(q, k, v, q_offset=-1)
    with pytest.raises(ValueError):
        # GQA ratio must divide
        flash_prefill.flash_prefill_attention(q[:, :, :3], k, v)


# -- selection policy ---------------------------------------------------------

@pytest.mark.parametrize(
    "configured,platform,head_dim,n_kv_heads,want", [
        ("auto", "cpu", 8, 4, "xla"),        # auto off the chip
        ("xla", "cpu", 8, 4, "xla"),         # an explicit value wins
        ("flash", "cpu", 8, 4, "flash"),
        ("auto", "tpu", 128, 8, "flash"),    # the serving cell's layout
        ("auto", "tpu", 64, 8, "xla"),       # the decode rule: one policy
        ("xla", "tpu", 128, 8, "xla"),
        ("flash", "tpu", 64, 8, ValueError("head_dim 64")),
    ])
def test_resolve_impl_policy(monkeypatch, configured, platform, head_dim,
                             n_kv_heads, want):
    from kubeflow_tpu.ops import pallas_compat

    monkeypatch.setattr(pallas_compat, "target_platform", lambda: platform)
    if isinstance(want, Exception):
        with pytest.raises(type(want), match=str(want)):
            flash_prefill.resolve_impl(configured, head_dim=head_dim,
                                       n_kv_heads=n_kv_heads)
    else:
        assert flash_prefill.resolve_impl(
            configured, head_dim=head_dim, n_kv_heads=n_kv_heads) == want


def test_config_validates_impl():
    with pytest.raises(ValueError):
        dataclasses.replace(llama.LlamaConfig.tiny(),
                            prefill_attention_impl="bogus")


# -- engine level -------------------------------------------------------------

ENG_KW = dict(n_slots=2, max_len=48, buckets=(8,), decode_chunk=2,
              prefix_cache=True, kv_quantize="int8")


@pytest.fixture(scope="module")
def engine_pair():
    """One warmed xla/flash PREFILL engine pair at toy dims (f32 model,
    int8 KV, radix prefix cache on — continuation programs with real
    q_offsets are the kernel's whole point). Module-scoped: the engine
    tests share the compiles."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(jax.random.key(0), cfg)
    from kubeflow_tpu.serving.llm import LLMEngine

    ex = LLMEngine(params, cfg, prefill_attention_impl="xla", **ENG_KW)
    ef = LLMEngine(params, cfg, prefill_attention_impl="flash", **ENG_KW)
    # no warmup(): the tests below touch every prefill body they assert
    # on, and lazy compiles keep the fast lane inside its budget —
    # warming BOTH engines' full menus would double the wall for zero
    # extra coverage
    yield ex, ef
    ex.close()
    ef.close()


def test_engine_reports_resolved_impl(engine_pair):
    ex, ef = engine_pair
    assert ex.metrics()["prefill_attention_impl"] == "xla"
    assert ef.metrics()["prefill_attention_impl"] == "flash"
    # the decode seam is untouched by the prefill pin
    assert ex.metrics()["decode_attention_impl"] \
        == ef.metrics()["decode_attention_impl"]


def test_engine_greedy_byte_parity(engine_pair):
    """Full prefills, a prefix-hit continuation (the repeated shared
    prefix), and a chunked long prompt (17 > bucket 8) — every prefill
    body the engine compiles."""
    ex, ef = engine_pair
    shared = [5, 6, 7, 8, 9, 10, 11]
    for p in ([1, 2, 3], shared, shared[:4] + [20, 21], [3] * 17):
        want = ex.generate(list(p), 8)
        got = ef.generate(list(p), 8)
        assert got == want, (p, got, want)


def test_engine_seeded_byte_parity(engine_pair):
    ex, ef = engine_pair
    for seed in (7, 12345):
        for p in ([3, 1, 4, 1, 5], [9] * 12):
            want = ex.generate(list(p), 6, temperature=0.9, seed=seed)
            got = ef.generate(list(p), 6, temperature=0.9, seed=seed)
            assert got == want, (p, seed, got, want)


def test_engine_prefix_hit_parity(engine_pair):
    """Warm the radix cache, then hit it: the continuation program runs
    the kernel at a REAL prefix offset on both engines."""
    ex, ef = engine_pair
    prefix = [11, 12, 13, 14, 15, 16, 17, 18]   # one full block
    for eng in (ex, ef):
        eng.generate(list(prefix), 4)           # bank the prefix
    hx = ex.metrics()["prefix_cache"]["hits"]
    want = ex.generate(list(prefix) + [30], 8)
    got = ef.generate(list(prefix) + [30], 8)
    assert got == want
    assert ex.metrics()["prefix_cache"]["hits"] > hx   # it WAS a hit


# -- slow lane ----------------------------------------------------------------

@pytest.mark.slow
def test_paged_engine_pair_parity():
    """PagedLLMEngine xla-vs-flash prefill: the kernel's block-table
    mode under a real oversubscribed pool, greedy + seeded, with the
    radix cache splicing shared blocks."""
    from kubeflow_tpu.serving.paged import PagedLLMEngine

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(jax.random.key(0), cfg)
    kw = dict(ENG_KW)
    engs = [PagedLLMEngine(params, cfg, prefill_attention_impl=i, **kw)
            for i in ("xla", "flash")]
    try:
        shared = [5, 6, 7, 8, 9, 10, 11, 12]
        for p in (shared, shared + [30], [3] * 17, [1, 2]):
            want = engs[0].generate(list(p), 8)
            got = engs[1].generate(list(p), 8)
            assert got == want, (p, got, want)
        want = engs[0].generate([9] * 10, 6, temperature=0.8, seed=5)
        got = engs[1].generate([9] * 10, 6, temperature=0.8, seed=5)
        assert got == want
    finally:
        for e in engs:
            e.close()


@pytest.mark.slow
@pytest.mark.parametrize("nh,nkv", [(8, 8), (8, 4), (8, 1)])
@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_gauntlet_offsets(nh, nkv, quantized):
    """Offset sweep per GQA ratio: every (s, p) shape class the wave
    grouping can emit, forced-small blocks included."""
    hd = 16
    cfg = _cfg(nh, nkv, hd)
    for s, t, p in ((32, 32, 0), (8, 16, 8), (16, 80, 64),
                    (13, 77, 64), (1, 129, 128)):
        q, k, v, ks, vs = _inputs(nh, nkv, s, t, hd, quantized, b=2,
                                  seed=s)
        want, got = _both(cfg, q, k, v, ks, vs, p)
        _close(want, got)
