"""Paged KV + continuous batching (ISSUE 19 tentpole): the radix block
pool is the ONLY owner of KV memory — per-slot block tables index pool
blocks, admission is a free-block reservation with radix eviction as the
valve, and recompute-from-prefix after a forced eviction is byte-exact.

The fast lane here pins the CONTRACT cheaply: BlockPool accounting
invariants (jax arrays, no engine), constructor/config validation, the
kv_layout seam, and ONE end-to-end forced-eviction recompute parity.
Heavy combos — int8 + chunked prefill eviction parity, seeded-sampling
parity, oversubscribed admission with held retries — ride the slow lane.
"""

import os

import numpy as np
import pytest

import jax

from kubeflow_tpu.kvcache.pool import BlockPool
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine
from kubeflow_tpu.serving.paged import PagedLLMEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    return params, cfg


# -- BlockPool accounting (no engine) -----------------------------------------


def make_pool(n_blocks=8, **kw):
    kw.setdefault("n_layers", 2)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("dtype", "float32")
    return BlockPool(n_blocks=n_blocks, **kw)


def test_pool_alloc_is_all_or_nothing():
    pool = make_pool(n_blocks=8)            # 7 usable (block 0 = trash)
    assert pool.capacity_blocks == 7
    ids = pool.alloc(5)
    assert ids is not None and len(ids) == 5
    assert 0 not in ids                     # the trash sentinel never leaves
    assert pool.free_blocks == 2
    # a request that does not fit changes NOTHING (no partial grants)
    assert pool.alloc(3) is None
    assert pool.free_blocks == 2
    assert pool.stats()["alloc_failures"] == 1
    pool.check_invariants()


def test_pool_refcount_and_free_list_roundtrip():
    pool = make_pool(n_blocks=6)
    ids = pool.alloc(3)
    pool.ref(ids[:2])                       # shared with the radix cache
    assert pool.refcount(ids[0]) == 2
    assert pool.deref(ids) == 1             # only the unshared block frees
    assert pool.free_blocks == 3
    assert pool.deref(ids[:2]) == 2         # second owner lets go
    assert pool.free_blocks == 5
    with pytest.raises(ValueError):
        pool.ref([0])                       # the trash block is untouchable
    with pytest.raises(ValueError):
        pool.deref(ids[:1])                 # double-free is a bug, loudly
    pool.check_invariants()


def test_pool_watermark_tracks_occupancy():
    pool = make_pool(n_blocks=9)            # 8 usable
    assert pool.watermark_frac == 1.0       # free fraction: 1.0 = empty
    ids = pool.alloc(6)
    assert pool.watermark_frac == pytest.approx(0.25)
    s = pool.stats()
    assert s["free_blocks"] == 2 and s["used_blocks"] == 6
    assert s["pool_blocks"] == 8
    pool.deref(ids)
    assert pool.watermark_frac == 1.0


# -- constructor / config validation ------------------------------------------


def test_paged_ctor_validation(tiny):
    params, cfg = tiny
    with pytest.raises(ValueError, match="slab"):
        PagedLLMEngine(params, cfg, mesh=object())
    with pytest.raises(ValueError, match="divide"):
        # bt = gcd(buckets) = 8 does not divide max_len
        PagedLLMEngine(params, cfg, n_slots=2, max_len=36, buckets=(8, 16))
    with pytest.raises(ValueError, match="pool_blocks"):
        # pool smaller than one slot's table: a max-length request could
        # never be funded and would hold forever
        PagedLLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                       pool_blocks=3)


@pytest.mark.parametrize("config,want", [
    ({}, "slab"),                                   # unset resolves slab
    ({"kv_layout": "slab"}, "slab"),
    ({"kv_layout": "paged"}, "paged"),
    ({"kv_layout": "bogus"}, "kv_layout"),
    ({"kv_layout": "paged", "parallel": {"stage": 2}}, "stage"),
    ({"kv_layout": "paged", "mesh": {"tensor": 2}}, "mesh"),
    ({"kv_layout": "paged", "disaggregated": True}, "disaggregated"),
])
def test_runtime_kv_layout_seam(config, want):
    """The layout is the configuration's `kv_layout` and nothing else; a
    combination the paged engine cannot serve is refused by name."""
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    if want in ("slab", "paged"):
        assert LLMModel("m", **config)._kv_layout == want
    else:
        with pytest.raises(ValueError, match=want):
            LLMModel("m", **config)


def test_stage_sharded_rejects_paged(tiny):
    from kubeflow_tpu.serving.multichip import StageShardedEngine

    params, cfg = tiny
    with pytest.raises(ValueError, match="paged"):
        StageShardedEngine(params, cfg, stage=2, kv_layout="paged",
                           n_slots=2, max_len=32, buckets=(8,))


# -- forced-eviction recompute parity (the property, fast shape) --------------

PROMPT = list(range(1, 14))                  # 13 tokens → 1 full block + tail


def test_forced_eviction_recompute_is_byte_identical(tiny):
    """The oversubscription valve: evicting banked radix blocks must
    cost only recompute, never correctness — the same prompt after a
    forced full eviction reproduces the never-evicted output byte for
    byte, and the pool's refcounts balance through the whole cycle."""
    params, cfg = tiny
    slab = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                     decode_chunk=4)
    want = slab.generate(PROMPT, 6)
    slab.close()

    eng = PagedLLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                         decode_chunk=4, prefix_cache=True)
    try:
        assert eng.generate(PROMPT, 6) == want          # banks the prefix
        assert eng.metrics()["prefix_misses"] == 1
        evicted = eng.kvcache.evict(10**6)              # forced: evict ALL
        assert evicted > 0
        eng._flush_derefs()
        assert eng._pool.free_blocks == eng._pool.capacity_blocks
        assert eng.generate(PROMPT, 6) == want          # recompute path
        assert eng.generate(PROMPT, 6) == want          # re-banked hit path
        assert eng.metrics()["prefix_hits"] >= 1
        eng._pool.check_invariants()
        # every generation released its slot blocks; only banked radix
        # blocks still hold pool references
        m = eng.metrics()["kv_pool"]
        assert m["used_blocks"] == eng.metrics()["prefix_cache"]["blocks"]
        assert m["alloc_failures"] == 0 and eng._held == []
    finally:
        eng.close()


# -- cached prefixes fund themselves (ISSUE 20 bugfix) ------------------------


def test_admission_reserves_only_uncached_suffix(tiny):
    """Funding re-probes the radix cache and reserves blocks only for
    the uncached suffix: a request whose 2-block prefix is banked
    admits with ONE fresh block even when full-need funding would have
    failed (and would have evicted the banked prefix via the valve).
    alloc_failures == 0 is the proof the valve never fired."""
    params, cfg = tiny
    prompt = list(range(1, 18))              # 17 tokens → 2-block prefix
    slab = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                     decode_chunk=4)
    want = slab.generate(prompt, 6)
    slab.close()

    # 7 blocks total: banked prefix 2 + blocker 4 leaves ONE free —
    # enough for the suffix (need 3 - cached 2), not for full need 3
    eng = PagedLLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                         decode_chunk=4, prefix_cache=True, pool_blocks=7)
    try:
        assert eng.generate(prompt, 6) == want   # banks the 2-block prefix
        blocker = eng.submit([50, 51, 52, 53, 54, 55, 56], 25)
        eng.step()                               # blocker takes 4 blocks
        assert eng._pool.free_blocks == 1
        rid = eng.submit(list(prompt), 6)
        eng.step()                               # admission: must fund NOW
        assert eng._held == []                   # not held — suffix-funded
        assert eng._pool.free_blocks == 0
        for _ in range(200):
            if eng.is_done(rid):
                break
            eng.step()
        assert eng.result(rid) == want
        m = eng.metrics()
        assert m["prefix_hits"] == 1             # the reuse actually rode
        assert m["kv_pool"]["alloc_failures"] == 0   # valve never fired
        eng._pool.check_invariants()
        eng.cancel(blocker)
    finally:
        eng.close()


# -- heavy combos: slow lane --------------------------------------------------


@pytest.mark.slow
def test_eviction_parity_int8_and_chunked_prefill(tiny):
    """The property again under the two mechanisms that touch the block
    write path hardest: int8 KV (per-token scales ride the pool) and
    chunked prefill (the splice-then-continue path) — forced eviction
    between runs, byte parity throughout."""
    params, cfg = tiny
    long_prompt = list(range(1, 21))         # 20 tokens > bucket 8: chunked
    slab = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                     decode_chunk=4, kv_quantize="int8")
    want_long = slab.generate(long_prompt, 6)
    want_short = slab.generate(PROMPT, 6)
    slab.close()

    eng = PagedLLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                         decode_chunk=4, kv_quantize="int8",
                         prefix_cache=True)
    try:
        for _ in range(2):                   # miss+bank, then radix hit
            assert eng.generate(long_prompt, 6) == want_long
            assert eng.generate(PROMPT, 6) == want_short
            assert eng.kvcache.evict(10**6) >= 0
            eng._flush_derefs()
            eng._pool.check_invariants()
        assert eng._pool.free_blocks == eng._pool.capacity_blocks
    finally:
        eng.close()


@pytest.mark.slow
def test_oversubscribed_admission_no_lost_or_duplicated_tokens(tiny):
    """More concurrent streams than the pool can fund at once: admission
    holds what it cannot fund, eviction makes room, every request still
    delivers exactly its tokens (no losses, no duplicates) and matches
    the slab engine byte for byte."""
    params, cfg = tiny
    prompts = [[10 + i, 20 + i, 30 + i, 40 + i] for i in range(8)]
    slab = LLMEngine(params, cfg, n_slots=4, max_len=32, buckets=(8,),
                     decode_chunk=4)
    want = [slab.generate(p, 6) for p in prompts]
    slab.close()

    # pool = 6 blocks but 4 slots x 4-block tables could demand 16:
    # admission MUST oversubscribe through held retries
    eng = PagedLLMEngine(params, cfg, n_slots=4, max_len=32, buckets=(8,),
                         decode_chunk=4, prefix_cache=True, pool_blocks=6)
    try:
        rids = [eng.submit(p, 6) for p in prompts]
        for _ in range(600):
            if all(eng.is_done(r) for r in rids):
                break
            eng.step()
        outs = [eng.result(r) for r in rids]
        assert outs == want
        assert all(len(o) == 6 for o in outs)
        assert eng._held == []
        eng._pool.check_invariants()
        # the squeeze actually happened: funding failed at least once
        assert eng.metrics()["kv_pool"]["alloc_failures"] > 0
    finally:
        eng.close()


@pytest.mark.slow
def test_held_retry_reprobes_radix_and_keeps_prefix_pinned(tiny):
    """The held-prefill retry path end to end: a request held under
    pressure (a) does NOT let the eviction valve eat the banked prefix
    it is waiting to reuse (the match pin rides through the valve), and
    (b) re-probes the radix cache on the retry that finally funds — so
    it admits on the uncached suffix and the reuse still counts as a
    hit."""
    params, cfg = tiny
    prompt = list(range(1, 18))              # 17 tokens → 2-block prefix
    slab = LLMEngine(params, cfg, n_slots=3, max_len=32, buckets=(8,),
                     decode_chunk=4)
    want = slab.generate(prompt, 15)
    slab.close()

    eng = PagedLLMEngine(params, cfg, n_slots=3, max_len=32, buckets=(8,),
                         decode_chunk=4, prefix_cache=True, pool_blocks=7)
    try:
        eng.generate(prompt, 6)              # banks 2 blocks → 5 free
        blocker = eng.submit([50, 51, 52, 53, 54, 55, 56], 25)  # 4 blocks
        eng.step()
        assert eng._pool.free_blocks == 1
        # need 4, cached 2 → alloc_need 2 > 1 free: held. The valve must
        # NOT evict the pinned prefix while deciding to hold.
        rid = eng.submit(list(prompt), 15)
        eng.step()
        assert len(eng._held) == 1
        assert eng.metrics()["prefix_cache"]["blocks"] == 2   # survived
        for _ in range(600):                 # blocker drains → retry funds
            if eng.is_done(rid):
                break
            eng.step()
        assert eng.result(rid) == want
        assert eng.metrics()["prefix_hits"] == 1   # retry re-probed
        assert eng._held == []
        eng._pool.check_invariants()
        eng.cancel(blocker)
    finally:
        eng.close()


@pytest.mark.slow
def test_seeded_sampling_parity_slab_vs_paged(tiny):
    """Seeded temperature sampling derives keys from (seed, position)
    alone — the KV layout must be invisible to the sampled stream."""
    params, cfg = tiny
    kw = dict(n_slots=2, max_len=32, buckets=(8,), decode_chunk=4)
    slab = LLMEngine(params, cfg, **kw)
    want = slab.generate(PROMPT, 8, temperature=0.8, seed=123)
    slab.close()
    eng = PagedLLMEngine(params, cfg, **kw)
    try:
        assert eng.generate(PROMPT, 8, temperature=0.8, seed=123) == want
    finally:
        eng.close()
