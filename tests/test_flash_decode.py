"""Differential gauntlet for the Pallas flash-decode kernel (ISSUE 15,
ops/flash_decode.py) — the kernel runs via the interpreter on the CPU
mesh (FORCE_INTERPRET, the flash_pallas/quant_matmul pattern), so every
claim here is byte-level testable without hardware:

- op level: kernel-vs-einsum parity across GQA ratios (1:1, 4:1, 8:1),
  int8 + f32 KV, span edge cases (span=1, span=max_len, ragged spans
  across slots), and S_v ∈ {1, 4} verify windows — all against
  llama.decode_attention's XLA reference on identical inputs;
- selection policy: explicit config, else the rule (target platform x
  head_dim x kv heads; xla on this CPU box);
- engine level: a full warmed xla-vs-flash engine pair (int8 KV, f32
  model) produces byte-identical greedy AND seeded outputs — the
  fast-lane core at toy dims; heavy combos (prefix-cache + chunked
  prompts, speculative verify, bf16) ride the slow lane. On the chip the
  kernel is measured by the benchmark's `flash_decode_roofline`.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import flash_decode


@pytest.fixture(autouse=True)
def _interpret():
    flash_decode.FORCE_INTERPRET = True
    yield
    flash_decode.FORCE_INTERPRET = False


def _cfg(nh, nkv, hd, dtype=jnp.float32):
    return llama.LlamaConfig(vocab_size=64, d_model=nh * hd, n_layers=1,
                             n_heads=nh, n_kv_heads=nkv, d_ff=32,
                             max_seq_len=512, dtype=dtype)


def _inputs(nh, nkv, s_v, t, hd, quantized, lengths, *, seed=0,
            dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.normal(size=(b, s_v, nh, hd)), dtype)
    kf = jnp.asarray(rng.normal(size=(b, t, nkv, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(b, t, nkv, hd)), jnp.float32)
    if quantized:
        kq, ks = llama.quantize_kv(kf)
        vq, vs = llama.quantize_kv(vf)
        return q, kq, vq, ks, vs
    return q, kf.astype(dtype), vf.astype(dtype), None, None


def _layer_cache(ck, cv, cks, cvs):
    """One layer's [B, T, kv, hd] rows (scales [B, T, kv]) as the cache
    the serving scan carries: a leading layer axis, scales lane-major."""
    cache = {"k": ck[None], "v": cv[None]}
    if cks is not None:
        cache["k_s"] = jnp.swapaxes(cks, 1, 2)[None]
        cache["v_s"] = jnp.swapaxes(cvs, 1, 2)[None]
    return cache


def _both(cfg, q, ck, cv, cks, cvs, lengths):
    s_v = q.shape[1]
    positions = jnp.asarray(lengths, jnp.int32)[:, None] \
        + jnp.arange(s_v)[None]
    cache = _layer_cache(ck, cv, cks, cvs)
    want = llama.decode_attention(cfg, q, cache, 0, positions, impl="xla")
    got = llama.decode_attention(cfg, q, cache, 0, positions, impl="flash")
    return np.asarray(want, np.float32), np.asarray(got, np.float32)


# GQA 1:1 / 4:1 / 8:1 × {f32, int8} KV × S_v ∈ {1, 4} × span shapes:
# span=1 (a single cached token), span=max_len (lengths reach the last
# row), a multi-block span that pads (300 % 128 != 0), and an exact
# block multiple — every case with RAGGED lengths across slots.
CASES = [
    # nh, nkv, s_v,   t, quantized
    (4,    4,   1,  40, False),
    (8,    2,   1,  40, False),
    (8,    1,   1,  40, False),
    (8,    2,   4,  40, False),
    (8,    2,   1,   1, False),
    (8,    2,   4,   1, True),
    (4,    4,   1,  40, True),
    (8,    1,   4,  40, True),
    (8,    2,   1, 300, True),
    (8,    2,   4, 256, True),
]


@pytest.mark.parametrize("nh,nkv,s_v,t,quantized", CASES)
def test_kernel_matches_einsum(nh, nkv, s_v, t, quantized):
    hd = 16
    cfg = _cfg(nh, nkv, hd)
    rng = np.random.default_rng(1)
    # ragged spans across slots, INCLUDING the span=max_len edge: one
    # slot pinned at t-1 (its S_v window reads the whole span), one at 0
    lengths = rng.integers(0, t, size=(3,))
    lengths[0], lengths[-1] = t - 1, 0
    q, ck, cv, cks, cvs = _inputs(nh, nkv, s_v, t, hd, quantized, lengths)
    want, got = _both(cfg, q, ck, cv, cks, cvs, lengths)
    assert got.shape == want.shape
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want))) or 1.0
    assert err / scale < 1e-5, (nh, nkv, s_v, t, quantized, err, scale)


def test_kernel_bf16_close_to_einsum():
    """bf16 compute (the production model dtype): accumulation order
    differs across the impls, so the bound is bf16-ulp-scale, not
    exact — the byte-exactness claim lives at the ENGINE level where
    argmax/sampling consume the logits."""
    cfg = _cfg(8, 2, 16, dtype=jnp.bfloat16)
    lengths = [17, 3, 39]
    q, ck, cv, cks, cvs = _inputs(8, 2, 2, 40, 16, True, lengths,
                                  dtype=jnp.bfloat16)
    want, got = _both(cfg, q, ck, cv, cks, cvs, lengths)
    assert float(np.max(np.abs(got - want))) < 0.05


def test_rows_mask_independent_slots():
    """Slot i's output must depend only on slot i's span: perturbing KV
    rows BEYOND a slot's visible window (k_pos > lengths + S_v - 1)
    changes nothing — the in-kernel mask, not the caller, enforces it."""
    cfg = _cfg(8, 2, 16)
    lengths = [5, 20, 11]
    q, ck, cv, cks, cvs = _inputs(8, 2, 1, 40, 16, False, lengths)
    _, base = _both(cfg, q, ck, cv, cks, cvs, lengths)
    ck2 = ck.at[0, 10:].set(99.0)   # beyond slot 0's window (5)
    cv2 = cv.at[0, 10:].set(-99.0)
    _, got = _both(cfg, q, ck2, cv2, cks, cvs, lengths)
    np.testing.assert_allclose(got[0], base[0], rtol=0, atol=0)
    # positive control: the same rows INSIDE slot 1's window (20) must
    # change slot 1's output — the mask is per-slot, not global
    ck3 = ck.at[1, 10:].set(99.0)
    _, got3 = _both(cfg, q, ck3, cv, cks, cvs, lengths)
    assert np.any(got3[1] != base[1])


# -- the in-place entry (ISSUE 28): the kernel takes the WHOLE cache -----------
# 8 kv heads of 128 is the serving layout: int8 rows unpack from 32-bit
# words (4 heads to a word), bf16 rows from half-words; 2 kv heads of 16
# with int8 is a layout the word view cannot express (the value path).

LAYERS, SLOTS_ALL, T_CACHE = 3, 5, 320


def _whole_cache(nkv, hd, kv_dtype, *, seed=0, t=T_CACHE):
    """A filled slab cache [L, slots, T, kv, hd] (+ lane-major scales)."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, SLOTS_ALL, t, nkv, hd)
    kf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    if kv_dtype == jnp.int8:
        (kq, ks), (vq, vs) = llama.quantize_kv(kf), llama.quantize_kv(vf)
        return {"k": kq, "v": vq, "k_s": jnp.swapaxes(ks, 2, 3),
                "v_s": jnp.swapaxes(vs, 2, 3)}
    return {"k": kf.astype(kv_dtype), "v": vf.astype(kv_dtype)}


def _paged(cache, bt):
    """The same rows as a block pool [L, N, bt, kv, hd] (+ [L, N, kv, bt])
    behind shuffled tables [slots, T // bt]; block 0 is the trash block."""
    n_layers, slots, t = cache["k"].shape[:3]
    per_slot = t // bt
    order = np.random.default_rng(9).permutation(slots * per_slot)
    tables = jnp.asarray(1 + order.reshape(slots, per_slot), jnp.int32)
    inverse = np.argsort(order)
    pool = {}
    for name, buf in cache.items():
        if name.endswith("_s"):   # [L, slots, kv, T] -> [L, N, kv, bt]
            blocks = buf.reshape(n_layers, slots, -1, per_slot, bt)
            blocks = jnp.moveaxis(blocks, 3, 2).reshape(
                n_layers, slots * per_slot, -1, bt)
        else:
            blocks = buf.reshape(n_layers, slots * per_slot, bt,
                                 *buf.shape[3:])
        blocks = blocks[:, inverse]
        pool[name] = jnp.concatenate(
            [jnp.zeros_like(blocks[:, :1]), blocks], axis=1)
    return pool, tables


def _ragged_lengths(b, span, s_v):
    """Ragged, with two slots at 0 and one at the span's last window."""
    lengths = np.random.default_rng(4).integers(1, span - s_v, size=(b,))
    lengths[0], lengths[-1] = 0, span - s_v
    if b > 2:
        lengths[1] = 0
    return jnp.asarray(lengths, jnp.int32)


def _garbage_elsewhere(cache, layer, slot_start, lengths, s_v, tables=None):
    """Every row no query may see — past each slot's window, in every
    other slot and every other layer — overwritten with large finite
    junk: what is not fetched cannot matter."""
    junk = {name: (jnp.full_like(buf, 113) if buf.dtype == jnp.int8
                   else jnp.full_like(buf, 3.0e4))
            for name, buf in cache.items()}
    out = dict(junk)
    lengths = np.asarray(lengths)
    for i, n in enumerate(lengths):
        seen = int(n) + s_v
        for name, buf in cache.items():
            if tables is None:
                rows = (layer, slot_start + i)
                keep = ((rows + (slice(None), slice(0, seen)))
                        if name.endswith("_s")
                        else (rows + (slice(0, seen),)))
                out[name] = out[name].at[keep].set(buf[keep])
                continue
            bt = cache["k"].shape[2]
            for j in range(-(-seen // bt)):
                blk = int(tables[i, j])
                live = min(bt, seen - j * bt)
                keep = ((layer, blk, slice(None), slice(0, live))
                        if name.endswith("_s")
                        else (layer, blk, slice(0, live)))
                out[name] = out[name].at[keep].set(buf[keep])
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "impl", "span",
                                             "slot_start"))
def _attend(cfg, q, cache, layer, positions, tables, *, impl, span=None,
            slot_start=0):
    # jitted so that the three layers of a case share one compile: the
    # layer is a traced scalar, exactly as in the serving scan
    return llama.decode_attention(cfg, q, cache, layer, positions,
                                  impl=impl, span=span,
                                  slot_start=slot_start, tables=tables)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "mid", "last"])
@pytest.mark.parametrize("s_v", [1, 4])
@pytest.mark.parametrize("slot_start,b", [(0, SLOTS_ALL), (1, 3)],
                         ids=["full_batch", "microbatch"])
@pytest.mark.parametrize("nkv,hd,kv_dtype,paged", [
    (8, 128, jnp.int8, False), (8, 128, jnp.int8, True),
    (8, 128, jnp.bfloat16, False), (2, 16, jnp.int8, False),
    (2, 16, jnp.float32, True)],
    ids=["int8-slab", "int8-paged", "bf16-slab", "int8-toy-slab",
         "f32-toy-paged"])
def test_in_place_entry(monkeypatch, nkv, hd, kv_dtype, paged, slot_start,
                        b, s_v, layer):
    """The kernel handed the whole cache, a layer index and a slot
    window: equal to the einsum reference; bit-identical to the kernel
    handed that layer and window sliced out; and blind to anything a
    query may not see. Four KV blocks to a span, five to the cache."""
    monkeypatch.setattr(flash_decode, "DEFAULT_BLOCK_KV", 64)
    span = 256
    nh = nkv * 2
    q_dtype = jnp.bfloat16 if kv_dtype == jnp.bfloat16 else jnp.float32
    cfg = _cfg(nh, nkv, hd, dtype=q_dtype)
    cache = _whole_cache(nkv, hd, kv_dtype)
    lengths = _ragged_lengths(b, span, s_v)
    positions = lengths[:, None] + jnp.arange(s_v)[None]
    q = jnp.asarray(np.random.default_rng(2).normal(
        size=(b, s_v, nh, hd)), q_dtype)
    tables = None
    kw = dict(span=span, slot_start=slot_start)
    if paged:
        cache, tables = _paged(cache, bt=64)
        tables = tables[slot_start:slot_start + b, :span // 64]
        kw = dict(tables=tables)

    def attend(c, li, impl, tables=None, **kw):
        return np.asarray(_attend(cfg, q, c, li, positions, tables,
                                  impl=impl, **kw), np.float32)

    got = attend(cache, jnp.int32(layer), "flash", **kw)
    want = attend(cache, jnp.int32(layer), "xla", **kw)
    tol = 0.05 if q_dtype == jnp.bfloat16 else 1e-5 * (
        float(np.max(np.abs(want))) or 1.0)
    assert float(np.max(np.abs(got - want))) < tol
    # the layer (and, for a slab, the slot window) sliced out by XLA: the
    # index maps must have picked exactly those blocks
    sliced = {name: buf[layer][None] for name, buf in cache.items()}
    if not paged:
        sliced = {name: buf[:, slot_start:slot_start + b]
                  for name, buf in sliced.items()}
        kw = dict(span=span)
    np.testing.assert_array_equal(
        got, attend(sliced, jnp.int32(0), "flash", **kw))
    # what no query may see is not read
    kw = dict(tables=tables) if paged else dict(span=span,
                                                slot_start=slot_start)
    junked = _garbage_elsewhere(cache, layer, slot_start, lengths, s_v,
                                tables)
    np.testing.assert_array_equal(
        got, attend(junked, jnp.int32(layer), "flash", **kw))


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("s_v", [1, 3])
@pytest.mark.parametrize("pattern", [(None, 63, None, 130, 0),
                                     (None, None, 130, 63, None)],
                         ids=["dead_between", "dead_ahead_and_after"])
def test_kernel_stores_the_steps_scales(monkeypatch, pattern, s_v, paged):
    """The rows a step wrote come with scales the planes do not hold yet:
    the kernel attends with them and stores them in place — exactly what
    the einsum path's scatter leaves, for live rows; a row that attends
    nothing (before, between and after live ones) stores nothing, and
    nothing else in the planes moves. One window straddles two blocks."""
    monkeypatch.setattr(flash_decode, "DEFAULT_BLOCK_KV", 64)
    cfg = _cfg(16, 8, 128)
    cache = _whole_cache(8, 128, jnp.int8)
    layer, span = 1, 256
    lengths = np.asarray([-s_v if n is None else n for n in pattern])
    live = np.flatnonzero(lengths >= 0)
    rng = np.random.default_rng(6)
    new = tuple(jnp.asarray(rng.uniform(0.5, 1.5, size=(SLOTS_ALL, s_v, 8)),
                            jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(SLOTS_ALL, s_v, 16, 128)), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32)[:, None] + jnp.arange(s_v)
    tables, kw = None, dict(span=span)
    if paged:
        cache, tables = _paged(cache, bt=64)
        tables = tables[:, :span // 64]
        kw = dict(tables=tables)
    want = {}
    for name, sc in zip(("k_s", "v_s"), new):
        plane = cache[name]
        for b in live:
            for i in range(s_v):
                p = int(lengths[b]) + i
                at = ((layer, int(tables[b, p // 64]), slice(None), p % 64)
                      if paged else (layer, int(b), slice(None), p))
                plane = plane.at[at].set(sc[b, i])
        want[name] = plane
    out, k_s, v_s = llama.decode_attention(
        cfg, q, cache, jnp.int32(layer), positions, impl="flash",
        new_scales=new, **kw)
    np.testing.assert_array_equal(np.asarray(k_s), np.asarray(want["k_s"]))
    np.testing.assert_array_equal(np.asarray(v_s), np.asarray(want["v_s"]))
    ref = llama.decode_attention(cfg, q, dict(cache, **want),
                                 jnp.int32(layer), positions, impl="xla",
                                 **kw)
    out, ref = np.asarray(out), np.asarray(ref)
    assert float(np.max(np.abs(out[live] - ref[live]))) < 1e-5 * float(
        np.max(np.abs(ref)))
    assert not out[lengths < 0].any()


def test_inactive_rows_attend_nothing():
    """verify_inner hands the attention position -S_v for a row that is
    not active: every key is masked, no block computes, the output is 0
    (flash) or a finite average (einsum) — and a live row is untouched."""
    cfg = _cfg(16, 8, 128)
    cache = _whole_cache(8, 128, jnp.int8)
    q = jnp.asarray(np.random.default_rng(2).normal(
        size=(SLOTS_ALL, 1, 16, 128)), jnp.float32)
    live = jnp.asarray([[300], [7], [0], [255], [90]], jnp.int32)
    dead = live.at[1].set(-1).at[4].set(-1)
    base, got = (np.asarray(llama.decode_attention(
        cfg, q, cache, jnp.int32(1), pos, span=T_CACHE, impl="flash"))
        for pos in (live, dead))
    np.testing.assert_array_equal(got[[0, 2, 3]], base[[0, 2, 3]])
    assert not got[[1, 4]].any() and base[[1, 4]].any()
    ref = np.asarray(llama.decode_attention(
        cfg, q, cache, jnp.int32(1), dead, span=T_CACHE, impl="xla"))
    assert np.isfinite(ref).all()


#: (configured, target platform, head_dim, kv heads) -> the impl, or the
#: reason an explicit "flash" is refused. On a TPU target the policy
#: follows what Mosaic can tile: auto takes the kernel where
#: kv heads x head_dim fills whole 128-lane tiles.
SELECTION_CASES = [
    ("auto", "cpu", 8, 4, "xla"),        # auto off the chip
    ("auto", "cpu", 128, 8, "xla"),      # ... at any layout
    ("xla", "cpu", 8, 4, "xla"),         # an explicit value wins
    ("flash", "cpu", 8, 4, "flash"),     # ... (interpret mode here)
    ("auto", "tpu", 128, 8, "flash"),    # the serving cell's layout
    ("auto", "tpu", 64, 8, "xla"),       # a head the kernel cannot tile
    ("auto", "tpu", 64, 1, "flash"),     # lane dim == head_dim
    ("auto", "tpu", 32, 4, "xla"),
    ("xla", "tpu", 128, 8, "xla"),
    ("flash", "tpu", 128, 8, "flash"),
    ("flash", "tpu", 64, 8, ValueError("head_dim 64")),
    ("flash", "tpu", 32, 4, ValueError("head_dim 32")),
]


def _check_selection(monkeypatch, resolve, configured, platform, head_dim,
                     n_kv_heads, want):
    from kubeflow_tpu.ops import pallas_compat

    monkeypatch.setattr(pallas_compat, "target_platform", lambda: platform)
    if isinstance(want, Exception):
        with pytest.raises(type(want), match=str(want)):
            resolve(configured, head_dim=head_dim, n_kv_heads=n_kv_heads)
    else:
        assert resolve(configured, head_dim=head_dim,
                       n_kv_heads=n_kv_heads) == want


@pytest.mark.parametrize(
    "configured,platform,head_dim,n_kv_heads,want", SELECTION_CASES)
def test_selection_policy(monkeypatch, configured, platform, head_dim,
                          n_kv_heads, want):
    _check_selection(monkeypatch, flash_decode.resolve_impl, configured,
                     platform, head_dim, n_kv_heads, want)


def test_config_refuses_an_unknown_decode_impl():
    with pytest.raises(ValueError):
        dataclasses.replace(llama.LlamaConfig.tiny(),
                            decode_attention_impl="mosaic")


@pytest.mark.parametrize("forced,platform,gspmd,want", [
    (False, "cpu", False, "xla"),      # this CPU box
    (False, "tpu", False, "pallas"),   # one chip
    (False, "tpu", True, "xla"),       # XLA partitions the program
    (False, "cpu", True, "xla"),
    (True, "cpu", False, "pallas"),    # the programmatic force-on
    (True, "tpu", True, "pallas"),
])
def test_quant_matmul_selection_policy(monkeypatch, forced, platform,
                                       gspmd, want):
    """The weight-read path's policy has the same shape: the force-on
    flag, else XLA wherever a GSPMD mesh partitions the program, else the
    target platform."""
    from kubeflow_tpu.ops import pallas_compat, quant

    monkeypatch.setattr(quant, "USE_PALLAS_DEQUANT", forced)
    monkeypatch.setattr(pallas_compat, "target_platform", lambda: platform)
    monkeypatch.setattr(pallas_compat, "gspmd_partitioned", lambda: gspmd)
    assert quant.resolve_quant_matmul_impl() == want


# -- engine level -------------------------------------------------------------

ENG_KW = dict(n_slots=2, max_len=48, buckets=(8,), decode_chunk=2)


@pytest.fixture(scope="module")
def engine_pair():
    """One warmed xla/flash engine pair at toy dims (f32 model — byte
    comparison must not be an accumulation-order coin flip — with int8
    KV, half the kernel's contract). Module-scoped: every fast-lane
    engine test shares the ~15s of compiles."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(jax.random.key(0), cfg)
    from kubeflow_tpu.serving.llm import LLMEngine

    ex = LLMEngine(params, cfg, decode_attention_impl="xla",
                   kv_quantize="int8", **ENG_KW)
    ef = LLMEngine(params, cfg, decode_attention_impl="flash",
                   kv_quantize="int8", **ENG_KW)
    ex.warmup()
    ef.warmup()
    yield ex, ef
    ex.close()
    ef.close()


def test_engine_reports_resolved_impl(engine_pair):
    ex, ef = engine_pair
    assert ex.metrics()["decode_attention_impl"] == "xla"
    assert ef.metrics()["decode_attention_impl"] == "flash"


def test_engine_greedy_byte_parity(engine_pair):
    ex, ef = engine_pair
    for p in ([1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [40, 2]):
        want = ex.generate(list(p), 10)
        got = ef.generate(list(p), 10)
        assert got == want, (p, got, want)


def test_engine_seeded_byte_parity(engine_pair):
    ex, ef = engine_pair
    for seed in (7, 12345):
        for p in ([3, 1, 4, 1, 5], [9, 9, 9]):
            want = ex.generate(list(p), 8, temperature=0.9, seed=seed)
            got = ef.generate(list(p), 8, temperature=0.9, seed=seed)
            assert got == want, (p, seed, got, want)


def test_engine_penalized_greedy_parity(engine_pair):
    """Penalty edits run AFTER the attention produces logits — the
    kernel must not perturb the penalized sampling pipeline either."""
    ex, ef = engine_pair
    p = [2, 4, 6, 8]
    want = ex.generate(list(p), 8, presence_penalty=0.7,
                       frequency_penalty=0.3)
    got = ef.generate(list(p), 8, presence_penalty=0.7,
                      frequency_penalty=0.3)
    assert got == want


@pytest.mark.slow
def test_engine_prefix_cache_and_chunked_parity():
    """The heavy engine gauntlet: prefix-cache hits (radix admission →
    continuation programs) and chunked long prompts through a flash
    engine match the xla engine byte-for-byte, greedy and seeded."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(jax.random.key(0), cfg)
    from kubeflow_tpu.serving.llm import LLMEngine

    kw = dict(n_slots=2, max_len=96, buckets=(8, 16, 32),
              decode_chunk=4, kv_quantize="int8", prefix_cache=True)
    ex = LLMEngine(params, cfg, decode_attention_impl="xla", **kw)
    ef = LLMEngine(params, cfg, decode_attention_impl="flash", **kw)
    try:
        ex.warmup()
        ef.warmup()
        shared = list(range(1, 18))           # 2 radix blocks
        long = shared + list(range(300, 335))  # 52 tokens > bucket 32
        for p in (shared + [99, 100], shared + [7], long):
            want = ex.generate(list(p), 8)
            got = ef.generate(list(p), 8)
            assert got == want, p
        assert ef.metrics()["prefix_hits"] >= 1   # the hit path ran
        want = ex.generate(shared + [55], 8, temperature=0.8, seed=42)
        got = ef.generate(shared + [55], 8, temperature=0.8, seed=42)
        assert got == want
    finally:
        ex.close()
        ef.close()


@pytest.mark.slow
def test_engine_speculative_verify_parity():
    """Speculative decoding dispatches verify windows (S_v = k+1 > 1)
    through the SAME attention body — a flash spec engine must match
    the xla spec engine (and, by the engine invariant, plain greedy)
    byte-for-byte."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(jax.random.key(0), cfg)
    from kubeflow_tpu.serving.llm import LLMEngine

    kw = dict(n_slots=2, max_len=96, buckets=(16,), decode_chunk=4,
              kv_quantize="int8", speculative=3)
    sx = LLMEngine(params, cfg, decode_attention_impl="xla", **kw)
    sf = LLMEngine(params, cfg, decode_attention_impl="flash", **kw)
    try:
        sx.warmup()
        sf.warmup()
        for p in ([1, 2, 3, 1, 2, 3, 1], list(range(5, 17))):
            want = sx.generate(list(p), 10)
            got = sf.generate(list(p), 10)
            assert got == want, p
    finally:
        sx.close()
        sf.close()


@pytest.mark.slow
def test_engine_bf16_greedy_parity():
    """The production dtype: greedy argmax over bf16 logits survives
    the kernel's (mathematically equal, differently-ordered) softmax at
    toy dims — the claim the TPU record rides on."""
    cfg = llama.LlamaConfig.tiny()   # bf16 default
    params = llama.init(jax.random.key(0), cfg)
    from kubeflow_tpu.serving.llm import LLMEngine

    ex = LLMEngine(params, cfg, decode_attention_impl="xla", **ENG_KW)
    ef = LLMEngine(params, cfg, decode_attention_impl="flash", **ENG_KW)
    try:
        ex.warmup()
        ef.warmup()
        for p in ([1, 2, 3], [11, 12, 13, 14]):
            assert ex.generate(list(p), 8) == ef.generate(list(p), 8), p
    finally:
        ex.close()
        ef.close()


def test_auto_pins_to_xla_under_gspmd_sharding():
    """Under GSPMD sharding "auto" must pin to the einsum path — a
    pallas custom call has no SPMD partitioning rule, so the kernel
    would make XLA replicate the sharded cache. Explicit "flash" is
    honored (the operator owns the layout claim)."""
    from kubeflow_tpu.parallel import MeshConfig
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.multichip import StageShardedEngine

    cfg = llama.LlamaConfig.tiny()          # decode_attention_impl=auto
    params = llama.init(jax.random.key(0), cfg)
    eng = LLMEngine(params, cfg, mesh=MeshConfig(tensor=2), **ENG_KW)
    assert eng.cfg.decode_attention_impl == "xla"
    eng.close()
    eng = LLMEngine(params, cfg, mesh=MeshConfig(tensor=2),
                    decode_attention_impl="flash", **ENG_KW)
    assert eng.cfg.decode_attention_impl == "flash"
    eng.close()
    eng = StageShardedEngine(params, cfg, stage=2, tensor=2, **ENG_KW)
    assert eng.cfg.decode_attention_impl == "xla"
    eng.close()
    # tensor=1 stages run whole per device: "auto" follows the platform
    # default exactly like the single-program engine — and is PINNED at
    # construction (this CPU box resolves xla), so a later env flip can
    # never hand an engine a mixed-impl program menu
    eng = StageShardedEngine(params, cfg, stage=2, **ENG_KW)
    assert eng.cfg.decode_attention_impl == "xla"
    eng.close()
    eng = LLMEngine(params, cfg, **ENG_KW)   # no mesh: same pin
    assert eng.cfg.decode_attention_impl == "xla"
    eng.close()
