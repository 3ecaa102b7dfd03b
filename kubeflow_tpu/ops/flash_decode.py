"""Pallas (Mosaic) flash-decode kernel — fused grouped-query attention
that reads the serving engine's KV cache WHERE IT LIES (ISSUE 15, ROADMAP
#5; in place since ISSUE 28).

Decode re-reads the KV span every step, so at serving dims a decode
step's attention is HBM traffic the XLA einsum path
(separate score/softmax/weighted-sum programs) cannot tile optimally.
This kernel streams each live KV block HBM→VMEM exactly once and runs the
whole attention — scores, per-token int8 dequant, online softmax,
weighted sum — in VMEM:

  - **The whole cache is the operand.** K/V arrive as the arrays the
    layer scan carries: the slab `[L, slots, max_len, kv_heads, hd]` or
    the paged pool `[L, N_blocks, bt, kv_heads, hd]`, int8 or the model
    dtype, plus (int8) per-token-per-head f32 scales stored lane-major,
    `[L, slots, kv_heads, max_len]` / `[L, N_blocks, kv_heads, bt]`. The
    BlockSpec index maps pick the LAYER (a prefetched scalar), the SLOT
    (`slot_start + b`, a microbatch's window) and the KV BLOCK, so XLA
    stages no slice, reshape, transpose or convert of anything of cache
    size, per layer or otherwise: the operands ARE the carried arrays.
  - **Bytes follow the context.** The KV-block coordinate of every K, V
    and scale index map is clamped to the slot's last live block
    (`(lengths[b] + S_v - 1) // block_kv`, from the prefetched lengths):
    past it the pipeline sees an unchanged block index and issues no
    copy, and `pl.when` skips the compute. `span` bounds only the grid's
    length; a row handed a negative length (the engine's dead slots)
    stands on the block before it and moves no byte.
  - **One grid step serves every KV head of a (slot, KV block)**: grid
    `(slots, span / block_kv)`. A block is `[block_kv, kv_heads, hd]`,
    a token's `[kv_heads, hd]` tile contiguous; head h's keys are row h
    of every tile, read by a sublane-strided load of the block viewed
    as 32-bit words (4 int8 or 2 bf16 heads to a word, unpacked by
    shifts), so no head is ever transposed out. The heads are the batch
    axis of the two matmuls and of the ONE softmax pass between them.
  - **One body for decode and verify.** q is `[slots, S_v, heads, hd]`:
    S_v=1 is `decode_step`, S_v>1 is the speculative `verify_step`
    window — the same verify-is-decode-at-S_v=1 invariant the engine's
    einsum path keeps. Query row r of kv-head h covers head-group
    member r // S_v at position `lengths[b] + r % S_v`.
  - **GQA inside the kernel.** q heads regroup onto their kv heads
    before the call (`[B, kv, g*S_v, hd]` — a reshape of the tiny q
    tensor, not of the cache), so the head-expanded `repeat_kv` K/V
    copy never exists.
  - **Online softmax over KV blocks.** The KV axis is sequential
    ("arbitrary"): per head (acc, m, l) carry across KV blocks in VMEM
    scratch, exactly the ops/flash_pallas.py forward recurrence.

Per-ROW masking comes from `lengths` (scalar-prefetched): key position t
is visible to query row r iff `t <= lengths[b] + r % S_v` (and
`t < span`) — byte-for-byte the mask `llama.decode_attention` applies on
the einsum path.

Follows the ops/flash_pallas.py precedent exactly: on non-TPU backends
the kernel runs under `interpret=True` (numerics identical to the
compiled Mosaic path), so the byte-level differential gauntlet
(tests/test_flash_decode.py) runs in the CPU fast lane with no code
path fork other than `interpret=`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat

NEG_INF = -1e30

# Tests on the CPU backend set this to exercise the kernel via the Pallas
# interpreter (numerics identical to the compiled Mosaic path).
FORCE_INTERPRET = False

#: default KV block (tokens per sequential grid step; a cache shorter
#: than it is one block). Measured on the v5e at the serving shapes
#: (ISSUE 28, 16 slots x 8 kv heads of 128, int8): a grid step costs
#: ~0.35 us whether it computes or not and a live block is bound by its
#: copy (0.65 us per 256 tokens), so 512 halves the steps a span costs
#: for a sixth more tokens fetched past the average context; at 1024 the
#: bytes past the context cost more than the steps saved.
DEFAULT_BLOCK_KV = 512

#: the impl selection (`LlamaConfig.decode_attention_impl`): an
#: EXPLICIT "flash" | "xla" wins (tests pin impls per engine); "auto"
#: is decided from what the process can observe, the target platform
#: and the KV layout, and from nothing a user sets on the machine: a
#: trace or a /metrics reading names the impl, and no one has to ask
#: what the environment held where it was made.


def resolve_impl(configured: str = "auto", *, head_dim: int,
                 n_kv_heads: int) -> str:
    """Selection policy: explicit config ("xla"/"flash") > flash
    where it compiles (TPU target, KV layout the kernel tiles), xla
    elsewhere — see pallas_compat.resolve_flash_impl (it raises on an
    explicit "flash" at a layout the TPU compiler would refuse).
    Static — resolved at trace time, so each engine's compiled menu
    covers exactly one impl."""
    return pallas_compat.resolve_flash_impl(
        configured, head_dim=head_dim,
        n_kv_heads=n_kv_heads)


def _resolve_interpret(interpret):
    if interpret is not None:
        return interpret
    if FORCE_INTERPRET:
        return True
    # non-TPU target: interpreter mode: the differential tests' CPU
    # fast lane runs the SAME kernel body the chip compiles
    return pallas_compat.target_platform() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _heads(ref, dtype):
    """Every head's keys (or values) `[kv, n_tok, hd]` in `dtype`, out of
    a KV block `ref` `[1, 1, n_tok, kv, hd]` as the cache stores it. Head
    h is row h of every token's `[kv, hd]` tile, i.e. every kv-th row of
    the block seen as `[n_tok * kv, hd]`: ONE sublane-strided load
    (indexing the kv axis of the 5-D block instead compiles to a gather an
    order of magnitude slower: measured, ISSUE 28). The TPU packs
    sub-32-bit rows along the sublanes (4 int8 / 2 bf16 consecutive rows
    to a word, row `4 w + i` in bits `[8 i, 8 i + 8)`), so those are
    loaded through the block's 32-bit view, `pack` heads to a load, and
    unpacked by two shifts."""
    _, _, n_tok, nkv, hd = ref.shape
    src = jnp.dtype(ref.dtype)
    pack = 4 // src.itemsize
    if nkv % pack or (pack > 1 and src not in (jnp.int8, jnp.bfloat16)):
        # a toy layout with no word view (interpret mode)
        return jnp.swapaxes(ref[0, 0], 0, 1).astype(dtype)
    rows = ref.reshape(1, 1, n_tok * nkv, hd)
    if pack == 1:
        return jnp.stack([rows[0, 0, pl.ds(h, n_tok, stride=nkv), :]
                          for h in range(nkv)]).astype(dtype)
    words = jnp.stack([          # [kv / pack, 1, n_tok, hd]
        rows.bitcast(jnp.int32)[0, 0, pl.ds(w, n_tok, stride=nkv // pack), :]
        for w in range(nkv // pack)])[:, None]
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, pack, 1, 1), 1)
    if src == jnp.int8:
        vals = (words << (24 - 8 * sub)) >> 24        # sign-extending
        vals = vals.astype(jnp.float32)
    else:   # bf16 is the high half of the f32 with the same value
        vals = jax.lax.bitcast_convert_type(
            jnp.where(sub == 0, words << 16, words & jnp.int32(-65536)),
            jnp.float32)
    return vals.astype(dtype).reshape(nkv, n_tok, hd)


def _decode_kernel(meta_ref, *refs, s_v, block_kv, nkv, span, t_cache,
                   scale, quantized, row_at, new_scales_at, paged=False,
                   window=None, lo_at=None):
    if paged:
        # block-table mode (ISSUE 19): the table ref is scalar-prefetch
        # arg 2 — it steers the k/v/scale BlockSpec index_maps (the
        # indirection happens in the pipeline, before the body runs),
        # so the body itself never reads it: by the time a block is in
        # VMEM, k_start below is its LOGICAL span offset either way.
        _tbl_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    ks_out = vs_out = None
    if quantized and new_scales_at is not None:
        ks_ref, vs_ref, o_ref, ks_out, vs_out, acc_ref, m_ref, l_ref = rest
    elif quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = meta_ref[b]
    if window is None:
        k_start = j * block_kv
    else:
        # a ring: grid step j stands on the slot's j-th block of
        # POSITIONS from the window's lower edge (the index maps fold it
        # onto the ring), so everything below reads positions as ever
        k_start = (meta_ref[lo_at + b] + j) * block_kv
    # a cache whose length the block does not divide (toy dims): the last
    # block's tail lies past the array and holds anything, NaN included
    ragged = t_cache is not None and t_cache % block_kv != 0

    def scale_blocks():
        """This block's K and V scales, each [kv, block_kv]; with the
        step's own (they ride the prefetched vector as bits: K's from
        `new_scales_at[0]`, V's from `[1]`, [B, S_v, kv] flat) set at the
        slot's S_v write positions, where the cache does not hold them
        yet."""
        blocks = [ks_ref[0, 0], vs_ref[0, 0]]
        if new_scales_at is None:
            return blocks
        shape = blocks[0].shape
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        head = [jax.lax.broadcasted_iota(jnp.int32, shape, 0) == h
                for h in range(1, nkv)]
        for n, base in enumerate(new_scales_at):
            at = base + b * (s_v * nkv)
            for i in range(s_v):
                # a [kv, 1] column of this position's scales, built in
                # the lanes: head 0's everywhere, then each other head's
                # on its sublane
                bits = jnp.full(shape, meta_ref[at + i * nkv], jnp.int32)
                for h in range(1, nkv):
                    bits = jnp.where(head[h - 1],
                                     meta_ref[at + (i * nkv + h)], bits)
                blocks[n] = jnp.where(
                    lane == length + i - k_start,
                    jax.lax.bitcast_convert_type(bits, jnp.float32),
                    blocks[n])
        return blocks

    def attend(ks, vs):
        """One KV block against every head at once: the heads are the
        leading (batch) axis of both matmuls and of the ONE softmax pass
        between them. (A head at a time, each small matmul waits out the
        one before it and the reductions between: 3x slower, measured.)"""
        dtype = q_ref.dtype
        # int8 → model dtype in-register (the einsum path's
        # ck.astype(cfg.dtype)); float caches pass through untouched
        s = jax.lax.dot_general(
            q_ref[0], _heads(k_ref, dtype), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # [kv, rows, block_kv]
        if quantized:
            # per-token k scale on the score column — the einsum path's
            # `att * k_scales` order (scale BEFORE 1/sqrt(hd))
            s = s * ks
        s = s * scale
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # row r of a kv head is query position r % S_v (rows stack as
        # [group member, S_v]); padded rows compute garbage sliced off
        q_pos = length + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) % s_v
        if window is None:
            valid = (k_pos < span) & (k_pos <= q_pos)
        else:
            # the ring row of a position past the slot's newest still
            # holds the position a ring earlier: unseen as `k_pos` says
            valid = (k_pos <= q_pos) & (q_pos - k_pos < window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # fully-masked rows keep m_new == NEG_INF; exp(s - m_new) would
        # be exp(0)=1 there, so zero masked entries explicitly
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, :, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = _heads(v_ref, dtype)
        if quantized:
            # fold the per-token v scale into p so the int8 payload
            # feeds the dot un-materialized (the einsum path's
            # probs_s = probs * v_scales trick)
            p = p * vs
        if ragged:
            p = jnp.where(valid, p, 0.0)
            v = jnp.where(k_start + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 1) < t_cache, v, jnp.zeros_like(v))
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def per_head(sc):   # [kv, block_kv] -> [kv, 1, block_kv]
        return jnp.stack([sc[h:h + 1] for h in range(nkv)])

    # whole block beyond the deepest query position of this slot → skip
    # (block 0 computes whenever length >= 0: it keys at least position
    # 0); the index maps fetched nothing new for it either
    live = k_start <= length + s_v - 1

    @pl.when(live)
    def _():
        ks, vs = scale_blocks() if quantized else (None, None)
        if ks_out is not None:
            # the cache's own copy of this block, the step's scales in it
            ks_out[0, 0] = ks
            vs_out[0, 0] = vs
        attend(*((per_head(ks), per_head(vs)) if quantized
                 else (None, None)))

    if ks_out is not None:
        # rows that attend nothing, ahead of the first live one, stand on
        # row 0's block 0, which was fetched and which the pipeline will
        # write back: hand it through. (After a live row the buffers
        # still hold that row's last block, the step's scales in it, and
        # must stay as they are.)
        @pl.when((j == 0) & (meta_ref[row_at + b] == 0)
                 & (meta_ref[0] + (s_v - 1) < 0))
        def _():
            ks_out[0, 0] = ks_ref[0, 0]
            vs_out[0, 0] = vs_ref[0, 0]

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_decode_attention(q, k, v, lengths, *, layer, span=None,
                           slot_start=0, k_scale=None, v_scale=None,
                           new_scales=None, scale=None, block_kv=None,
                           interpret=None, tables=None, window=None):
    """Fused GQA decode/verify attention over the KV cache, in place.

    q: [B, S_v, heads, hd] (model dtype); k/v: the WHOLE cache payload
    `[L, slots, T, kv_heads, hd]`, int8 (with k_scale/v_scale
    `[L, slots, kv_heads, T]` f32, lane-major) or float; `layer`: which
    of the L (a traced int32 scalar: the layer scan's index); `span`:
    how many of a slot's T rows the grid covers (default T); the B rows
    of q are cache slots `slot_start .. slot_start + B - 1` (static);
    lengths: [B] int32 — query row i of slot b attends key positions
    <= lengths[b] + i (< span). Returns [B, S_v, heads, hd] in q.dtype.

    Nothing of cache size is sliced, reshaped across tiles, padded or
    transposed here: the index maps pick layer, slot and KV block, and a
    block past a slot's context is neither fetched nor computed.

    `new_scales` = (k, v) `[B, S_v, kv_heads]` f32: the scales of the S_v
    rows this step wrote into the int8 payloads at positions lengths[b]
    .. lengths[b] + S_v - 1, NOT yet in k_scale/v_scale. The kernel uses
    them for those positions and stores them: it returns `(out, k_scale,
    v_scale)` with the planes updated in place (aliased), the scale block
    a slot's write lands in being one the kernel holds in VMEM anyway.
    (The XLA alternative, a scatter of [kv]-windows into a lane-major
    plane, makes the TPU's layout assignment carry the plane kv-minor and
    transpose all of it around every call: compiled and read, ISSUE 28.)

    PAGED mode (ISSUE 19): with `tables` [B, n_blocks_per_slot] int32,
    k/v are the block POOL `[L, N_blocks, bt, kv_heads, hd]` (scales
    `[L, N_blocks, kv_heads, bt]`) and slot b's logical span is its
    table's blocks concatenated (`span` and `slot_start` are the
    table's: the caller slices ITS rows and columns). The grid already
    walks (slot, kv_block); paged just indirects the kv-block axis of
    the k/v/scale BlockSpecs through the scalar-prefetched table — the
    kernel body, its masking, and the online-softmax recurrence are
    byte-identical to slab mode, which is what keeps the layouts
    parity-comparable.

    WINDOW mode (`window`, static; None = today's programs): the slab is
    a RING of T rows a slot, position p in row `p mod T`; query row i of
    slot b sees positions p with `0 <= lengths[b] + i - p < window`. T
    is a whole number of KV blocks and at least `window + S_v - 1`. A
    slot's grid steps walk its blocks of positions from the one that
    holds the window's lower edge up to the one its newest row is in
    (two blocks for a window of one block's length), folded onto the
    ring by the index maps; blocks below the window are neither fetched
    nor computed, and `span` is not read.
    """
    b, s_v, nh, hd = q.shape
    paged = tables is not None
    t_cache, nkv = k.shape[2:4]
    if nh % nkv:
        raise ValueError(f"heads {nh} must divide by kv_heads {nkv}")
    g = nh // nkv
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    interpret = _resolve_interpret(interpret)
    scale = 1.0 / (hd ** 0.5) if scale is None else scale
    if paged:
        # the block size IS the pool's block_tokens; the span is the
        # table width — always block-aligned, so no ragged tail exists
        block_kv = t_cache
        if tables.shape[0] != b:
            raise ValueError(f"tables rows {tables.shape[0]} != batch {b}")
        n_k = tables.shape[1]
        span, t_cache = n_k * block_kv, None
    elif window is None:
        span = t_cache if span is None else min(span, t_cache)
        block_kv = DEFAULT_BLOCK_KV if block_kv is None else block_kv
        block_kv = min(block_kv, t_cache)
        n_k = pl.cdiv(span, block_kv)
    else:
        block_kv = DEFAULT_BLOCK_KV if block_kv is None else block_kv
        block_kv = min(block_kv, t_cache)
        if t_cache % block_kv or t_cache < window + s_v - 1:
            raise ValueError(
                f"a ring of {t_cache} rows must be whole blocks of "
                f"{block_kv} and hold a window of {window} + {s_v - 1}")
        n_ring = t_cache // block_kv
        # the blocks a band of window + S_v - 1 positions can touch
        n_k = min(n_ring, (window + s_v - 2) // block_kv + 2)
        span = None
    if window is not None and paged:
        raise ValueError("window attention has no paged form")

    # regroup q heads onto their kv heads: [B, S_v, nh, hd] →
    # [B, kv, g*S_v, hd] (kv-major head split, the verify_inner
    # convention); rows pad to the f32-accumulator sublane floor
    rows = g * s_v
    r_pad = max(8, _round_up(rows, 8))
    qg = jnp.transpose(q.reshape(b, s_v, nkv, g, hd),
                       (0, 2, 3, 1, 4)).reshape(b, nkv, rows, hd)
    if r_pad != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, r_pad - rows), (0, 0)))

    # ONE prefetched vector (the benchmark tells this kernel by its
    # operand list): lengths, the layer's index, where each row's grid
    # steps look, and the step's own scales as bits. A live row walks its
    # own blocks
    # up to its last live one and stands there: the pipeline issues no
    # copy for an unchanged block index. A row that attends nothing
    # (length < 0: the engine's dead slots) stands on the block the live
    # row before it ended on, so it moves no byte at all (the rows before
    # the first live one: all on row 0's block 0, one copy between them).
    lengths = jnp.asarray(lengths, jnp.int32)
    slot = jnp.arange(b, dtype=jnp.int32)
    live = lengths + (s_v - 1) >= 0
    row = jnp.maximum(jax.lax.cummax(jnp.where(live, slot, -1)), 0)
    if window is None:
        cap = jnp.clip((lengths + (s_v - 1)) // block_kv, 0, n_k - 1)[row]
    else:   # blocks of positions: the index maps fold them onto the ring
        cap = (jnp.maximum(lengths + (s_v - 1), 0) // block_kv)[row]
    meta = [lengths, jnp.asarray(layer, jnp.int32).reshape(1), row, cap]
    row_at, cap_at = b + 1, 2 * b + 1
    new_scales_at = None
    if new_scales is not None:
        if not quantized:
            raise ValueError("new_scales come with k_scale and v_scale")
        new_scales_at = (3 * b + 1, 3 * b + 1 + b * s_v * nkv)
        meta += [jax.lax.bitcast_convert_type(
            sc.astype(jnp.float32), jnp.int32).reshape(-1)
            for sc in new_scales]
    lo_at = None
    if window is not None:
        lo_at = sum(m.shape[0] for m in meta)
        meta.append(jnp.maximum(lengths - (window - 1), 0) // block_kv)
    meta = jnp.concatenate(meta)

    if window is None:
        def kv_block(b_, j, meta_ref):
            cap = meta_ref[cap_at + b_]
            return jnp.where(meta_ref[b_] + (s_v - 1) >= 0,
                             jnp.minimum(j, cap), cap)
    else:
        def kv_block(b_, j, meta_ref):
            cap = meta_ref[cap_at + b_]
            return jnp.where(
                meta_ref[b_] + (s_v - 1) >= 0,
                jnp.minimum(meta_ref[lo_at + b_] + j, cap), cap) % n_ring

    if paged:
        def sc_at(b_, j, meta_ref, tbl_ref):
            return (meta_ref[b], tbl_ref[meta_ref[row_at + b_],
                                         kv_block(b_, j, meta_ref)], 0, 0)

        def at(*args):
            return sc_at(*args) + (0,)
    else:
        def at(b_, j, meta_ref):
            return (meta_ref[b], slot_start + meta_ref[row_at + b_],
                    kv_block(b_, j, meta_ref), 0, 0)

        def sc_at(b_, j, meta_ref):
            return (meta_ref[b], slot_start + meta_ref[row_at + b_], 0,
                    kv_block(b_, j, meta_ref))
    kv_spec = pl.BlockSpec((1, 1, block_kv, nkv, hd), at)

    qo_spec = pl.BlockSpec((1, nkv, r_pad, hd),
                           lambda b_, j, *_: (b_, 0, 0, 0))
    out_specs = qo_spec
    out_shape = pallas_compat.sds_with_vma(
        (b, nkv, r_pad, hd), q.dtype, q, k, v)
    aliases = {}
    extra_specs, extra_args = [], []
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, nkv, block_kv), sc_at)
        extra_specs = [sc_spec, sc_spec]
        extra_args = [k_scale, v_scale]
    n_prefetch = 2 if paged else 1
    if new_scales_at is not None:
        # the planes come back as outputs 1 and 2, in the operands' place
        out_specs = [qo_spec, sc_spec, sc_spec]
        out_shape = [out_shape] + [
            pallas_compat.sds_with_vma(sc.shape, sc.dtype, sc)
            for sc in (k_scale, v_scale)]
        aliases = {n_prefetch + 3: 1, n_prefetch + 4: 2}

    prefetch = [meta]
    if paged:
        prefetch.append(jnp.asarray(tables, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(b, n_k),
        in_specs=[qo_spec, kv_spec, kv_spec, *extra_specs],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((nkv, r_pad, hd), jnp.float32),
            pltpu.VMEM((nkv, r_pad, 128), jnp.float32),
            pltpu.VMEM((nkv, r_pad, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, s_v=s_v, block_kv=block_kv, nkv=nkv, span=span,
        t_cache=t_cache, scale=scale, quantized=quantized, row_at=row_at,
        new_scales_at=new_scales_at, paged=paged,
        **({} if window is None
           else {"window": int(window), "lo_at": lo_at}))
    itemsize = jnp.dtype(k.dtype).itemsize
    reach = span if window is None else n_k * block_kv
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # rows in order too: a dead row stands on the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * nh * s_v * reach * hd,
            bytes_accessed=2 * b * reach * nkv * hd * itemsize,
            transcendentals=b * nh * s_v * reach,
        ),
        interpret=interpret,
    )(*prefetch, qg, k, v, *extra_args)
    planes = ()
    if new_scales_at is not None:
        out, *planes = out
    out = out[:, :, :rows]                           # [B, kv, g*S_v, hd]
    out = out.reshape(b, nkv, g, s_v, hd).transpose(
        0, 3, 1, 2, 4).reshape(b, s_v, nh, hd)
    return (out, *planes) if planes else out
