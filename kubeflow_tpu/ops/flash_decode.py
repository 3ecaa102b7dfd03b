"""Pallas (Mosaic) flash-decode kernel — fused grouped-query attention
directly over the serving engine's KV slab layout (ISSUE 15, ROADMAP #5).

Decode re-reads the entire KV span every step, so at serving dims the
attention bucket of `serving_decode_breakdown` is HBM traffic the XLA
einsum path (separate score/softmax/weighted-sum programs) cannot tile
optimally. This kernel streams each KV block HBM→VMEM exactly once and
runs the whole attention — scores, per-token int8 dequant, online
softmax, weighted sum — in VMEM:

  - **Slab-native layout.** K/V arrive exactly as `llama.verify_inner`
    slices them from the cache: `[slots, span, kv_heads, hd]` in cache
    dtype (int8 or the model dtype) plus per-token-per-head f32 scales
    `[slots, span, kv_heads]`. The int8 payload is converted in-register
    at the block load and its scale folded into the score/probability —
    a dequantized f32/bf16 copy of the cache NEVER materializes in HBM
    (the whole point: the cache's HBM footprint is its int8 bytes).
    The kv-head grid axis indexes the slab through a metadata-only
    `[B, span, kv*hd]` reshape, so no transpose of the payload is ever
    staged; only the tiny scale arrays are transposed to `[B, kv, 1, span]`
    (4/hd of the payload bytes).
  - **One body for decode and verify.** q is `[slots, S_v, heads, hd]`:
    S_v=1 is `decode_step`, S_v>1 is the speculative `verify_step`
    window — the same verify-is-decode-at-S_v=1 invariant the engine's
    einsum path keeps. Query row r of kv-head h covers head-group
    member r // S_v at position `lengths[b] + r % S_v`.
  - **GQA inside the kernel.** q heads regroup onto their kv heads
    before the call (`[B, kv, g*S_v, hd]` — a reshape of the tiny q
    tensor, not of the cache), so the head-expanded `repeat_kv` K/V
    copy never exists.
  - **Online softmax over KV blocks.** grid `(B, kv_heads, n_kv)` with
    the KV axis sequential ("arbitrary"): (acc, m, l) carry across KV
    blocks in VMEM scratch, exactly the ops/flash_pallas.py forward
    recurrence. Blocks entirely beyond every query position skip their
    compute (`pl.when`), the decode twin of the causal block skip.

Per-slot `span` bounding comes from the caller slicing the slab (the
engine's length-aware span menu); per-ROW masking comes from `lengths`
(scalar-prefetched): key position t is visible to query row r iff
`t <= lengths[b] + r % S_v` — byte-for-byte the mask
`llama.verify_inner` applies on the einsum path.

Follows the ops/flash_pallas.py precedent exactly: on non-TPU backends
the kernel runs under `interpret=True` (numerics identical to the
compiled Mosaic path), so the byte-level differential gauntlet
(tests/test_flash_decode.py) runs in the CPU fast lane with no code
path fork other than `interpret=`.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat

NEG_INF = -1e30

# Tests on the CPU backend set this to exercise the kernel via the Pallas
# interpreter (numerics identical to the compiled Mosaic path).
FORCE_INTERPRET = False

#: default KV block (tokens per sequential grid step). Production spans
#: are powers of two >= 128, so the default divides them; the wrapper
#: clamps (and pads — toy dims only) when the span is smaller or ragged.
DEFAULT_BLOCK_KV = 256

#: env override for the auto impl selection (`LlamaConfig
#: .decode_attention_impl == "auto"`): "flash" | "xla". An EXPLICIT
#: config value wins over the env (tests and the bench A/B pin impls per
#: engine); the env wins over the platform default (the operational
#: kill-switch for a fleet without config pushes).
IMPL_ENV = "KTPU_DECODE_ATTN"


def resolve_impl(configured: str = "auto", *, head_dim: int,
                 n_kv_heads: int) -> str:
    """Selection policy: explicit config ("xla"/"flash") >
    KTPU_DECODE_ATTN env > flash where it compiles (TPU target, KV
    layout the kernel tiles), xla elsewhere — see
    pallas_compat.resolve_flash_impl. Static — resolved at trace time,
    so each engine's compiled menu covers exactly one impl."""
    return pallas_compat.resolve_flash_impl(
        configured, os.environ.get(IMPL_ENV), head_dim=head_dim,
        n_kv_heads=n_kv_heads)


def _resolve_interpret(interpret):
    if interpret is not None:
        return interpret
    if FORCE_INTERPRET:
        return True
    # non-TPU target: interpreter mode — the differential tests' CPU
    # fast lane (and the bench's CPU A/B smoke) run the SAME kernel body
    return pallas_compat.target_platform() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _decode_kernel(len_ref, *refs, s_v, block_kv, t_real, scale,
                   quantized, paged=False):
    if paged:
        # block-table mode (ISSUE 19): the table ref is scalar-prefetch
        # arg 2 — it steers the k/v/scale BlockSpec index_maps (the
        # indirection happens in the pipeline, before the body runs),
        # so the body itself never reads it: by the time a block is in
        # VMEM, k_start below is its LOGICAL span offset either way.
        _tbl_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    k_start = j * block_kv
    rows = q_ref.shape[2]          # g*S_v padded to the sublane floor

    def compute():
        q = q_ref[0, 0]                              # [rows, hd]
        # int8 → model dtype in-register (the einsum path's
        # ck.astype(cfg.dtype)); float caches pass through untouched
        k = k_ref[0].astype(q.dtype)                 # [block_kv, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [rows, block_kv]
        if quantized:
            # per-token k scale on the score column — the einsum path's
            # `att * k_scales` order (scale BEFORE 1/sqrt(hd))
            s = s * ks_ref[0, 0]
        s = s * scale
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 1)
        # row r of this kv head is query position r % S_v (rows stack as
        # [group member, S_v]); padded rows compute garbage sliced off
        q_pos = length + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 0) % s_v
        valid = (k_pos < t_real) & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # fully-masked rows keep m_new == NEG_INF; exp(s - m_new) would
        # be exp(0)=1 there, so zero masked entries explicitly
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # fold the per-token v scale into p so the int8 payload
            # feeds the dot un-materialized (the einsum path's
            # probs_s = probs * v_scales trick)
            pv = (p * vs_ref[0, 0]).astype(q.dtype)
        else:
            pv = p.astype(q.dtype)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            pv, v_ref[0].astype(q.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # whole block beyond the deepest query position of this slot → skip
    # (block 0 always computes: length >= 0 keys at least position 0)
    @pl.when(k_start <= length + s_v - 1)
    def _():
        compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_decode_attention(q, k, v, lengths, *, k_scale=None, v_scale=None,
                           scale=None, block_kv=None, interpret=None,
                           tables=None):
    """Fused GQA decode/verify attention over a KV cache slab.

    q: [B, S_v, heads, hd] (model dtype); k/v: [B, T, kv_heads, hd] —
    the span-sliced cache slab, int8 (with k_scale/v_scale
    [B, T, kv_heads] f32) or float; lengths: [B] int32 — query row i of
    slot b attends key positions <= lengths[b] + i. Returns
    [B, S_v, heads, hd] in q.dtype.

    T is padded up to a block multiple only when it isn't one already
    (toy test dims; the engine's span menu is powers of two >= 128,
    which the default block divides — no production pad, no copy).

    PAGED mode (ISSUE 19): with `tables` [B, n_blocks_per_slot] int32,
    k/v are the block POOL `[N_blocks, bt, kv_heads, hd]` (scales
    `[N_blocks, bt, kv_heads]`) and slot b's logical span is its
    table's blocks concatenated. The grid already walks (slot, kv_head,
    kv_block); paged just indirects the kv-block axis of the k/v/scale
    BlockSpecs through the scalar-prefetched table — the kernel body,
    its masking, and the online-softmax recurrence are byte-identical
    to slab mode, which is what keeps the layouts parity-comparable.
    """
    b, s_v, nh, hd = q.shape
    paged = tables is not None
    nkv = k.shape[-2]
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
        # table width — always block-aligned, so no pad path exists
        n_pool, block_kv = k.shape[0], k.shape[1]
        if tables.shape[0] != b:
            raise ValueError(f"tables rows {tables.shape[0]} != batch {b}")
        n_k = tables.shape[1]
        t = t_pad = n_k * block_kv
    else:
        t = k.shape[1]
        block_kv = DEFAULT_BLOCK_KV if block_kv is None else block_kv
        block_kv = min(block_kv, _round_up(t, 128))
        t_pad = _round_up(t, block_kv)
        if t_pad != t:
            pad = ((0, 0), (0, t_pad - t), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            if quantized:
                spad = ((0, 0), (0, t_pad - t), (0, 0))
                k_scale = jnp.pad(k_scale, spad)
                v_scale = jnp.pad(v_scale, spad)
        n_k = t_pad // block_kv

    # regroup q heads onto their kv heads: [B, S_v, nh, hd] →
    # [B, kv, g*S_v, hd] (kv-major head split, the verify_inner
    # convention); rows pad to the f32-accumulator sublane floor
    rows = g * s_v
    r_pad = max(8, _round_up(rows, 8))
    qg = jnp.transpose(q.reshape(b, s_v, nkv, g, hd),
                       (0, 2, 3, 1, 4)).reshape(b, nkv, rows, hd)
    if r_pad != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, r_pad - rows), (0, 0)))

    # the kv-head axis folds into the lane dimension via a metadata-only
    # reshape, so the h grid index picks head h's hd-wide column block
    # without ever staging a transposed copy of the payload
    if paged:
        k3 = k.reshape(n_pool, block_kv, nkv * hd)
        v3 = v.reshape(n_pool, block_kv, nkv * hd)
        # the table steers the kv-block axis: grid step (b_, h, j)
        # pipelines pool block tables[b_, j] — the ONLY difference from
        # slab mode, expressed entirely in the index_map
        kv_spec = pl.BlockSpec(
            (1, block_kv, hd),
            lambda b_, h, j, len_ref, tbl_ref: (tbl_ref[b_, j], 0, h))
        sc_spec = pl.BlockSpec(
            (1, 1, 1, block_kv),
            lambda b_, h, j, len_ref, tbl_ref: (tbl_ref[b_, j], h, 0, 0))
    else:
        k3 = k.reshape(b, t_pad, nkv * hd)
        v3 = v.reshape(b, t_pad, nkv * hd)
        kv_spec = pl.BlockSpec((1, block_kv, hd),
                               lambda b_, h, j, *_: (b_, j, h))
        sc_spec = pl.BlockSpec((1, 1, 1, block_kv),
                               lambda b_, h, j, *_: (b_, h, 0, j))

    extra_specs, extra_args = [], []
    if quantized:
        # scales ARE transposed, lane-major per head with a unit
        # sublane axis (slab [B, kv, 1, T] / pool [N, kv, 1, bt]): the
        # (1, block_kv) block tail then equals the array's second-minor
        # dimension, which is what Mosaic's tiling rule asks of a block
        # that is not a multiple of 8 sublanes. 4/hd of the payload bytes.
        extra_specs = [sc_spec, sc_spec]
        extra_args = [
            jnp.swapaxes(sc, -2, -1).astype(jnp.float32)[:, :, None, :]
            for sc in (k_scale, v_scale)]

    prefetch = [jnp.asarray(lengths, jnp.int32)]
    if paged:
        prefetch.append(jnp.asarray(tables, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, r_pad, hd),
                         lambda b_, h, j, *_: (b_, h, 0, 0)),
            kv_spec,
            kv_spec,
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, r_pad, hd),
                               lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, hd), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, s_v=s_v, block_kv=block_kv, t_real=t, scale=scale,
        quantized=quantized, paged=paged)
    itemsize = jnp.dtype(k.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=pallas_compat.sds_with_vma(
            (b, nkv, r_pad, hd), q.dtype, q, k, v),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * nh * s_v * t_pad * hd,
            bytes_accessed=2 * b * t_pad * nkv * hd * itemsize,
            transcendentals=b * nh * s_v * t_pad,
        ),
        interpret=interpret,
    )(*prefetch, qg, k3, v3, *extra_args)
    out = out[:, :, :rows]                           # [B, kv, g*S_v, hd]
    return out.reshape(b, nkv, g, s_v, hd).transpose(
        0, 3, 1, 2, 4).reshape(b, s_v, nh, hd)
