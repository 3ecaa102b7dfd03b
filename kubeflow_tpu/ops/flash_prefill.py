"""Pallas (Mosaic) flash chunked-prefill kernel — fused causal attention
for prefill/continuation chunks directly over the serving engine's KV
layout (ISSUE 20, ROADMAP #3).

TTFT is the prefill half of the decode roofline: r14's flash-decode
kernel covered the per-step KV re-read, but every prefill chunk — full
prompts, bucketed continuation chunks, radix prefix-cache-hit starts —
still ran the reference XLA einsum (`mha`), which stages the full
[S_chunk, T] score matrix through HBM at serving dims. This kernel
streams each KV block HBM→VMEM once per q block and runs scores, int8
dequant, online softmax, and the weighted sum in VMEM:

  - **One body for every prefill shape.** q is a chunk
    `[slots, S_chunk, heads, hd]` whose rows sit at absolute positions
    `q_offset + i`; K/V cover positions `0..T-1` (prefix + chunk).
    `q_offset=0` is full prefill, `q_offset=p` a continuation chunk
    after a p-token prefix (the `mha(..., q_offset=p)` hot path in
    `llama.prefill_continue_inner`) — including radix prefix-cache-hit
    starts, where p is the cached-prefix length. `q_offset` is STATIC:
    the engine groups continuation waves by (p, t), so each compiled
    program serves exactly one offset.
  - **The flash_decode layout contract.** K/V arrive as the slab slice
    `[slots, T, kv_heads, hd]` (model dtype or int8 + per-token f32
    scales `[slots, T, kv_heads]`) OR as the paged block pool
    `[N_blocks, bt, kv_heads, hd]` with scalar-prefetched block tables
    steering the kv-block grid axis — byte-identical kernel body either
    way. int8 dequant is fused at the block load (scale folded into
    score/probability), so a dequantized copy never materializes in HBM.
    The kv-head grid axis indexes the payload through a metadata-only
    `[B, T, kv*hd]` reshape; only the tiny scale planes transpose.
  - **GQA inside the kernel.** q heads regroup onto their kv heads on
    the host (`[B, kv, n_q_blocks, g*block_q, hd]` — a reshape of the
    tiny q chunk, not of the cache), so the head-expanded `repeat_kv`
    K/V copy never exists. All g group members of one kv head share one
    q block's mask and ride one matmul.
  - **Online softmax + causal block skip.** grid
    `(B, kv_heads, n_q_blocks, n_kv_blocks)` with the KV axis sequential
    ("arbitrary"): (acc, m, l) carry across KV blocks in VMEM scratch.
    KV blocks entirely above the q block's deepest position
    (`k_start > q_offset + (iq+1)*block_q - 1`) skip their compute —
    the causal triangle at block granularity, which is where chunked
    prefill's ~2x over full-rectangle attention comes from.

Masking is exactly `ops/attention.mha`'s causal rule: key position t is
visible to query row i iff `t <= q_offset + i`. Padded q rows (chunk
padded up to a block multiple) compute garbage that the caller slices
off; padded KV rows mask via `t_real`.

Follows the ops/flash_decode.py precedent exactly: on non-TPU backends
the kernel runs under `interpret=True` (numerics identical to the
compiled Mosaic path), so the byte-level differential gauntlet
(tests/test_flash_prefill.py) runs in the CPU fast lane with no code
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

#: default q-block (chunk rows per grid step) and KV block (tokens per
#: sequential grid step). Serving chunk buckets and spans are powers of
#: two, so the defaults divide them; the wrapper clamps (and pads — the
#: ragged-chunk and toy-dim path) when they don't.
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 256

#: the impl selection (`LlamaConfig.prefill_attention_impl`): an
#: EXPLICIT "flash" | "xla" wins (tests pin impls per engine); "auto"
#: is decided from what the process can observe, the target platform
#: and the KV layout, and from nothing a user sets on the machine
#: (flash_decode.resolve_impl's rule: both kernels tile the same KV
#: layout, so one policy in pallas_compat serves both, and an engine
#: never runs one as a kernel and the other as XLA by accident).


def resolve_impl(configured: str = "auto", *, head_dim: int,
                 n_kv_heads: int) -> str:
    """Selection policy: explicit config ("xla"/"flash") > flash
    where it compiles (TPU target, KV layout the kernel tiles), xla
    elsewhere — see pallas_compat.resolve_flash_impl (it raises on an
    explicit "flash" at a layout the TPU compiler would refuse).
    Static — resolved at trace time, so each engine's compiled
    prefill menu covers exactly one impl."""
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


def _prefill_kernel(*refs, block_q, block_kv, t_real, q_offset, scale,
                    quantized, paged=False, window=None):
    if paged:
        # block-table mode: the table ref is the scalar-prefetch arg —
        # it steers the k/v/scale BlockSpec index_maps (the indirection
        # happens in the pipeline, before the body runs), so the body
        # itself never reads it: by the time a block is in VMEM,
        # k_start below is its LOGICAL span offset either way.
        _tbl_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = j * block_kv
    rows = q_ref.shape[3]          # g * block_q (whole rows are real q
    # rows except the chunk's block pad, which the wrapper slices off)

    def compute():
        q = q_ref[0, 0, 0]                           # [rows, hd]
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
        # row r of this q block is query position
        # q_offset + iq*block_q + r % block_q (rows stack as
        # [group member, block_q] — all g members share the positions)
        q_pos = (q_offset + iq * block_q
                 + jax.lax.broadcasted_iota(
                     jnp.int32, (rows, block_kv), 0) % block_q)
        # mha's causal rule: key t visible to row i iff t <= q_offset+i
        valid = (k_pos < t_real) & (k_pos <= q_pos)
        if window is not None:
            # the lower edge: key t is visible iff q - t < window
            valid = valid & (q_pos - k_pos < window)
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

    # causal block skip: whole KV block above this q block's deepest
    # position — or entirely in the T pad — contributes nothing (block
    # 0 always computes: every q row sees key position 0)
    live = ((k_start <= q_offset + (iq + 1) * block_q - 1)
            & (k_start < t_real))
    if window is not None:
        # ... nor does a KV block wholly below the window of the q
        # block's FIRST row (with a window a row may see nothing in a
        # block that computes: the rows' running max starts at NEG_INF
        # and masked entries are zeroed, so the order is free)
        live = live & (k_start + block_kv - 1
                       >= q_offset + iq * block_q - (window - 1))

    @pl.when(live)
    def _():
        compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, 0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_prefill_attention(q, k, v, *, q_offset=0, k_scale=None,
                            v_scale=None, scale=None, block_q=None,
                            block_kv=None, interpret=None, tables=None,
                            window=None):
    """Fused causal GQA prefill attention for one chunk.

    q: [B, S_chunk, heads, hd] (model dtype) — row i of slot b sits at
    absolute position `q_offset + i`; k/v: [B, T, kv_heads, hd] — prefix
    + chunk KV covering positions 0..T-1, int8 (with k_scale/v_scale
    [B, T, kv_heads] f32) or float. Key position t is visible to row i
    iff `t <= q_offset + i` (ops/attention.mha's causal rule at the
    given offset). `q_offset` must be a python int (static per trace —
    the engine's continuation waves group by (p, t)). Returns
    [B, S_chunk, heads, hd] in q.dtype.

    S_chunk pads up to a q-block multiple and T up to a KV-block
    multiple only when they aren't already (ragged chunks, toy test
    dims; the engine's buckets are powers of two the defaults divide).

    PAGED mode: with `tables` [B, n_blocks] int32, k/v are the block
    POOL `[N_blocks, bt, kv_heads, hd]` (scales `[N_blocks, bt,
    kv_heads]`) and slot b's logical 0..T-1 span is its table's blocks
    concatenated. The kv-block grid axis indirects through the
    scalar-prefetched table exactly like ops/flash_decode; the kernel
    body, its masking, and the online-softmax recurrence are
    byte-identical to slab mode.

    `window` (static; None = causal alone, today's program): key t is
    visible to row i iff `0 <= q_offset + i - t < window`. A KV block
    wholly outside a q block's band — above its last row or below its
    first row's window — is neither computed nor fetched: the index maps
    hold the block coordinate inside the band, and the pipeline issues
    no copy for an unchanged index. Positions are relative: a caller
    that holds only the last P' rows of a prefix passes them as K/V and
    `q_offset=P'`.
    """
    b, s, nh, hd = q.shape
    paged = tables is not None
    nkv = k.shape[-2]
    if nh % nkv:
        raise ValueError(f"heads {nh} must divide by kv_heads {nkv}")
    g = nh // nkv
    q_offset = int(q_offset)
    if q_offset < 0:
        raise ValueError(f"q_offset must be >= 0, got {q_offset}")
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    if window is not None and paged:
        raise ValueError("window attention has no paged form")
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

    # q blocks: the f32-accumulator sublane floor is 8 rows; the chunk
    # pads to a block multiple and the pad rows' garbage is sliced off
    block_q = DEFAULT_BLOCK_Q if block_q is None else block_q
    block_q = max(8, min(_round_up(block_q, 8), _round_up(s, 8)))
    s_pad = _round_up(s, block_q)
    n_q = s_pad // block_q
    rows = g * block_q

    # regroup q heads onto their kv heads AND pre-pack the per-block row
    # layout: [B, S, nh, hd] → [B, kv, g, S_pad, hd] → blocks of
    # [B, kv, n_q, g*block_q, hd] — host-side reshapes of the tiny q
    # chunk (never of the cache), so the kernel reads 2D [rows, hd]
    # tiles with no in-kernel reshuffle.
    qg = jnp.transpose(q.reshape(b, s, nkv, g, hd),
                       (0, 2, 3, 1, 4))              # [B, kv, g, S, hd]
    if s_pad != s:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    qb = jnp.transpose(qg.reshape(b, nkv, g, n_q, block_q, hd),
                       (0, 1, 3, 2, 4, 5)).reshape(
                           b, nkv, n_q, rows, hd)

    # the kv-head axis folds into the lane dimension via a metadata-only
    # reshape, so the h grid index picks head h's hd-wide column block
    # without ever staging a transposed copy of the payload
    if paged:
        k3 = k.reshape(n_pool, block_kv, nkv * hd)
        v3 = v.reshape(n_pool, block_kv, nkv * hd)
        # the table steers the kv-block axis: grid step (b_, h, iq, j)
        # pipelines pool block tables[b_, j] — the ONLY difference from
        # slab mode, expressed entirely in the index_map
        kv_spec = pl.BlockSpec(
            (1, block_kv, hd),
            lambda b_, h, iq, j, tbl_ref: (tbl_ref[b_, j], 0, h))
        sc_spec = pl.BlockSpec(
            (1, 1, 1, block_kv),
            lambda b_, h, iq, j, tbl_ref: (tbl_ref[b_, j], h, 0, 0))
    else:
        k3 = k.reshape(b, t_pad, nkv * hd)
        v3 = v.reshape(b, t_pad, nkv * hd)
        if window is None:
            def band(iq, j):
                return j
        else:
            def band(iq, j):
                # the KV blocks q block iq can see, first to last
                lo = jnp.maximum(q_offset + iq * block_q - (window - 1),
                                 0) // block_kv
                hi = jnp.minimum((q_offset + (iq + 1) * block_q - 1)
                                 // block_kv, n_k - 1)
                return jnp.clip(j, lo, hi)
        kv_spec = pl.BlockSpec(
            (1, block_kv, hd),
            lambda b_, h, iq, j, *_: (b_, band(iq, j), h))
        sc_spec = pl.BlockSpec(
            (1, 1, 1, block_kv),
            lambda b_, h, iq, j, *_: (b_, h, 0, band(iq, j)))

    extra_specs, extra_args = [], []
    if quantized:
        # scales ARE transposed, lane-major per head with a unit
        # sublane axis (slab [B, kv, 1, T] / pool [N, kv, 1, bt]) — the
        # flash_decode scale layout, for the same tiling rule
        extra_specs = [sc_spec, sc_spec]
        extra_args = [
            jnp.swapaxes(sc, -2, -1).astype(jnp.float32)[:, :, None, :]
            for sc in (k_scale, v_scale)]

    prefetch = [jnp.asarray(tables, jnp.int32)] if paged else []
    qo_spec = pl.BlockSpec((1, 1, 1, rows, hd),
                           lambda b_, h, iq, j, *_: (b_, h, iq, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nkv, n_q, n_k),
        in_specs=[qo_spec, kv_spec, kv_spec, *extra_specs],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_kv=block_kv, t_real=t,
        q_offset=q_offset, scale=scale, quantized=quantized, paged=paged,
        **({} if window is None else {"window": int(window)}))
    itemsize = jnp.dtype(k.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=pallas_compat.sds_with_vma(
            (b, nkv, n_q, rows, hd), q.dtype, q, k, v),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * nh * s_pad * t_pad * hd,
            bytes_accessed=2 * b * n_q * t_pad * nkv * hd * itemsize,
            transcendentals=b * nh * s_pad * t_pad,
        ),
        interpret=interpret,
    )(*prefetch, qb, k3, v3, *extra_args)
    # unpack: [B, kv, n_q, g*block_q, hd] → [B, kv, g, S_pad, hd] →
    # slice the chunk pad → [B, S, nh, hd]
    out = jnp.transpose(out.reshape(b, nkv, n_q, g, block_q, hd),
                        (0, 1, 3, 2, 4, 5)).reshape(
                            b, nkv, g, s_pad, hd)[:, :, :, :s]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, nh, hd)
