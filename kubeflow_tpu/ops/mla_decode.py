"""Pallas (Mosaic) absorbed latent-attention decode: every query head of a
slot against ONE shared latent row a token, read from the serving engine's
latent slab where it lies.

Latent attention (MLA) caches per token and layer one row of `latent +
rope` values: the normed KV latent `c_kv` and the rotated key `k_rope` that
every head shares. Decode folds each head's key up-projection into its
query (`q_nope W_UK`, [heads, latent]) and puts the rotated query beside
it, so a head's score against position t is one dot product with the row
`[c_kv | k_rope]`, and its output is the softmax-weighted sum of the rows'
first `latent` columns (the value up-projection `W_UV` comes after, outside
the kernel). Every head reads the same row: the heads are the M dimension
of both matmuls, and the row is fetched once for all of them.

  - **The whole slab is the operand**: `[L, slots, T, latent + rope]`, as
    the decode step carries it. The BlockSpec index map picks the LAYER (a
    prefetched scalar), the SLOT and the KV BLOCK: nothing of cache size is
    sliced, copied or transposed before the call.
  - **Bytes follow the context**: a slot's KV-block coordinate is clamped
    to its last live block (`lengths[b] // block_kv`, prefetched); past it
    the pipeline sees an unchanged block index and issues no copy, and
    `pl.when` skips the compute. A dead slot (length < 0) stands on the
    block the live slot before it ended on and moves no byte
    (ops/flash_decode.py's rule).
  - **Online softmax in float32** over the KV blocks, which run in order
    ("arbitrary"): per head (acc, m, l) carry in VMEM scratch.

Query row h of slot b sees key positions t <= lengths[b] (the engine
writes the step's own row at `lengths[b]` before the call). Off the TPU the
kernel runs under `interpret=True`, with no other fork: the CPU tests run
the body the chip compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat

NEG_INF = -1e30

# Tests on the CPU backend set this to run the kernel in the interpreter.
FORCE_INTERPRET = False

#: tokens a sequential grid step reads: 512 rows of 576 bf16 values are
#: 576 KiB, two in flight; the step costs ~0.35 us whether it computes or
#: not (ops/flash_decode.py's measurement), so a block this long keeps the
#: steps' share small at the contexts a long-prompt mix holds
DEFAULT_BLOCK_KV = 512


def _resolve_interpret(interpret):
    if interpret is not None:
        return interpret
    if FORCE_INTERPRET:
        return True
    return pallas_compat.target_platform() != "tpu"


def _kernel(meta_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_kv, latent, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = meta_ref[b]
    k_start = j * block_kv

    @pl.when((length >= 0) & (k_start <= length))
    def _compute():
        q = q_ref[0]                                          # [H, C]
        kv = kv_ref[0, 0]                                     # [bk, C]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [H, bk]
        seen = (k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                <= length)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                    ).astype(o_ref.dtype)


def mla_decode_attention(q, cache, lengths, *, layer, latent: int,
                         scale: float, span: int | None = None,
                         block_kv: int | None = None, interpret=None):
    """q [B, H, C] (C = latent + rope: the absorbed query beside the rotated
    one, the model dtype); cache: the WHOLE latent slab [L, slots, T, C]
    (the B rows of q are slots 0..B-1); `layer` which of the L (an int or a
    traced int32 scalar); lengths [B] int32: row b sees positions
    <= lengths[b], none where negative; `span` how many of a slot's T rows
    the grid covers (default T). Returns [B, H, latent] in q.dtype."""
    b, h, c = q.shape
    t_cache = cache.shape[2]
    if cache.shape[3] != c:
        raise ValueError(f"query width {c} != latent row {cache.shape[3]}")
    interpret = _resolve_interpret(interpret)
    span = t_cache if span is None else min(span, t_cache)
    block_kv = min(DEFAULT_BLOCK_KV if block_kv is None else block_kv,
                   t_cache)
    n_k = pl.cdiv(span, block_kv)

    # ONE prefetched vector: lengths, the layer, the slot each grid row
    # reads (a dead row: the live row before it) and its last live block
    lengths = jnp.asarray(lengths, jnp.int32)
    slot = jnp.arange(b, dtype=jnp.int32)
    row = jnp.maximum(jax.lax.cummax(jnp.where(lengths >= 0, slot, -1)), 0)
    cap = jnp.clip(lengths // block_kv, 0, n_k - 1)[row]
    meta = jnp.concatenate([lengths, jnp.asarray(layer, jnp.int32)
                            .reshape(1), row, cap])
    row_at, cap_at = b + 1, 2 * b + 1

    def at(b_, j, meta_ref):
        cap = meta_ref[cap_at + b_]
        blk = jnp.where(meta_ref[b_] >= 0, jnp.minimum(j, cap), cap)
        return (meta_ref[b], meta_ref[row_at + b_], blk, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=[pl.BlockSpec((1, h, c), lambda b_, j, *_: (b_, 0, 0)),
                  pl.BlockSpec((1, 1, block_kv, c), at)],
        out_specs=pl.BlockSpec((1, h, latent), lambda b_, j, *_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, latent), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32)],
    )
    itemsize = jnp.dtype(cache.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, block_kv=block_kv, latent=latent,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=pallas_compat.sds_with_vma((b, h, latent), q.dtype, q,
                                             cache),
        # rows in order: a dead row stands on the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * span * (c + latent),
            bytes_accessed=b * span * c * itemsize,
            transcendentals=b * h * span),
        interpret=interpret,
    )(meta, q, cache)


def mla_decode_xla(q, cache, lengths, *, layer, latent: int, scale: float,
                   span: int | None = None):
    """The same attention as einsums over the slab's first `span` rows (the
    path off the TPU, and what the kernel is tested against)."""
    b = q.shape[0]
    span = cache.shape[2] if span is None else min(span, cache.shape[2])
    kv = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    kv = kv[:b, :span]                                        # [B, T, C]
    s = jnp.einsum("bhc,btc->bht", q, kv,
                   preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(span)[None, None, :]
            <= jnp.asarray(lengths, jnp.int32)[:, None, None])
    s = jnp.where(seen, s, NEG_INF)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return jnp.einsum("bht,btc->bhc", p.astype(kv.dtype), kv[..., :latent],
                      preferred_element_type=jnp.float32).astype(q.dtype)
