"""Pallas (Mosaic) fused int8-dequant matmul — the default TPU
weight-read path since ISSUE 15 (XLA's expression elsewhere and under a
mesh; ops/quant.py resolve_quant_matmul_impl is the selection policy).

Decode/verify matmuls are pure bandwidth: a handful of activation rows
(m = slots × verify-positions, 4..~100) against every int8 weight in the
model, every step. XLA's lowering of `x @ q.astype(bf16)` stages a bf16
copy of each weight tile before the dot; on v5e the int8 model streams at
only ~0.65x the bf16 byte rate (202 vs 308 GiB/s at L16 geometry). This
kernel reads the int8 tile HBM→VMEM once, converts in-register,
accumulates f32 across d-blocks in VMEM scratch, and applies the
per-output-channel scale on the last block — the weight's HBM footprint
is its int8 bytes, full stop.

TWO ENTRIES, ONE BODY. _dequant_matmul_2d takes a two-dimensional weight
(the lm_head). _dequant_matmul_stacked takes a whole stack [L, d, o] with
the layer's index as a prefetched scalar and picks the layer in the
BlockSpec index maps of q and s: the serving bodies scan over layers whose
weights are stacked, and nothing may be sliced outside the kernel. Sliced
by the scan, a layer's weights reached the 2-D custom call as the output
of a dynamic-slice; XLA fuses nothing into a custom call, so it
materialised the slice: every decode step copied each layer's int8
weights (read + write) and the kernel then read the copy. On the v5e at
Mistral-7B widths, 8 layers, 16 decode rows (PERF.md, PR 24 and PR 26):
the copies took 3.17 s of a traced 10 s beside 1.51 s in the kernel, a
decode step 11.59 ms; read in place a step takes 6.17 ms, its seven layer
matmuls 2.45 ms for 1.75 GB (2.13 ms at 819 GB/s: 88 % of the HBM roof),
and the p95 gap between tokens fell from 118.3 to 60.5 ms end to end.
That copy is the cause of what an earlier toolchain's record here called
"-17% on the engine's scan-of-steps chunk programs ... the custom call
blocked XLA's cross-iteration weight prefetch". quant.matmul gates on
resolve_quant_matmul_impl(), which reads the target platform and the
ambient mesh and nothing a user sets (or FORCE_INTERPRET in tests); see
ops/quant.py for the policy.

Gating (quant.matmul decides): m ≤ MAX_ROWS (decode/verify shapes; big
prefill batches are compute-bound and XLA's MXU path is fine), block
sizes must divide (d, o) — anything else falls back to the XLA
expression. On non-TPU backends the kernel runs only under FORCE_INTERPRET
(tests); otherwise callers fall back, mirroring ops/flash_pallas.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tests on the CPU backend set this to exercise the kernel via the Pallas
# interpreter (numerics identical to the compiled Mosaic path).
FORCE_INTERPRET = False

# decode/verify row counts; beyond this the matmul is compute-heavy enough
# that XLA's plain MXU path wins and the kernel gate declines
MAX_ROWS = 128

# sublane floor for the padded row dimension (f32 acc tile is (8, 128))
_MIN_M = 8


def _pick_block(dim: int, prefs: tuple[int, ...]) -> int | None:
    for b in prefs:
        if dim % b == 0:
            return b
    return None


def kernel_applicable(m: int, d: int, o: int) -> bool:
    """Static shape gate shared with quant.matmul."""
    return (m <= MAX_ROWS
            and _pick_block(d, (2048, 1024, 512, 256)) is not None
            and _pick_block(o, (512, 384, 256, 128)) is not None)


def _dequant_kernel(*refs, n_d: int, out_dtype):
    # one body for both entries: the stacked entry's refs lead with the
    # prefetched layer index, which only its index maps read
    x_ref, q_ref, s_ref, o_ref, acc_ref = refs[-5:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...]                            # [m_pad, bd] bf16
    qb = q_ref[...].astype(jnp.bfloat16)       # int8 → bf16 in-register
    acc_ref[...] += jax.lax.dot_general(
        xb, qb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_d - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_dtype)


def _pallas_dequant(x, q, s, layer, out_dtype, interpret):
    """The one pallas_call of both entries. x [m, d] bf16, result [m, o].
    `layer` None: q [d, o], s [1, o]. Else q [L, d, o] and s [L, 1, o] are
    whole stacks and `layer` (s32[1]) is prefetched: the index maps of q
    and s pick the layer, their leading block dimension is squeezed, and
    x, the output, the grid, the blocks, the f32 accumulator and the body
    are the same, tile for tile."""
    m, d = x.shape
    o = q.shape[-1]
    block_d = _pick_block(d, (2048, 1024, 512, 256))
    block_o = _pick_block(o, (512, 384, 256, 128))
    m_pad = max(_MIN_M, m)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    n_d, n_o = d // block_d, o // block_o
    if layer is None:
        prefetch, lead, at = (), (), lambda refs: ()
    else:
        prefetch, lead, at = (layer,), (None,), lambda refs: (refs[0][0],)
    # an index map takes the grid indices, then the prefetched refs
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, n_d=n_d, out_dtype=out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_o, n_d),
            in_specs=[
                pl.BlockSpec((m_pad, block_d), lambda i, j, *refs: (0, j)),
                pl.BlockSpec(lead + (block_d, block_o),
                             lambda i, j, *refs: at(refs) + (j, i)),
                pl.BlockSpec(lead + (1, block_o),
                             lambda i, j, *refs: at(refs) + (0, i)),
            ],
            out_specs=pl.BlockSpec((m_pad, block_o),
                                   lambda i, j, *refs: (0, i)),
            scratch_shapes=[pltpu.VMEM((m_pad, block_o), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m_pad, o), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, x, q, s)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _dequant_matmul_2d(x, q, s, *, out_dtype, interpret=False):
    """[m, d] bf16 @ int8 [d, o] (scale [o]) → [m, o] out_dtype."""
    return _pallas_dequant(x, q, s.reshape(1, -1), None, out_dtype,
                           interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _dequant_matmul_stacked(layer, x, q, s, *, out_dtype, interpret=False):
    """[m, d] bf16 @ int8 q[layer] of a stack [L, d, o] (scales [L, o]) →
    [m, o] out_dtype, bit-identical to _dequant_matmul_2d(x, q[l], s[l]).
    The device trace names the custom call after this function."""
    n_l, _, o = q.shape
    return _pallas_dequant(x, q, s.reshape(n_l, 1, o), layer, out_dtype,
                           interpret)


def dequant_matmul(x: jax.Array, q: jax.Array, s: jax.Array,
                   out_dtype, layer: jax.Array | None = None) -> jax.Array:
    """x [..., d] @ {q int8 [d, o], s f32 [o]} → [..., o] out_dtype,
    f32 accumulation, scale applied once per output channel. With `layer`
    (a scalar index), q [L, d, o] and s [L, o] are whole stacks and the
    kernel reads that layer in place. Caller has already checked
    kernel_applicable() on the flattened row count."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.bfloat16)
    kw = dict(out_dtype=jnp.dtype(out_dtype), interpret=FORCE_INTERPRET)
    if layer is None:
        out = _dequant_matmul_2d(x2, q, s, **kw)
    else:
        out = _dequant_matmul_stacked(
            jnp.asarray(layer, jnp.int32).reshape(1), x2, q, s, **kw)
    return out.reshape(*lead, q.shape[-1])
