"""Pallas (Mosaic) fused int8-dequant matmul — the default TPU
weight-read path since ISSUE 15 (env kill-switch KTPU_QUANT_MATMUL=xla;
see ops/quant.py resolve_quant_matmul_impl for the selection policy).

Decode/verify matmuls are pure bandwidth: a handful of activation rows
(m = slots × verify-positions, 4..~100) against every int8 weight in the
model, every step. XLA's lowering of `x @ q.astype(bf16)` stages a bf16
copy of each weight tile before the dot; on v5e the int8 model streams at
only ~0.65x the bf16 byte rate (202 vs 308 GiB/s at L16 geometry). This
kernel reads the int8 tile HBM→VMEM once, converts in-register,
accumulates f32 across d-blocks in VMEM scratch, and applies the
per-output-channel scale on the last block — the weight's HBM footprint
is its int8 bytes, full stop.

MEASURED HISTORY (v5e, 8B geometry, r2 jax): +7% on a single-step
decode program, but -17% on the engine's scan-of-steps chunk programs —
inside the step scan the custom call blocked XLA's cross-iteration
weight prefetch. ISSUE 15 promotes the kernel to the TPU default
anyway, WITH teeth: every bench record carries a serving_kernels A/B on
the same warmed engine (schema 9), so a regression on the current
toolchain shows up as a committed per-bucket delta, and
KTPU_QUANT_MATMUL=xla flips the fleet back without a code push.
quant.matmul gates on resolve_quant_matmul_impl() (or FORCE_INTERPRET
in tests); see ops/quant.py for the policy.

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


def _dequant_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_d: int,
                    out_dtype):
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


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _dequant_matmul_2d(x, q, s, *, out_dtype, interpret=False):
    """[m, d] bf16 @ int8 [d, o] (scale [o]) → [m, o] out_dtype."""
    m, d = x.shape
    o = q.shape[1]
    block_d = _pick_block(d, (2048, 1024, 512, 256))
    block_o = _pick_block(o, (512, 384, 256, 128))
    m_pad = max(_MIN_M, m)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    n_d, n_o = d // block_d, o // block_o
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, n_d=n_d, out_dtype=out_dtype),
        grid=(n_o, n_d),
        in_specs=[
            pl.BlockSpec((m_pad, block_d), lambda i, j: (0, j)),
            pl.BlockSpec((block_d, block_o), lambda i, j: (j, i)),
            pl.BlockSpec((1, block_o), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((m_pad, block_o), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m_pad, o), out_dtype),
        scratch_shapes=[pltpu.VMEM((m_pad, block_o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, q, s.reshape(1, o))
    return out[:m]


def dequant_matmul(x: jax.Array, q: jax.Array, s: jax.Array,
                   out_dtype) -> jax.Array:
    """x [..., d] @ {q int8 [d, o], s f32 [o]} → [..., o] out_dtype,
    f32 accumulation, scale applied once per output channel. Caller has
    already checked kernel_applicable() on the flattened row count."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d).astype(jnp.bfloat16)
    interpret = False
    if FORCE_INTERPRET:
        interpret = True
    out = _dequant_matmul_2d(x2, q, s, out_dtype=jnp.dtype(out_dtype),
                             interpret=interpret)
    return out.reshape(*lead, q.shape[1])
