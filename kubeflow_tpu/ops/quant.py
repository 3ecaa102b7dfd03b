"""Weight-only int8 quantization for serving (SURVEY.md §2.4: the
Triton-LLM runtime slot ships quantized serving; here it is a framework
primitive shaped for the TPU).

Decode is HBM-bound: every step re-reads all weights for a handful of
tokens, so int8 storage cuts the dominant traffic 2x vs bf16 (4x vs f32)
while the MXU still computes in bf16 — per-output-channel scales keep the
quantization error ~0.4% of each channel's range, the standard weight-only
trade. Activations stay un-quantized (no calibration needed).

A quantized weight is a dict leaf {"q": int8 [..., in, out],
"s": f32 [..., out]}; the matmul helpers below dequantize at the use point
(XLA fuses the int8->bf16 convert + scale into the matmul read).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import math
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops import pallas_compat


def quantize_int8(w: jax.Array) -> dict[str, jax.Array]:
    """Per-output-channel (last axis) symmetric int8 quantization."""
    s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    s = jnp.maximum(s, 1e-8) / 127.0            # [..., 1, out]
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.squeeze(-2).astype(jnp.float32)}  # s: [..., out]


def is_quantized(wt: Any) -> bool:
    return isinstance(wt, dict) and "q" in wt and "s" in wt


# Fused Pallas dequant-matmul for decode-shaped int8 matmuls (few
# activation rows against a whole 2D weight). PROMOTED to the default
# TPU weight-read path in ISSUE 15 (ROADMAP #5: "kernels on by default
# where they win"), behind the same impl-selection mechanism as the
# flash-decode kernel: "pallas" on TPU, "xla" elsewhere and under a
# GSPMD mesh, and USE_PALLAS_DEQUANT=True as the programmatic force-on
# the older tests use. Inside the serving scans the kernel is handed the
# whole stacked leaf and the layer's index (_fused_or_leaf): a layer
# sliced out by the scan reached the custom call as a copy made on every
# decode step, which is what the r2 record's "-17% on scan-of-steps chunk
# programs" was (ops/quant_matmul.py has the measured numbers).
USE_PALLAS_DEQUANT: bool = False


def resolve_quant_matmul_impl() -> str:
    """"pallas" | "xla" — which lowering decode-shaped int8 matmuls take:
    USE_PALLAS_DEQUANT (programmatic force-on) > xla wherever XLA will
    partition the program (an active GSPMD mesh: the Mosaic custom call
    has no partitioning rule, the attention kernels' boundary) >
    platform default (pallas on TPU, xla elsewhere). The probes are the
    mesh-aware ones in ops/pallas_compat that the flash kernels use, so
    the kernel defaults cannot diverge on the AOT-for-TPU-from-CPU
    scenario."""
    if USE_PALLAS_DEQUANT:
        return "pallas"
    if pallas_compat.gspmd_partitioned():
        return "xla"
    return "pallas" if pallas_compat.target_platform() == "tpu" else "xla"


def _pallas_dequant_wanted(x, q) -> bool:
    from kubeflow_tpu.ops import quant_matmul

    if not quant_matmul.kernel_applicable(
            math.prod(x.shape[:-1]), *q.shape[-2:]):
        return False
    if quant_matmul.FORCE_INTERPRET:
        return True
    # forced on but the compile TARGET isn't a TPU: compiled Mosaic
    # cannot lower there — the XLA expression
    return (resolve_quant_matmul_impl() == "pallas"
            and pallas_compat.target_platform() == "tpu")


# Trace-time census of the quantized matmul call sites by the path each
# took ("stacked_kernel" | "kernel_2d" | "xla"), kept only while a caller
# asks: the serving engine counts its warm-up menu and reports it as
# metrics()["quant_matmul_sites"]. A site is traced once per program,
# whatever the scans around it repeat.
_sites: contextvars.ContextVar[collections.Counter | None] = (
    contextvars.ContextVar("quant_matmul_sites", default=None))


@contextlib.contextmanager
def count_sites():
    """Counter, by path, of the quantized matmul sites traced inside the
    block (tracing runs in the caller's own context)."""
    sites: collections.Counter = collections.Counter()
    token = _sites.set(sites)
    try:
        yield sites
    finally:
        _sites.reset(token)


def _fused_or_leaf(x, wt, layer, out_dtype):
    """(kernel result, None) where the fused kernel takes this site, else
    (None, the 2-D {"q", "s"} leaf) for the caller's XLA expression.

    A rank-3 `q` is a whole stack [L, in, out] and comes with its `layer`
    index: the kernel reads that layer in place from the stack
    (quant_matmul._dequant_matmul_stacked). Outside the kernel's gate the
    layer is indexed here, which is what a scan's own slicing of `xs`
    gives: XLA fuses that slice into the dot's operand read."""
    q, s = wt["q"], wt["s"]
    stacked = q.ndim == 3
    if stacked and layer is None:
        raise ValueError("a stacked quantized weight needs its layer index")
    sites = _sites.get()
    if _pallas_dequant_wanted(x, q):
        from kubeflow_tpu.ops import quant_matmul

        if sites is not None:
            sites["stacked_kernel" if stacked else "kernel_2d"] += 1
        return quant_matmul.dequant_matmul(
            x, q, s, out_dtype, layer=layer if stacked else None), None
    if sites is not None:
        sites["xla"] += 1
    if stacked:
        q = jax.lax.dynamic_index_in_dim(q, layer, axis=0, keepdims=False)
        s = jax.lax.dynamic_index_in_dim(s, layer, axis=0, keepdims=False)
    return None, {"q": q, "s": s}


def matmul(x: jax.Array, wt: Any, dtype, layer=None) -> jax.Array:
    """x @ W for a raw or quantized weight leaf (x: [..., in]). The scale
    is applied in f32 and the PRODUCT cast to dtype — casting s itself to
    bf16 first would add a systematic per-channel bias on top of the
    quantization error (s is tiny; this costs nothing). Decode-shaped
    quantized matmuls route through the fused Pallas kernel
    (ops/quant_matmul.py) when resolve_quant_matmul_impl() selects it —
    the TPU default since ISSUE 15; everything else (big prefill rows,
    ragged blocks, non-TPU) takes this XLA lowering. `layer` indexes a
    stacked quantized leaf (_fused_or_leaf)."""
    if is_quantized(wt):
        out, wt = _fused_or_leaf(x, wt, layer, dtype)
        if out is not None:
            return out
        return ((x @ wt["q"].astype(dtype)).astype(jnp.float32)
                * wt["s"]).astype(dtype)
    return x @ wt.astype(dtype)


def matmul_f32_out(x: jax.Array, wt: Any, dtype, layer=None) -> jax.Array:
    """Like matmul but accumulating to f32 (the lm-head contract)."""
    if is_quantized(wt):
        out, wt = _fused_or_leaf(x, wt, layer, jnp.float32)
        if out is not None:
            return out
        out = jnp.einsum("...d,dv->...v", x, wt["q"].astype(dtype),
                         preferred_element_type=jnp.float32)
        return out * wt["s"]
    return jnp.einsum("...d,dv->...v", x, wt.astype(dtype),
                      preferred_element_type=jnp.float32)
