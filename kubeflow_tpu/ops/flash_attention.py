"""Memory-efficient attention: flash-attention algorithm (online softmax over
KV blocks) so the S×S score matrix never materializes.

Two implementations behind one API:
  - ``impl="xla"``: blockwise ``lax.scan`` — pure XLA, differentiable,
    O(S·block) memory, runs anywhere (CPU tests included).
  - ``impl="pallas"``: Mosaic kernel (ops/flash_pallas.py) for the TPU hot
    path. ``impl="auto"`` takes it wherever the kernel accepts the call
    and the blockwise path elsewhere; on a TPU target a refusal is logged
    once with its reason, and ``TRACED_BODIES`` names every body traced.

The reference platform has no attention code at all (compute is delegated to
user containers, SURVEY.md L7); this is one of the framework's native-compute
components replacing what CUDA users get from flash-attn kernels.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.ops import flash_pallas, pallas_compat
from kubeflow_tpu.ops.attention import repeat_kv
from kubeflow_tpu.parallel.mesh import (get_active_mesh,
                                        manual_axis_names as _manual_axis_names,
                                        mesh_shape)

NEG_INF = -1e30

_log = logging.getLogger(__name__)

#: the bodies flash_attention has traced in this process: "pallas" and/or
#: "xla" (blockwise). A caller that must know the kernel is what compiled
#: (chip_smoke.py) reads it after the step has been traced.
TRACED_BODIES: set[str] = set()
#: beside it, what each traced call of the Pallas kernels with a static
#: q_offset found: flash_pallas.block_census's (interior, diagonal, future)
#: tiles a head. A Trainer reads the slice its own step's trace added.
TRACED_CENSUS: list[tuple[int, int, int]] = []
_refusals_logged: set[str] = set()


def _pallas_island(q, k, v, segment_ids, call):
    """Mosaic kernels can't be auto-partitioned by GSPMD: on a sharded mesh
    the kernel must run as a shard_map island with batch over data/fsdp and
    heads over tensor (each device then runs the kernel on its local slice —
    no cross-shard attention math, since seq stays unsharded here; the
    sequence-parallel paths are ring/ulysses). The island wraps exactly the
    mesh axes that are still automatic at this trace point — inside a
    partial-manual region (pipeline stages are manual over `stage` only) it
    nests a shard_map over the remaining auto axes.

    Returns the island output; None when a plain call is right (all relevant
    axes already manual/local or trivial); raises NotImplementedError when
    the kernel cannot run sharded (indivisible shapes, auto seq sharding) so
    the caller falls back to the partitionable blockwise-XLA path."""
    mesh = get_active_mesh()
    if mesh is None:
        return None
    # target-platform gate BEFORE any shard_map construction: aborting a
    # trace mid-shard_map (kernel raising NotImplementedError inside the
    # body) can leave partial state behind — decide early instead
    if not flash_pallas.FORCE_INTERPRET and \
            mesh.devices.flat[0].platform != "tpu":
        raise NotImplementedError(
            "pallas flash kernel: non-TPU mesh target")
    # seq-length gate up here too ("decide early, never abort mid-shard_map"):
    # seq is unsharded in the island, so the global shapes ARE what the
    # kernel would see — raising now routes to the blockwise path without
    # ever constructing the shard_map
    if q.shape[1] < 128 or k.shape[1] < 128:
        raise NotImplementedError("pallas flash kernel needs seq >= 128")
    shape = mesh_shape(mesh)
    manual = _manual_axis_names(mesh)
    batch_axes = tuple(a for a in ("data", "fsdp")
                       if shape.get(a, 1) > 1 and a not in manual)
    head_axes = tuple(a for a in ("tensor",)
                      if shape.get(a, 1) > 1 and a not in manual)
    if not batch_axes and not head_axes:
        return None  # fully local (or single device): plain call is fine
    if shape.get("sequence", 1) > 1 and "sequence" not in manual:
        # auto-sharded seq under jit would make GSPMD partition the kernel
        raise NotImplementedError(
            "pallas flash kernel with auto sequence sharding; "
            "use ring/ulysses attention or the blockwise path")
    b, _, h, _ = q.shape
    n_batch = math.prod(shape[a] for a in batch_axes) if batch_axes else 1
    n_heads = math.prod(shape[a] for a in head_axes) if head_axes else 1
    if b % n_batch or h % n_heads:
        raise NotImplementedError(
            f"pallas flash kernel: b={b}/h={h} not divisible by mesh "
            f"axes {batch_axes + head_axes}")
    spec = P(batch_axes or None, None, head_axes or None, None)
    # the island must leave NOTHING auto: Mosaic custom calls reject even
    # partially-automatic partitioning, so manualize every mesh axis not
    # already manual in the surrounding region (size-1/replicated axes are
    # free — unmentioned in the specs, each shard group just replicates).
    # Inside an existing manual region the nested shard_map must bind to
    # the CONTEXT mesh (the abstract mesh with its Manual axis types), not
    # the concrete Mesh object — mesh=None means "use the context mesh".
    axis_names = frozenset(mesh.axis_names) - manual
    inner_mesh = None if manual else mesh
    if segment_ids is None:
        # check_vma off: the island body is per-shard local math (no
        # collectives), and pallas_call outputs carry no vma annotation
        return jax.shard_map(lambda ql, kl, vl: call(ql, kl, vl),
                             mesh=inner_mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, axis_names=axis_names,
                             check_vma=False)(q, k, v)
    seg_spec = P(batch_axes or None, None)
    return jax.shard_map(
        lambda ql, kl, vl, sl: call(ql, kl, vl, segment_ids=sl),
        mesh=inner_mesh, in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec, axis_names=axis_names,
        check_vma=False)(q, k, v, segment_ids)


def _blockwise_attn(q, k, v, *, causal: bool, scale: float, q_offset,
                    block_kv: int, segment_ids=None):
    """Online-softmax attention for one query block against all KV blocks.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; segment_ids: [B, Sk] or None —
    tokens only attend within equal segment ids (packed-sequence masking).
    Scans KV in blocks of `block_kv`, carrying (acc, row_max, row_sum) — the
    flash-attention recurrence.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n_blocks = max(1, (sk + block_kv - 1) // block_kv)
    pad = n_blocks * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [B,H,Sq,D]
    kb = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, h, n_blocks, block_kv, d)
    vb = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, h, n_blocks, block_kv, v.shape[3])

    q_pos = jnp.arange(sq) + q_offset  # [Sq]
    if segment_ids is not None:
        # pad KV segments with -1 so padded keys never match a query segment;
        # q segments: self-attention ⇒ q row i has the segment of token
        # q_offset+i (decode path passes the full-length seg array).
        seg_k = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-1)
        seg_kb = seg_k.reshape(b, n_blocks, block_kv).transpose(1, 0, 2)
        seg_q = jax.lax.dynamic_slice_in_dim(
            segment_ids, q_offset, sq, axis=1) if sq != sk else segment_ids
    else:
        seg_kb = jnp.zeros((n_blocks, b, block_kv), jnp.int32)
        seg_q = None

    def body(carry, inputs):
        acc, m, s = carry  # [B,H,Sq,D], [B,H,Sq], [B,H,Sq]
        k_blk, v_blk, seg_blk, blk_idx = inputs
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk)  # [B,H,Sq,block]
        k_pos = blk_idx * block_kv + jnp.arange(block_kv)
        valid = (k_pos < sk)[None, :]  # [1, block]
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        valid = jnp.broadcast_to(valid[None], (b, sq, block_kv))
        if seg_q is not None:
            valid = valid & (seg_q[:, :, None] == seg_blk[:, None, :])
        logits = jnp.where(valid[:, None], logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])
        new_s = s * correction + jnp.sum(p, axis=-1)
        new_acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk)
        return (new_acc, new_m, new_s), None

    # carries derived from q (not fresh zeros) so they inherit q's varying
    # manual axes — required when this runs inside a shard_map body (e.g.
    # a pipeline stage), harmless under plain jit
    bhqd = _zeros_like_q(qf, v.shape[3])  # [B,H,Sq,Dv]
    init = (
        bhqd,
        jnp.full_like(bhqd[..., 0], NEG_INF),
        jnp.zeros_like(bhqd[..., 0]),
    )
    (acc, m, s), _ = jax.lax.scan(
        body, init,
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4),
         seg_kb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(s[..., None], 1e-37)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B,Sq,H,D]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int | jax.Array = 0,
    block_kv: int | None = None,  # None = seq-adaptive kernel defaults
    segment_ids: jax.Array | None = None,
    impl: str = "auto",  # auto | pallas | xla
) -> jax.Array:
    """Flash attention, BSHD layout, GQA-aware. Numerically matches ops.mha."""
    h, hkv = q.shape[2], k.shape[2]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)

    if impl in ("auto", "pallas"):
        try:
            kernel_block_kv = None if block_kv is None else max(block_kv, 128)
            call = functools.partial(
                flash_pallas.pallas_flash_attention, causal=causal,
                scale=scale, q_offset=q_offset, block_kv=kernel_block_kv)
            out = None
            if isinstance(q_offset, int) and q_offset == 0:
                out = _pallas_island(q, k, v, segment_ids, call)
            if out is None:
                out = call(q, k, v, segment_ids=segment_ids)
            TRACED_BODIES.add("pallas")
            if isinstance(q_offset, int):
                sq, sk = q.shape[1], k.shape[1]
                TRACED_CENSUS.append(flash_pallas.block_census(
                    sq, sk, *flash_pallas.resolve_blocks(
                        sq, sk, None, kernel_block_kv),
                    causal, q_offset, segmented=segment_ids is not None))
            return out
        except NotImplementedError as e:
            if impl == "pallas":
                raise
            # off-TPU the blockwise path IS the platform's body; on a TPU
            # target a refusal means the chip trains on the slow path, and
            # must say why
            if pallas_compat.target_platform() == "tpu" \
                    and str(e) not in _refusals_logged:
                _refusals_logged.add(str(e))
                _log.warning("flash_attention: Pallas kernel refused on a "
                             "TPU target (%s); tracing the blockwise XLA "
                             "body", e)
    TRACED_BODIES.add("xla")
    block = min(block_kv or 512, k.shape[1])
    return _blockwise_attn(q, k, v, causal=causal, scale=scale,
                           q_offset=q_offset, block_kv=block,
                           segment_ids=segment_ids)


def _zeros_like_q(qf, dv: int):
    """float32 zeros [B, H, Sq, dv] derived from q; v's head size may be
    another than q's (latent attention: 192 beside 128). Below the entry
    point so that no line above it moves."""
    z = jnp.zeros_like(qf, jnp.float32)
    if dv == qf.shape[-1]:
        return z
    return jnp.broadcast_to(z[..., :1], z.shape[:-1] + (dv,))
