"""What the Pallas kernel modules (flash_pallas, quant_matmul,
flash_decode, flash_prefill) share: the compile-target probe their
selection policies use, the tiling rule of the serving KV layout, and the
vma annotation that makes a pallas_call legal inside a shard_map region.
"""

from __future__ import annotations

import jax

from kubeflow_tpu.parallel.mesh import (get_active_mesh, manual_axis_names,
                                        mesh_shape)


def sds_with_vma(shape, dtype, *xs):
    """ShapeDtypeStruct for a pallas out_shape carrying the union of the
    inputs' varying-manual-axes. Inside a check_vma=True shard_map (e.g.
    a pipeline stage body) a pallas_call output without vma is rejected;
    annotating with the inputs' axes makes the kernels legal in any
    manual region."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in xs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def target_platform() -> str:
    """The platform kernels would COMPILE for: the active mesh's (it may
    be a PJRT *topology* — AOT-compiling for v5e from a CPU-pinned
    process must still pick the kernel path), else the process default
    backend. The ONE platform probe every kernel-selection policy uses,
    so the policies cannot diverge on the AOT/mesh scenario."""
    mesh = get_active_mesh()
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def gspmd_partitioned() -> bool:
    """True when the current trace runs under an active mesh that still
    has an automatic (non-manual) axis of size > 1: XLA will partition
    the program, and a Mosaic custom call has no partitioning rule
    ("Mosaic kernels cannot be automatically partitioned"). Kernels that
    are not wrapped in their own shard_map island must take the XLA
    lowering there."""
    mesh = get_active_mesh()
    if mesh is None:
        return False
    manual = manual_axis_names(mesh)
    return any(size > 1 and name not in manual
               for name, size in mesh_shape(mesh).items())


def flash_kv_refusal(head_dim: int, n_kv_heads: int) -> str | None:
    """Why the compiled serving flash kernels (flash_decode,
    flash_prefill) cannot tile a KV layout, or None when they can. Both
    read head h's keys as the `(1, block_kv, head_dim)` block at lane
    offset `h * head_dim` of the `[B, T, kv_heads * head_dim]` view, and
    Mosaic needs a block's last dimension to be a multiple of 128 lanes
    or the whole array dimension. Interpret mode has no such rule."""
    if head_dim % 128 == 0 or n_kv_heads == 1:
        return None
    return (f"head_dim {head_dim} is not a multiple of 128 lanes (with "
            f"{n_kv_heads} kv heads folded into the lane dimension the "
            "per-head KV block cannot be tiled by Mosaic)")


def resolve_flash_impl(configured: str, *, head_dim: int,
                       n_kv_heads: int) -> str:
    """The one selection policy of both serving attention kernels:
    explicit config ("xla"/"flash"), else "flash" exactly where it
    compiles (a TPU target and a KV layout the kernel tiles), "xla"
    elsewhere. An explicit "flash" at a layout the TPU compiler refuses
    raises with the reason instead of failing at the first prefill.
    Static: engines resolve once at construction."""
    if configured == "xla":
        return "xla"
    on_tpu = target_platform() == "tpu"
    refusal = flash_kv_refusal(head_dim, n_kv_heads) if on_tpu else None
    if configured == "flash":
        if refusal:
            raise ValueError(f"flash attention kernel refused: {refusal}")
        return "flash"
    return "flash" if on_tpu and refusal is None else "xla"
