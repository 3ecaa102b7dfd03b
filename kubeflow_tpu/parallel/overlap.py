"""Tensor-parallel projections that carry their own exchange.

A megatron layer under plain GSPMD keeps the residual stream replicated
over `tensor` and closes each row-parallel projection with an all-reduce
whose result the very next operation needs: the chip waits for the link
with its MXU idle. Here the residual is sharded along the SEQUENCE over
`tensor` between projections, and each projection moves its share of the
exchange in pieces under its own matmul, in a region that is manual over
`tensor` (`jax.shard_map`):

- column-parallel (`wq|wk|wv`, `w_gate|w_up`; the weight holds a slice of
  the OUTPUT features): `gather_matmul`. The local sequence block is
  multiplied while the next block arrives by `ppermute`; ONE rotation of
  the activation feeds every weight that reads it. Only slicing, no sum
  is reassociated: bit-exact against `all_gather(x) @ w`.
- row-parallel (`wo`, `w_down`; the weight holds a slice of the INPUT
  features, so every chip has a partial sum for every row):
  `matmul_scatter`. The partial for the block that is furthest round the
  ring is computed first and sent on while the next is computed; the
  chip's own block comes last and closes the sum. Equal to
  `psum_scatter(x @ w)`; with two ranks the one addition is exact in
  either order.

The backward of each is JAX's transpose of the same loop (`ppermute`
transposes to `ppermute`), which is the other form. The same bytes cross
the link as under the all-reduces; what changes is that the MXU works
while they cross.

`mesh_for` is the path selection, from what the caller can see alone: no
configuration key names the mechanism. `count_sites` is the census a
Trainer reads while it traces its step (0 sites on a mesh without
`tensor`).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from kubeflow_tpu.parallel.mesh import (get_active_mesh, manual_axis_names,
                                        mesh_shape)

AXIS = "tensor"

# Trace-time census of the overlapped projections by the name their
# caller gave them, kept only while a caller asks (ops/quant.py's
# count_sites is the same idea): a site is traced once a program,
# whatever the scans round it repeat.
_sites: contextvars.ContextVar[set | None] = contextvars.ContextVar(
    "overlap_sites", default=None)


@contextlib.contextmanager
def count_sites():
    """The set of `site` names whose projection traced in the overlapped
    form inside the block (tracing runs in the caller's own context)."""
    sites: set[str] = set()
    token = _sites.set(sites)
    try:
        yield sites
    finally:
        _sites.reset(token)


def mesh_for(seq_len: int, weights: Sequence) -> jax.sharding.Mesh | None:
    """The active mesh where a layer's projections take the overlapped
    form, else None (the caller's plain GSPMD code): `tensor` > 1, no
    axis manual already at this trace point (a pipeline stage body, an
    enclosing shard_map), the sequence not sharded by another axis and
    dividing by `tensor`, every weight a raw array (a quantised or
    adapter leaf is a dict and goes through ops/quant.py)."""
    mesh = get_active_mesh()
    if mesh is None:
        return None
    shape = mesh_shape(mesh)
    size = shape.get(AXIS, 1)
    if (size == 1 or shape.get("sequence", 1) > 1 or seq_len % size
            or manual_axis_names(mesh)
            or not all(isinstance(w, jax.Array) for w in weights)):
        return None
    return mesh


def _ring(axis_name, shift, axis_size, axis_index, step: int):
    """(size, this rank's index, shift) of the ring over `axis_name`; the
    default `shift` hands every rank's value to rank + `step`."""
    size = (int(axis_size) if axis_size is not None
            else jax.lax.axis_size(axis_name))
    idx = (axis_index if axis_index is not None
           else jax.lax.axis_index(axis_name))
    if shift is None:
        perm = [(i, (i + step) % size) for i in range(size)]

        def shift(cur):
            return jax.lax.ppermute(cur, axis_name, perm)
    return size, idx, shift


def _site(form: str, site: str | None):
    """Enter `site` in the census; the named scope that the form's
    operations carry into a capture (`tf_op`)."""
    sites = _sites.get()
    if sites is not None and site is not None:
        sites.add(site)
    return jax.named_scope(f"{form}.{site}" if site else form)


def gather_matmul(x_shard: jax.Array, w, *, axis: int = 0,
                  axis_name: str = AXIS, blocks: bool = False,
                  shift: Callable[[jax.Array], jax.Array] | None = None,
                  axis_size: int | None = None, axis_index=None,
                  site: str | None = None):
    """`all_gather(x, axis) @ w` with the gather in pieces under the
    matmul. `x_shard` is block `idx` of x along `axis`; `w` is one weight
    `[in, out]` or a tuple of them (one rotation feeds all; the result is
    then a tuple). After j rotations a rank holds block (idx + j) % size:
    it is multiplied while the next one arrives.

    `blocks=True` returns the list of the `size` per-block products in
    that ring order instead of the assembled array: a consumer that works
    a block at a time (`matmul_scatter`) needs no assembly.

    `shift`/`axis_size`/`axis_index` are injectable so that the schedule
    is testable in one process (a closure hands over successive blocks);
    inside shard_map leave them None: receive from the next rank."""
    size, idx, shift = _ring(axis_name, shift, axis_size, axis_index, -1)
    many = isinstance(w, (tuple, list))
    ws = tuple(w) if many else (w,)
    with _site("gather_matmul", site):
        parts, cur = [], x_shard
        for j in range(size):
            nxt = shift(cur) if j != size - 1 else None
            parts.append(tuple(cur @ wi for wi in ws))
            cur = nxt
        if blocks:
            return [p if many else p[0] for p in parts]
        rows = x_shard.shape[axis]
        outs = []
        for i in range(len(ws)):
            shape = list(parts[0][i].shape)
            shape[axis] = rows * size
            out = jnp.zeros(shape, parts[0][i].dtype)
            for j, p in enumerate(parts):
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, p[i], ((idx + j) % size) * rows, axis=axis)
            outs.append(out)
    return tuple(outs) if many else outs[0]


def matmul_scatter(x, w: jax.Array, *, axis: int = 0,
                   axis_name: str = AXIS,
                   shift: Callable[[jax.Array], jax.Array] | None = None,
                   axis_size: int | None = None, axis_index=None,
                   site: str | None = None) -> jax.Array:
    """`psum_scatter(x @ w, scatter_dimension=axis, tiled=True)` with the
    exchange in pieces under the matmul. `x` holds this rank's slice of
    the contracted features for EVERY block along `axis`: an array, or
    `gather_matmul(..., blocks=True)`'s list in its ring order. The sum
    for block b starts on rank b + 1 and walks the ring to rank b: at
    step j a rank multiplies block (idx - 1 - j) % size, adds what
    arrived and sends it on; the last step is its own block.

    `shift` as in `gather_matmul`; the default sends to the next rank."""
    size, idx, shift = _ring(axis_name, shift, axis_size, axis_index, 1)
    if isinstance(x, (tuple, list)):
        def block(j):          # ring position p holds block idx + p
            return x[size - 1 - j]
    else:
        rows = x.shape[axis] // size

        def block(j):
            return jax.lax.dynamic_slice_in_dim(
                x, ((idx - 1 - j) % size) * rows, rows, axis=axis)
    with _site("matmul_scatter", site):
        acc = None
        for j in range(size):
            arrived = shift(acc) if j else None
            part = block(j) @ w
            acc = part if arrived is None else part + arrived
    return acc
