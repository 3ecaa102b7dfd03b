"""Pallas (Mosaic) TPU flash-attention kernels — the framework's native-compute
hot path for the attention op (SURVEY.md §5.7, §7.3: the "C++-equivalent"
compiled component; the reference delegates attention to user containers, L7).

Forward + backward are hand-written kernels wired through `jax.custom_vjp`:
  - fwd: online-softmax over KV blocks; grid (B*H, n_q, n_kv) with the KV axis
    sequential ("arbitrary") so (acc, m, l) carry across KV blocks in VMEM
    scratch. Emits logsumexp for the backward pass.
  - bwd: two kernels — dq (grid over q blocks, KV sequential) and dk/dv (grid
    over KV blocks, q sequential) — the standard flash-attention backward
    decomposition with delta = rowsum(dO ⊙ O) precomputed in XLA.

Every grid step tells from its own block indices which of three kinds its
[block_q, block_kv] tile is (`_visited`, `_interior`; `block_census` counts
them), and does only that kind's work:
  - interior (no padded key, no segments, and under a causal mask wholly
    below the diagonal): nothing in it can be masked, so no mask is built;
  - diagonal (every other visited tile): the mask and the guard;
  - future (wholly above the diagonal): not computed, and not fetched: the
    index maps hold the sequential index on the nearest block the step's
    row of the grid does visit, so the pipeline sees an unchanged block and
    issues no copy.

Layout contract: BSHD in, GQA already expanded (flash_attention.py repeats KV
heads before calling); q and k share a head size, v and o may have another.
Sequences are padded here to block multiples; padded keys are masked via `k_pos
< sk`, padded query rows sliced off (dO rows zero-padded: no dk/dv from them).

On non-TPU backends the kernels run only in interpreter mode (tests set
FORCE_INTERPRET); otherwise NotImplementedError lets flash_attention.py fall
back to its blockwise-XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat
from kubeflow_tpu.ops.pallas_compat import sds_with_vma as _sds

NEG_INF = -1e30

# Tests on the CPU backend set this to exercise the kernels via the Pallas
# interpreter (numerics identical to the compiled Mosaic path).
FORCE_INTERPRET = False


# every kernel here walks (batch*heads, outer blocks, sequential blocks)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def default_blocks(sq: int, sk: int) -> tuple[int, int]:
    """The kernels' tiles from the two lengths: each length in the fewest
    equal parts of at most 1024, on the 128-lane grid (2048 and 8192 give
    1024, 1536 gives 768 and not a padded second 1024). Measured on a v5e
    with the three kinds of tile in place, 64 heads, forward twice (remat)
    plus backward, ms: S 2048 hd 128: 256 x 512 7.44, 512 x 512 5.98,
    512 x 1024 4.84, 1024 x 1024 4.52, 2048 x 2048 (one tile, with 100 MB
    of VMEM allowed) 5.26; S 8192 q/k 256 v 128: 512 x 1024 73.5,
    1024 x 1024 66.3, 2048 x 1024 (100 MB) 70.9; S 4096: 512 x 1024 15.4,
    1024 x 1024 13.9; S 1024: 256 x 512 2.34, 1024 x 1024 1.59; S 512: one
    tile or two alike. A grid step costs a pass over the [block_q, 128]
    statistics and accumulator whatever block_kv is, so wide tiles win until
    the diagonal's wasted half outweighs it. ONE source of truth: the ring
    body mirrors these."""
    def tile(s):
        return _round_up(-(-s // -(-s // 1024)), 128)
    return tile(sq), tile(sk)


def resolve_blocks(sq, sk, block_q=None, block_kv=None) -> tuple[int, int]:
    """The tiles a call of pallas_flash_attention runs with: the defaults
    unless given, no larger than the lengths rounded up to the lanes."""
    dq_blk, dkv_blk = default_blocks(sq, sk)
    block_q = dq_blk if block_q is None else block_q
    block_kv = dkv_blk if block_kv is None else block_kv
    return (min(block_q, _round_up(sq, 128)),
            min(block_kv, _round_up(sk, 128)))


# ---------------------------------------------------------------------------
# the kind of a tile, from where it lies to the diagonal
# ---------------------------------------------------------------------------
# Written once, on scalars: block_census hands them Python ints, the index
# maps the grid's traced indices, the kernels their program ids. A result is
# a Python bool where the call's shape alone decides it.

def _visited(q_start, k_start, block_q, causal):
    """The q block's last row sees some key of the KV block; a tile that
    fails this is a future tile."""
    return k_start <= q_start + block_q - 1 if causal else True


def _interior(q_start, k_start, block_kv, sk, causal, segmented):
    """Nothing in the (visited) tile can be masked: no segments, no padded
    key, and under a causal mask its last key no later than its first row."""
    if segmented:
        return False
    whole = sk % block_kv == 0 or k_start + block_kv <= sk
    return whole & (k_start + block_kv - 1 <= q_start) if causal else whole


def kv_block_index(i, j, block_q, block_kv, causal, q_offset=0):
    """The KV block grid step (q block i, KV block j) stands on: a future
    step stays on the last block its q block visits (`_visited` solved for
    the KV index)."""
    if not causal:
        return j
    return jnp.minimum(j, (i * block_q + q_offset + block_q - 1) // block_kv)


def q_block_index(i, j, block_q, block_kv, n_q, causal):
    """The q block grid step (KV block j, q block i) of the dK/dV kernel
    stands on: a future step (they come first there) waits on the first
    block its KV block visits (`_visited` solved for the q index; a KV
    block no q block visits stays on the last one)."""
    if not causal:
        return i
    return jnp.maximum(i, jnp.minimum(j * block_kv // block_q, n_q - 1))


def block_census(sq, sk, block_q, block_kv, causal, q_offset=0,
                 segmented=False):
    """(interior, diagonal, future) tiles a head of one call."""
    interior = diagonal = 0
    n_q, n_k = -(-sq // block_q), -(-sk // block_kv)
    for i in range(n_q):
        for j in range(n_k):
            q_start, k_start = i * block_q + q_offset, j * block_kv
            if not _visited(q_start, k_start, block_q, causal):
                continue
            if _interior(q_start, k_start, block_kv, sk, causal, segmented):
                interior += 1
            else:
                diagonal += 1
    return interior, diagonal, n_q * n_k - interior - diagonal


def _mask(q_start, k_start, block_q, block_kv, sk, causal, seg_q_ref,
          seg_k_ref):
    """[block_q, block_kv] bool: the keys a diagonal tile's rows may see."""
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    valid = k_pos < sk
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        valid = valid & (q_pos >= k_pos)
    if seg_q_ref is not None:
        valid = valid & (seg_q_ref[0, 0, 0, :][:, None]
                         == seg_k_ref[0, 0, 0, :][None, :])
    return valid


def _when(pred, body):
    """pl.when for a predicate the call's shape may already have decided."""
    if isinstance(pred, bool):
        if pred:
            body()
    else:
        pl.when(pred)(body)


def _by_kind(visited, interior, compute):
    """Run `compute(masked)` for the kind of tile this grid step holds:
    masked False on an interior tile, True on a diagonal one, not at all on
    a future one."""
    if isinstance(interior, bool):
        _when(visited, functools.partial(compute, not interior))
    else:
        _when(visited & interior, functools.partial(compute, False))
        _when(visited & ~interior, functools.partial(compute, True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref, *rest, scale, causal, block_q,
                block_kv, sk, segmented):
    if segmented:
        (seg_q_ref, seg_k_ref, o_ref, lse_ref,
         acc_ref, m_ref, l_ref) = rest
    else:
        seg_q_ref = seg_k_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = pl.program_id(1) * block_q + qoff_ref[0]
    k_start = ki * block_kv

    def compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if masked:
            s = jnp.where(_mask(q_start, k_start, block_q, block_kv, sk,
                                causal, seg_q_ref, seg_k_ref), s, NEG_INF)

        m_prev = m_ref[:, 0:1]                         # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            # guard: a fully-masked row keeps m_new == NEG_INF; exp(s - m_new)
            # would be exp(0)=1 there, so zero masked entries explicitly.
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _by_kind(_visited(q_start, k_start, block_q, causal),
             _interior(q_start, k_start, block_kv, sk, causal, segmented),
             compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = m_ref[:, 0] + jnp.log(l[:, 0])


def _block_rows(seg, s_pad, block):
    """[BH, S] int32 -> [BH, n, 1, block] padded with -1 (matches no segment);
    the 4D singleton-sublane layout satisfies the TPU tiling rule (like lse)."""
    bh, s = seg.shape
    if s_pad != s:
        seg = jnp.pad(seg, ((0, 0), (0, s_pad - s)), constant_values=-1)
    return seg.reshape(bh, s_pad // block, 1, block)


def _fwd(q, k, v, seg_q, seg_k, causal, scale, q_offset, interpret, block_q,
         block_kv):
    """q,k [BH, S, D], v [BH, S, Dv]; seg_q [BH, Sq], seg_k [BH, Sk] or None."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_kv)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    n_q, n_k = sq_p // block_q, sk_p // block_kv
    segmented = seg_q is not None

    def kv_at(i, j, qoff):
        return kv_block_index(i, j, block_q, block_kv, causal, qoff[0])

    seg_in_specs, seg_args = [], []
    if segmented:
        # seg arrays stay [B, ...] — grid row b (= batch*heads) maps back to
        # its batch via b // heads, so the h head-copies never materialize
        hpb = bh // seg_q.shape[0]
        seg_in_specs = [
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, i, j, *_: (b // hpb, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, i, j, qoff: (b // hpb, kv_at(i, j, qoff),
                                                0, 0)),
        ]
        seg_args = [_block_rows(seg_q, sq_p, block_q),
                    _block_rows(seg_k, sk_p, block_kv)]

    qoff = jnp.asarray([q_offset], jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda b, i, j, qoff: (b, kv_at(i, j, qoff), 0)),
            pl.BlockSpec((1, block_kv, dv),
                         lambda b, i, j, qoff: (b, kv_at(i, j, qoff), 0)),
            *seg_in_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, *_: (b, i, 0)),
            # lse is (BH, n_q, 1, block_q): the singleton sublane dim makes
            # the (1, block_q) block tail legal under the TPU tiling rule.
            pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j, *_: (b, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, sk=sk, segmented=segmented)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _sds((bh, sq_p, dv), q.dtype, q, k, v),
            _sds((bh, n_q, 1, block_q), jnp.float32, q, k, v),
        ],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * sq_p * sk_p * (d + dv),
            bytes_accessed=_io_bytes(bh, sq_p, sk_p, d, dv, q.dtype.itemsize),
            transcendentals=bh * sq_p * sk_p,
        ),
        interpret=interpret,
    )(qoff, q, k, v, *seg_args)
    return o[:, :sq], lse.reshape(bh, sq_p)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_q, block_kv, sk, segmented):
    if segmented:
        seg_q_ref, seg_k_ref, dq_ref, dq_acc = rest
    else:
        seg_q_ref = seg_k_ref = None
        dq_ref, dq_acc = rest
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = pl.program_id(1) * block_q
    k_start = ki * block_kv

    def compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0, 0, :][:, None])
        if masked:
            p = jnp.where(_mask(q_start, k_start, block_q, block_kv, sk,
                                causal, seg_q_ref, seg_k_ref), p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds lacks its factor `scale`: _finalize puts it on the accumulator
        ds = p * (dp - delta_ref[0, 0, 0, :][:, None])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _by_kind(_visited(q_start, k_start, block_q, causal),
             _interior(q_start, k_start, block_kv, sk, causal, segmented),
             compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, block_q, block_kv, sk, segmented):
    if segmented:
        seg_q_ref, seg_k_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        seg_q_ref = seg_k_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = pl.program_id(1) * block_kv

    def compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        p = jnp.exp(s - lse_ref[0, 0, 0, :][:, None])    # [bq, bk]
        if masked:
            p = jnp.where(_mask(q_start, k_start, block_q, block_kv, sk,
                                causal, seg_q_ref, seg_k_ref), p, 0.0)
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        # ds lacks its factor `scale`: _finalize puts it on the accumulator
        ds = p * (dp - delta_ref[0, 0, 0, :][:, None])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]

    _by_kind(_visited(q_start, k_start, block_q, causal),
             _interior(q_start, k_start, block_kv, sk, causal, segmented),
             compute)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, seg_q, seg_k, o, lse, do, causal, scale, interpret,
         block_q, block_kv):
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_kv)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    if sq_p != sq:
        pad = ((0, 0), (0, sq_p - sq), (0, 0))
        q, do = jnp.pad(q, pad), jnp.pad(do, pad)
        delta = jnp.pad(delta, ((0, 0), (0, sq_p - sq)))
    if sk_p != sk:
        pad = ((0, 0), (0, sk_p - sk), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    n_q, n_k = sq_p // block_q, sk_p // block_kv
    # lse comes from _fwd already padded to sq_p; reshape rows into 3D blocks
    # to satisfy the TPU (sublane, lane) tiling rule.
    lse3 = lse.reshape(bh, n_q, 1, block_q)
    delta3 = delta.reshape(bh, n_q, 1, block_q)
    segmented = seg_q is not None
    if segmented:
        seg_q3 = _block_rows(seg_q, sq_p, block_q)
        seg_k3 = _block_rows(seg_k, sk_p, block_kv)

    def kv_at(i, j):
        return kv_block_index(i, j, block_q, block_kv, causal)

    q_spec, do_spec = _row_specs(block_q, (d, dv), lambda b, i, j: (b, i, 0))
    k_sp, v_sp = _row_specs(block_kv, (d, dv),
                            lambda b, i, j: (b, kv_at(i, j), 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                           lambda b, i, j: (b, i, 0, 0))
    seg_specs_dq, seg_args = [], []
    if segmented:
        hpb = bh // seg_q.shape[0]
        seg_specs_dq = [
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, i, j: (b // hpb, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, i, j: (b // hpb, kv_at(i, j), 0, 0)),
        ]
        seg_args = [seg_q3, seg_k3]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, sk=sk,
                          segmented=segmented),
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, k_sp, v_sp, do_spec, row_spec, row_spec,
                  *seg_specs_dq],
        out_specs=q_spec,
        out_shape=_sds((bh, sq_p, d), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse3, delta3, *seg_args)

    def q_at(j, i):
        return q_block_index(i, j, block_q, block_kv, n_q, causal)

    q_kv, do_kv = _row_specs(block_q, (d, dv),
                             lambda b, j, i: (b, q_at(j, i), 0))
    k_spec, v_spec = _row_specs(block_kv, (d, dv), lambda b, j, i: (b, j, 0))
    row_spec_kv = pl.BlockSpec((1, 1, 1, block_q),
                              lambda b, j, i: (b, q_at(j, i), 0, 0))
    seg_specs_kv = []
    if segmented:
        seg_specs_kv = [
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, j, i: (b // hpb, q_at(j, i), 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, j, i: (b // hpb, j, 0, 0)),
        ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, sk=sk,
                          segmented=segmented),
        grid=(bh, n_k, n_q),
        in_specs=[q_kv, k_spec, v_spec, do_kv, row_spec_kv,
                  row_spec_kv, *seg_specs_kv],
        out_specs=[k_spec, v_spec],
        out_shape=[_sds((bh, sk_p, d), k.dtype, q, k, v, do),
                   _sds((bh, sk_p, dv), v.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse3, delta3, *seg_args)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


# ---------------------------------------------------------------------------
# ring-attention building blocks
# ---------------------------------------------------------------------------
# The ring body (ops/ring_attention.py) reuses the SAME kernels per arriving
# KV shard: forward emits per-shard (o, lse) merged across ring steps with
# the online-softmax recurrence; backward reuses the dq/dkv kernels with the
# GLOBAL lse/o — p = exp(s - lse_global) is then the true partial softmax,
# so per-shard grads sum to the exact full-attention gradient.


def flash_fwd_stats(q, k, v, seg_q=None, seg_k=None, *, causal, scale,
                    interpret, block_q=256, block_kv=512):
    """Forward-only (o [BH,S,D] in q.dtype, lse [BH,S] f32)."""
    return _fwd(q, k, v, seg_q, seg_k, causal, scale, 0, interpret,
                block_q, block_kv)


def flash_bwd_grads(q, k, v, seg_q, seg_k, o, lse, do, *, causal, scale,
                    interpret, block_q=256, block_kv=512):
    """(dq, dk, dv) for one q-block/KV-block pair given global (o, lse)."""
    return _bwd(q, k, v, seg_q, seg_k, o, lse, do, causal, scale, interpret,
                block_q, block_kv)


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, seg_q, seg_k, causal, scale, interpret, block_q,
           block_kv):
    o, _ = _fwd(q, k, v, seg_q, seg_k, causal, scale, 0, interpret,
                block_q, block_kv)
    return o


def _flash_fwd(q, k, v, seg_q, seg_k, causal, scale, interpret, block_q,
               block_kv):
    o, lse = _fwd(q, k, v, seg_q, seg_k, causal, scale, 0, interpret,
                  block_q, block_kv)
    return o, (q, k, v, seg_q, seg_k, o, lse)


def _flash_bwd(causal, scale, interpret, block_q, block_kv, res, do):
    q, k, v, seg_q, seg_k, o, lse = res
    dq, dk, dv = _bwd(q, k, v, seg_q, seg_k, o, lse, do, causal, scale,
                      interpret, block_q, block_kv)
    # int arrays carry float0 cotangents; None segments get None back
    dseg_q = (None if seg_q is None
              else np.zeros(seg_q.shape, jax.dtypes.float0))
    dseg_k = (None if seg_k is None
              else np.zeros(seg_k.shape, jax.dtypes.float0))
    return dq, dk, dv, dseg_q, dseg_k


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_flash_attention(q, k, v, *, causal=True, scale=None,
                           q_offset=0, block_q=None, block_kv=None,
                           segment_ids=None, interpret=None):
    """Flash attention via Pallas TPU kernels. BSHD layout, full heads.

    segment_ids: [B, Sk] int32 packed-sequence ids — tokens attend only
    within equal ids (query rows take the id at their absolute position;
    continuation prefill slices at q_offset, matching the blockwise-XLA
    path in flash_attention._blockwise_attn — ops.attention.mha itself
    rejects Sq != Sk with segment_ids).
    Differentiable when `q_offset == 0` (training/prefill-from-zero); the
    decode/prefill-with-offset path is forward-only. Falls back (raises
    NotImplementedError) for tiny query lengths — flash_attention.py routes
    those to the blockwise-XLA path.
    """
    if interpret is None:
        # auto mode: compiled when the COMPILE TARGET is a TPU; off-TPU only
        # when the interpreter was opted into globally, else refuse (the
        # caller takes the blockwise-XLA path)
        if FORCE_INTERPRET:
            interpret = True
        else:
            platform = pallas_compat.target_platform()
            if platform != "tpu":
                raise NotImplementedError(
                    f"pallas flash kernel: target platform {platform!r}")
            interpret = False
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    if sq < 128 or sk < 128:
        raise NotImplementedError("pallas flash kernel needs seq >= 128")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q, block_kv = resolve_blocks(sq, sk, block_q, block_kv)

    qf = _heads_first(q, lanes=128)
    kf = _heads_first(k, lanes=128)
    vf = _heads_first(v)

    seg_q = seg_k = None
    if segment_ids is not None:
        # kept [B, S]: the kernels' BlockSpec index maps fold the grid's
        # batch*heads row back to its batch, so no per-head copies exist
        seg_k = segment_ids.astype(jnp.int32)
        if sq != sk:  # continuation: q rows sit at [q_offset, q_offset+sq)
            seg_q = jax.lax.dynamic_slice_in_dim(seg_k, q_offset, sq, axis=1)
        else:
            seg_q = seg_k

    static_offset = isinstance(q_offset, int)
    if static_offset and q_offset == 0:
        of = _flash(qf, kf, vf, seg_q, seg_k, causal, scale, interpret,
                    block_q, block_kv)
    else:  # decode/continuation prefill: forward-only
        of, _ = _fwd(qf, kf, vf, seg_q, seg_k, causal, scale, q_offset,
                     interpret, block_q, block_kv)
        of = jax.lax.stop_gradient(of)
    return of.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)


# helpers of the entry points above; kept below them so that a change here
# moves no line of the kernels (Mosaic's serialized module carries them)

def _io_bytes(bh, sq, sk, d, dv, itemsize):
    return 2 * bh * (sq * d + sk * (d + dv)) * itemsize


def _row_specs(block, sizes, index_map):
    return [pl.BlockSpec((1, block, size), index_map) for size in sizes]


def _heads_first(x, lanes=None):
    """[B, S, H, D] -> [B * H, S, D]. With `lanes`, a head size off that
    grid (q and k of 192 beside values of 128: latent attention) is padded
    with zeros to the next multiple: they add nothing to q k^T, and the
    scale stays that of the published size."""
    b, s, h, d = x.shape
    if lanes and d % lanes:
        x = jnp.pad(x, ((0, 0),) * 3 + ((0, _round_up(d, lanes) - d),))
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[3])
