"""Chunked gated delta rule with a per-channel decay (Kimi Delta Attention).

Per head, with a state S in R^{dk x dv}, S_0 = 0:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(g_t <= 0 is the log-decay of every key channel, beta_t in (0, 1)). Nothing
here walks the positions one by one. A chunk of C = 64 positions with the
state S at its start and G_r = sum_{i<=r} g_i inside it gives

    A_ri = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <  r)
    B_ri = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <= r)
    M    = (I + Diag(beta) A)^-1 Diag(beta)             (the UT transform)
    U    = M V - M (K * exp(G)) S                       (beta_t times the "new values")
    O    = (Q * exp(G)) S + B U
    S'   = Diag(exp(G_C)) S + (K * exp(G_C - G))^T U

`exp(-G_i)` overflows float32 inside a chunk once the decay is strong, so A
and B are never computed as (K exp(G)) (K exp(-G))^T: the chunk is cut into
sub-blocks of 16 rows, a pair of different sub-blocks takes the first row of
the later one as its reference point (both factors are then <= 1), and the
pairs inside one sub-block are summed channel by channel with the exponent
taken of the difference itself. No exponent anywhere is positive.

Three stages, forward:
  1. `_intra_pallas`: A and B of every chunk (Pallas).
  2. `_ut_pallas`: the inverse X = (I + Diag(beta) A)^-1 of the unit lower
     triangle and M = X Diag(beta), UT_CHUNKS chunks a grid step in VMEM
     (Pallas): the two diagonal blocks of 32 rows by forward substitution
     on the vector unit, then the last step by halves, two 64 x 64 matmuls
     on the MXU; float32, matmuls at HIGHEST.
  3. `_state_pallas`: chunks in order, the state carried in VMEM (Pallas);
     on the way it can write the state at every chunk's start.
Stage 2 runs under the named scope `kda_solve` and the backward under
`kda_backward`: a capture's device operations carry them, and the benchmark
reads their shares of the device's time (benchmark/lib/xscopes.py).

Backward (`custom_vjp`), two kernels, fed by residuals of the forward (M, B,
X and the state at every chunk's start); nothing of stages 1-2 is re-run.
`_state_bwd_pallas` walks the chunks in reverse with the state's cotangent
in VMEM and writes the cotangents of `_prepare`'s six results (Qg, W, Uv, B,
Kd, gamma) heads first. `_prepare_bwd_pallas` then differentiates stages 1-2
by hand, one chunk a grid step: dM = dW (K exp G)^T + dUv V^T; the UT
transform in closed form, dL = -X^T dX X^T with dX = dM Diag(beta)
(`_ut_cotangents`: two matmuls, not the six steps transposed); and the sums
A and B transposed against the same sub-blocks, reference points and
clamped exponents as the forward, so that no channel-by-channel tensor
leaves VMEM. Since A and B do not depend on a reference point, G's cotangent
is q dq + k dk (rows) - k dk (columns) there, summed in reverse inside the
chunk for g's.

The kernels run where they compile (a TPU target) and, for the tests, under
the interpreter (FORCE_INTERPRET, as in ops/flash_pallas.py). Elsewhere the
forward is `_prepare` with `_states_xla`, stage 2 there `_ut_transform` (by
halves in six steps of two 64 x 64 matmuls, XLA), and the backward the
reverse scan `_state_bwd_xla` fed by `_prepare`, then `jax.vjp` of
`_prepare` (the same mathematics in jax.numpy) BACKWARD_GROUP chunks of
every head at a time: the CPU path, which nothing selects by hand, and the
kernels' oracle in the tests. Each traced forward appends its solve's path
to TRACED_SOLVE, each traced backward its own to TRACED_BACKWARD.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat
from kubeflow_tpu.ops.pallas_compat import sds_with_vma as _sds

CHUNK = 64
SUB = 16          # rows of a sub-block: one reference point each
# off the TPU: chunks of every head that the backward differentiates
# `_prepare` for at once (`back`, and the `_prepare` ahead of the scan)
BACKWARD_GROUP = 8
UT_CHUNKS = 16    # chunks a grid step of `_ut_pallas` inverts
HIGHEST = jax.lax.Precision.HIGHEST

# Tests on the CPU set this to run the kernels under the Pallas interpreter.
FORCE_INTERPRET = False

#: the path each traced backward took, "kernel" or "xla": a Trainer reads
#: the slice its own step's trace added (as flash_attention.TRACED_CENSUS)
TRACED_BACKWARD: list[str] = []
#: the same for each traced forward's solve (stage 2)
TRACED_SOLVE: list[str] = []


def _mm(x, y, dims, precision=None):
    return jax.lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# stage 1: A and B inside every chunk
# ---------------------------------------------------------------------------

def _intra_kernel(q_ref, k_ref, g_ref, a_ref, b_ref, *, chunk, sub, mm_dtype):
    q = q_ref[0].astype(jnp.float32)          # [C, dk]
    k = k_ref[0].astype(jnp.float32)
    g = g_ref[0]                              # cumulative log-decay, f32
    ns = chunk // sub
    dk = q.shape[-1]

    # pairs of different sub-blocks: rows of sub-block I against every
    # column, reference point the first row of I; columns that are not
    # before I are masked below (their exponent is clamped, not used)
    a_rows, b_rows = [], []
    for i in range(ns):
        ref = g[i * sub:i * sub + 1, :]                       # [1, dk]
        decay = jnp.exp(g[i * sub:(i + 1) * sub, :] - ref)    # <= 1
        rows = jnp.concatenate(
            [k[i * sub:(i + 1) * sub, :] * decay,
             q[i * sub:(i + 1) * sub, :] * decay], axis=0)    # [2 sub, dk]
        cols = k * jnp.exp(jnp.minimum(ref - g, 0.0))         # [C, dk]
        ab = jax.lax.dot_general(
            rows.astype(mm_dtype), cols.astype(mm_dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [2 sub, C]
        a_rows.append(ab[:sub])
        b_rows.append(ab[sub:])
    a_off = jnp.concatenate(a_rows, axis=0)                   # [C, C]
    b_off = jnp.concatenate(b_rows, axis=0)

    # pairs inside one sub-block: column i of every sub-block at once,
    # the exponent of the difference itself
    q3 = q.reshape(ns, sub, dk)
    k3 = k.reshape(ns, sub, dk)
    g3 = g.reshape(ns, sub, dk)
    col = jax.lax.broadcasted_iota(jnp.int32, (ns, sub, chunk), 2)
    blk = jax.lax.broadcasted_iota(jnp.int32, (ns, sub, chunk), 0)
    a_dg = jnp.zeros((ns, sub, chunk), jnp.float32)
    b_dg = jnp.zeros((ns, sub, chunk), jnp.float32)
    for i in range(sub):
        ki = k3[:, i:i + 1, :]                                # [ns, 1, dk]
        e = jnp.exp(jnp.minimum(g3 - g3[:, i:i + 1, :], 0.0)) * ki
        a_col = jnp.sum(k3 * e, axis=-1, keepdims=True)       # [ns, sub, 1]
        b_col = jnp.sum(q3 * e, axis=-1, keepdims=True)
        here = col == blk * sub + i
        a_dg = jnp.where(here, a_col, a_dg)
        b_dg = jnp.where(here, b_col, b_dg)
    a_dg = a_dg.reshape(chunk, chunk)
    b_dg = b_dg.reshape(chunk, chunk)

    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    same = (r // sub) == (c // sub)
    before = (c // sub) < (r // sub)
    a_ref[0, 0] = jnp.where(same & (c < r), a_dg,
                            jnp.where(before, a_off, 0.0))
    b_ref[0, 0] = jnp.where(same & (c <= r), b_dg,
                            jnp.where(before, b_off, 0.0))


def _intra_pallas(q, k, gc, *, interpret, mm_dtype):
    """q, k [BH, S, dk], gc [BH, S, dk] (cumulative inside each chunk) ->
    A, B [BH, S / C, C, C] float32."""
    bh, s, dk = q.shape
    nc = s // CHUNK
    row = pl.BlockSpec((1, CHUNK, dk), lambda b, c: (b, c, 0))
    sq = pl.BlockSpec((1, 1, CHUNK, CHUNK), lambda b, c: (b, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_intra_kernel, chunk=CHUNK, sub=SUB,
                          mm_dtype=mm_dtype),
        grid=(bh, nc),
        in_specs=[row, row, row],
        out_specs=[sq, sq],
        out_shape=[_sds((bh, nc, CHUNK, CHUNK), jnp.float32, q, k, gc),
                   _sds((bh, nc, CHUNK, CHUNK), jnp.float32, q, k, gc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q, k, gc)


def _intra_xla(q, k, gc, mm_dtype):
    """The same sums in jax.numpy: q, k, gc [n, C, dk] -> A, B [n, C, C]."""
    n, chunk, dk = q.shape
    ns = chunk // SUB
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    q4, k4, g4 = (x.reshape(n, ns, SUB, dk) for x in (q, k, gc))
    ref = g4[:, :, :1, :]                                     # [n, ns, 1, dk]
    decay = jnp.exp(g4 - ref)
    cols = k[:, None] * jnp.exp(
        jnp.minimum(ref - gc[:, None], 0.0))                  # [n, ns, C, dk]

    def off(rows):
        return jnp.einsum("nisd,nicd->nisc", (rows * decay).astype(mm_dtype),
                          cols.astype(mm_dtype), precision=HIGHEST,
                          preferred_element_type=jnp.float32
                          ).reshape(n, chunk, chunk)

    diff = g4[:, :, :, None, :] - g4[:, :, None, :, :]        # [n,ns,r,i,dk]
    e = jnp.exp(jnp.minimum(diff, 0.0)) * k4[:, :, None, :, :]

    def diag(rows):
        d = jnp.sum(rows[:, :, :, None, :] * e, axis=-1)      # [n,ns,r,i]
        eye = jnp.eye(ns, dtype=d.dtype)[None, :, None, :, None]
        return (d[:, :, :, None, :] * eye).reshape(n, chunk, chunk)

    r = jnp.arange(chunk)[:, None]
    c = jnp.arange(chunk)[None, :]
    same, before = (r // SUB) == (c // SUB), (c // SUB) < (r // SUB)
    a = jnp.where(same & (c < r), diag(k4), jnp.where(before, off(k4), 0.0))
    b = jnp.where(same & (c <= r), diag(q4), jnp.where(before, off(q4), 0.0))
    return a, b


# ---------------------------------------------------------------------------
# stage 2: the UT transform
# ---------------------------------------------------------------------------

def _ut_transform(a, beta):
    """-> M = X Diag(beta) and X = (I + Diag(beta) A)^-1 for A strictly
    lower, [.., C, C]. The inverse of a unit lower triangle by halves: with
    the diagonal blocks of size b inverted (X), those of size 2b are
    X - X L21 X."""
    chunk = a.shape[-1]
    r = jnp.arange(chunk)[:, None]
    c = jnp.arange(chunk)[None, :]
    with jax.named_scope("kda_solve"):
        low = beta[..., :, None] * a
        x = jnp.broadcast_to(jnp.eye(chunk, dtype=jnp.float32), a.shape)
        b = 1
        while b < chunk:
            l21 = ((r // (2 * b) == c // (2 * b)) & (r % (2 * b) >= b)
                   & (c % (2 * b) < b))
            mid = jnp.matmul(jnp.where(l21, low, 0.0), x, precision=HIGHEST)
            x = x - jnp.matmul(x, mid, precision=HIGHEST)
            b *= 2
        return x * beta[..., None, :], x


def _ut_kernel(a_ref, beta_ref, m_ref, x_ref, *, chunk, half):
    """Stage 2 for the chunks of one grid step, in VMEM: L = Diag(beta) A;
    its two diagonal blocks of `half` rows inverted by forward substitution
    on the vector unit (row i of every block is final at step i, and then
    leaves its multiple of L's column i in the rows below it), then the
    last step by halves on the MXU, X21 = -X22 L21 X11. Float32 throughout,
    matmuls at HIGHEST."""
    n = a_ref.shape[1]
    beta = beta_ref[0]                                        # [n, C]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, chunk, chunk), 1)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, chunk, chunk), 2)
    beta_r = jnp.sum(jnp.where(r == c, beta[:, None, :], 0.0), axis=2,
                     keepdims=True)                           # [n, C, 1]
    low = beta_r * a_ref[0]

    shape = (n, chunk // half, half, chunk)                   # [n, block, row, C]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    blk = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    l4 = jnp.where(col // half == blk, low.reshape(shape), 0.0)
    x = jnp.where(col == blk * half + row, 1.0, 0.0)
    for i in range(half - 1):
        li = jnp.sum(jnp.where(col == blk * half + i, l4, 0.0), axis=-1,
                     keepdims=True)                           # L's column i
        # rows up to i are final: the update starts at the tile of 8 rows
        # (float32's sublanes) that holds row i + 1
        lo = (i + 1) // 8 * 8
        below = x[:, :, lo:] - li[:, :, lo:] * x[:, :, i:i + 1]
        x = jnp.concatenate([x[:, :, :lo], below], axis=2) if lo else below
    x = x.reshape(n, chunk, chunk)

    bmm = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    l21 = (r >= half) & (c < half)
    x = x - bmm(x, bmm(jnp.where(l21, low, 0.0), x))
    x_ref[0] = x
    m_ref[0] = x * beta[:, None, :]


def _ut_pallas(a, beta, *, interpret):
    """Stage 2 as one kernel: A [BH, NC, C, C] as `_intra_pallas` writes it
    and beta [BH, NC, C] -> M = X Diag(beta) and X = (I + Diag(beta) A)^-1,
    float32 [BH, NC, C, C]; UT_CHUNKS chunks a grid step (the last step's
    block may run past NC: its chunks there are never written)."""
    bh, nc = a.shape[:2]
    n = min(UT_CHUNKS, nc)
    sq = pl.BlockSpec((1, n, CHUNK, CHUNK), lambda i, j: (i, j, 0, 0))
    with jax.named_scope("kda_solve"):
        return pl.pallas_call(
            functools.partial(_ut_kernel, chunk=CHUNK, half=CHUNK // 2),
            grid=(bh, pl.cdiv(nc, n)),
            in_specs=[sq, pl.BlockSpec((1, n, CHUNK), lambda i, j: (i, j, 0))],
            out_specs=[sq, sq],
            out_shape=[_sds(a.shape, jnp.float32, a, beta)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(a, beta)


def _ut_cotangents(x, beta, dm):
    """Stage 2 differentiated in closed form, one chunk: X and beta [1, C]
    as `_ut_transform` took and made them and the cotangent of M = X
    Diag(beta) -> that of L = Diag(beta) A, dL = -X^T dX X^T with dX = dM
    Diag(beta), masked to the strict lower triangle, and beta's through M's
    columns, sum_r dM_rc X_rc [1, C]. A's cotangent is then Diag(beta) dL,
    and beta's takes sum_c dL_rc A_rc besides. Two matmuls at HIGHEST in
    place of the six steps re-run and transposed."""
    chunk = x.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    xt_dx = _mm(x, dm * beta, ((0,), (0,)), HIGHEST)          # X^T dX
    dl = -_mm(xt_dx, x, ((1,), (1,)), HIGHEST)                # .. X^T
    return jnp.where(c < r, dl, 0.0), jnp.sum(dm * x, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# stage 3: the chunks in order
# ---------------------------------------------------------------------------

def _walk_operands(q_ref, k_ref, v_ref, g_ref, m_ref, st, chunk, mm_dtype):
    """What either walk over the chunks forms in VMEM from a chunk's blocks
    and the state S^T [dv, dk] at its start: Q exp(G), K exp(G_C - G),
    W = M (K exp G), S^T and U, each rounded to mm_dtype where a matmul takes
    it (sums in float32), and exp(G_C) [1, dk]."""
    g = g_ref[0]                                # [C, dk] f32
    gam = jnp.exp(g)
    g_last = g[chunk - 1:chunk, :]
    kf = k_ref[0].astype(jnp.float32)
    kg = (kf * gam).astype(mm_dtype)
    qg = (q_ref[0].astype(jnp.float32) * gam).astype(mm_dtype)
    kd = (kf * jnp.exp(g_last - g)).astype(mm_dtype)
    m = m_ref[0, 0].astype(mm_dtype)
    sb = st.astype(mm_dtype)
    w = _mm(m, kg, ((1,), (0,))).astype(mm_dtype)            # [C, dk]
    uv = _mm(m, v_ref[0].astype(mm_dtype), ((1,), (0,)))
    ub = (uv - _mm(w, sb, ((1,), (1,)))).astype(mm_dtype)    # [C, dv]
    return qg, kd, w, sb, ub, jnp.exp(g_last)


def _state_kernel(q_ref, k_ref, v_ref, g_ref, m_ref, b_ref, *rest, chunk,
                  mm_dtype, emit_states):
    if emit_states:
        o_ref, h_ref, st_ref = rest
    else:
        (o_ref, st_ref), h_ref = rest, None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        st_ref[:] = jnp.zeros_like(st_ref)

    st = st_ref[:]                              # S^T [dv, dk], f32
    if emit_states:
        h_ref[0, 0] = st
    qg, kd, _, sb, ub, decay = _walk_operands(q_ref, k_ref, v_ref, g_ref,
                                              m_ref, st, chunk, mm_dtype)
    o = _mm(qg, sb, ((1,), (1,))) + _mm(b_ref[0, 0].astype(mm_dtype), ub,
                                        ((1,), (0,)))
    o_ref[0] = o.astype(o_ref.dtype)
    st_ref[:] = st * decay + _mm(ub, kd, ((0,), (0,)))


def _state_pallas(q, k, v, gc, m, b, *, emit_states, interpret, mm_dtype):
    """-> o [BH, S, dv] (and the state S^T at every chunk's start,
    [BH, S / C, dv, dk] float32)."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    nc = s // CHUNK
    row = lambda d: pl.BlockSpec((1, CHUNK, d), lambda i, c: (i, c, 0))
    sq = pl.BlockSpec((1, 1, CHUNK, CHUNK), lambda i, c: (i, c, 0, 0))
    out_specs = [row(dv)]
    out_shape = [_sds((bh, s, dv), v.dtype, q, k, v, gc)]
    if emit_states:
        out_specs.append(pl.BlockSpec((1, 1, dv, dk),
                                      lambda i, c: (i, c, 0, 0)))
        out_shape.append(_sds((bh, nc, dv, dk), jnp.float32, q, k, v, gc))
    out = pl.pallas_call(
        functools.partial(_state_kernel, chunk=CHUNK, mm_dtype=mm_dtype,
                          emit_states=emit_states),
        grid=(bh, nc),
        in_specs=[row(dk), row(dk), row(dv), row(dk), sq, sq],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, gc, m, b)
    return (out[0], out[1]) if emit_states else (out[0], None)


def _state_bwd_kernel(q_ref, k_ref, v_ref, g_ref, m_ref, b_ref, h_ref, do_ref,
                      dqg_ref, dw_ref, duv_ref, db_ref, dkd_ref, dgam_ref,
                      ds_ref, *, chunk, mm_dtype):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    st = h_ref[0, 0]                            # S^T at the chunk's start, f32
    ds = ds_ref[:]                              # cotangent of S^T AFTER it
    qg, kd, w, sb, ub, decay = _walk_operands(q_ref, k_ref, v_ref, g_ref,
                                              m_ref, st, chunk, mm_dtype)
    dsb = ds.astype(mm_dtype)
    do = do_ref[0].astype(mm_dtype)
    du = (_mm(b_ref[0, 0].astype(mm_dtype), do, ((0,), (0,)))
          + _mm(kd, dsb, ((1,), (1,))))                      # [C, dv]
    dub = du.astype(mm_dtype)
    dqg_ref[0, 0] = _mm(do, sb, ((1,), (0,)))
    dw_ref[0, 0] = -_mm(dub, sb, ((1,), (0,)))
    duv_ref[0, 0] = du
    db_ref[0, 0] = _mm(do, ub, ((1,), (1,)))
    dkd_ref[0, 0] = _mm(ub, dsb, ((1,), (0,)))
    dgam_ref[0, 0] = jnp.sum(ds * st, axis=0, keepdims=True)
    ds_ref[:] = (_mm(do, qg, ((0,), (0,))) + ds * decay
                 - _mm(dub, w, ((0,), (0,))))


def _state_bwd_pallas(q, k, v, gc, m, b, h, do, *, interpret, mm_dtype):
    """Stage 3's cotangents, chunks in reverse: the operands of
    `_state_pallas`, the state S^T at every chunk's start (h) and the
    cotangent of o -> those of `_prepare`'s (Qg, W, Uv, B, Kd, gamma),
    float32, heads first as `_prepare_bwd_pallas` reads them: [BH, NC, C, ..]
    (gamma [BH, NC, dk])."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    nc = s // CHUNK
    ins = (q, k, v, gc, m, b, h, do)

    def at(i, c):         # grid step c of head i is chunk nc - 1 - c
        return i, nc - 1 - c

    row = lambda d: pl.BlockSpec((1, CHUNK, d), lambda i, c: (*at(i, c), 0))
    per_chunk = lambda r, d: pl.BlockSpec((1, 1, r, d),
                                          lambda i, c: (*at(i, c), 0, 0))
    out = lambda r, d: (per_chunk(r, d),
                        _sds((bh, nc, r, d), jnp.float32, *ins))
    out_specs, out_shape = zip(out(CHUNK, dk), out(CHUNK, dk), out(CHUNK, dv),
                               out(CHUNK, CHUNK), out(CHUNK, dk), out(1, dk))
    *d_ops, dgam = pl.pallas_call(
        functools.partial(_state_bwd_kernel, chunk=CHUNK, mm_dtype=mm_dtype),
        grid=(bh, nc),
        in_specs=[row(dk), row(dk), row(dv), row(dk),
                  per_chunk(CHUNK, CHUNK), per_chunk(CHUNK, CHUNK),
                  per_chunk(dv, dk), row(dv)],
        out_specs=list(out_specs),
        out_shape=list(out_shape),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*ins)
    return (*d_ops, dgam[:, :, 0])


# ---------------------------------------------------------------------------
# stages 1-2, backward: the chunk sums and the UT transform by hand
# ---------------------------------------------------------------------------

def _mm_cot(x, y, dims, mm_dtype):
    """A float32 cotangent (x or y) against an operand in mm_dtype (the
    other): below float32 the cotangent goes to the MXU in two mm_dtype
    parts, its rounding and what that left, so the product keeps ~16 bits
    of it, sums in float32."""
    if mm_dtype == jnp.float32:
        return _mm(x, y, dims, HIGHEST)
    f32 = jnp.float32

    def split(t):
        hi = t.astype(mm_dtype)
        return hi, (t - hi.astype(f32)).astype(mm_dtype)

    if x.dtype == f32:
        return sum(_mm(p, y, dims) for p in split(x))
    return sum(_mm(x, p, dims) for p in split(y))


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, x_ref,
                        dqg_ref, dw_ref, duv_ref, db_ref, dkd_ref, dgam_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, chunk,
                        sub, mm_dtype):
    f32 = jnp.float32
    lo = lambda t: t.astype(mm_dtype)
    q = q_ref[0].astype(f32)                  # [C, dk]
    k = k_ref[0].astype(f32)
    g = g_ref[0]                              # cumulative log-decay G, f32
    beta = beta_ref[0, 0]                     # [1, C]
    x = x_ref[0, 0]                           # X = (I + Diag(beta) A)^-1
    ns = chunk // sub
    dk = q.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    beta_r = jnp.sum(jnp.where(r == c, beta, 0.0), axis=1,
                     keepdims=True)           # the same, a column [C, 1]

    # W = M (K exp G) and Uv = M V as the forward's walk formed them, then
    # the closed form of stage 2
    gam = jnp.exp(g)
    m = lo(x * beta)
    dw, duv = dw_ref[0, 0], duv_ref[0, 0]
    dm = (_mm_cot(dw, lo(k * gam), ((1,), (1,)), mm_dtype)
          + _mm_cot(duv, lo(v_ref[0]), ((1,), (1,)), mm_dtype))
    d_kg = _mm_cot(m, dw, ((0,), (0,)), mm_dtype)             # M^T dW
    dv_ref[0] = _mm_cot(m, duv, ((0,), (0,)), mm_dtype).astype(dv_ref.dtype)
    dl, dbeta = _ut_cotangents(x, beta, dm)   # dA = Diag(beta) dL
    same = (r // sub) == (c // sub)
    db = jnp.where(c <= r, db_ref[0, 0], 0.0)

    # pairs inside one sub-block: `_intra_kernel`'s column loop transposed,
    # column i of every sub-block at once; dlk = sum_i dL_ri k_i e_ri, so
    # that A's row side is Diag(beta) dlk and sum_c dL_rc A_rc = k_r . dlk_r
    q3 = q.reshape(ns, sub, dk)
    k3 = k.reshape(ns, sub, dk)
    g3 = g.reshape(ns, sub, dk)
    beta3 = beta_r.reshape(ns, sub, 1)
    dl3 = jnp.where(same, dl, 0.0).reshape(ns, sub, chunk)
    db3 = jnp.where(same, db, 0.0).reshape(ns, sub, chunk)
    col = jax.lax.broadcasted_iota(jnp.int32, (ns, sub, chunk), 2)
    blk = jax.lax.broadcasted_iota(jnp.int32, (ns, sub, chunk), 0)
    at_row = jax.lax.broadcasted_iota(jnp.int32, (ns, sub, dk), 1)
    dlk = jnp.zeros((ns, sub, dk), f32)
    dq_in = jnp.zeros((ns, sub, dk), f32)
    dk_col = jnp.zeros((ns, sub, dk), f32)
    for i in range(sub):
        here = col == blk * sub + i
        dl_i = jnp.sum(jnp.where(here, dl3, 0.0), axis=-1, keepdims=True)
        db_i = jnp.sum(jnp.where(here, db3, 0.0), axis=-1, keepdims=True)
        e = jnp.exp(jnp.minimum(g3 - g3[:, i:i + 1, :], 0.0))
        ek = e * k3[:, i:i + 1, :]
        dlk = dlk + dl_i * ek
        dq_in = dq_in + db_i * ek
        to_i = jnp.sum((db_i * q3 + beta3 * dl_i * k3) * e, axis=1,
                       keepdims=True)                         # [ns, 1, dk]
        dk_col = jnp.where(at_row == i, to_i, dk_col)
    dlk = dlk.reshape(chunk, dk)
    dq_in = dq_in.reshape(chunk, dk)
    dk_col = dk_col.reshape(chunk, dk)
    dbeta_r = jnp.sum(k * dlk, axis=1, keepdims=True)         # [C, 1]

    # pairs of different sub-blocks: `_intra_kernel`'s matmuls transposed,
    # against the same rows and columns, mm_dtype operands
    zeros = jnp.zeros((sub, dk), f32)
    off_lk, off_q, off_beta = [zeros], [zeros], [jnp.zeros((sub, 1), f32)]
    for i in range(1, ns):
        rows = slice(i * sub, (i + 1) * sub)
        ref = g[i * sub:i * sub + 1, :]
        decay = jnp.exp(g[rows] - ref)
        to_cols = jnp.exp(jnp.minimum(ref - g, 0.0))
        cols = lo(k * to_cols)
        rows_k, rows_q = lo(k[rows] * decay), lo(q[rows] * decay)
        # (an iota of its own: Mosaic refuses a slice of one)
        before = jax.lax.broadcasted_iota(jnp.int32, (sub, chunk), 1) < (
            i * sub)
        dl_i = jnp.where(before, dl[rows], 0.0)                # [sub, C]
        db_i = jnp.where(before, db[rows], 0.0)
        p = _mm_cot(jnp.concatenate([dl_i, db_i], axis=0), cols,
                    ((1,), (0,)), mm_dtype)                    # [2 sub, dk]
        d_cols = _mm_cot(jnp.concatenate([beta_r[rows] * dl_i, db_i], axis=0),
                         jnp.concatenate([rows_k, rows_q], axis=0),
                         ((0,), (0,)), mm_dtype)               # [C, dk]
        off_lk.append(p[:sub] * decay)
        off_q.append(p[sub:] * decay)
        off_beta.append(jnp.sum(rows_k.astype(f32) * p[:sub], axis=1,
                                keepdims=True))
        dk_col = dk_col + d_cols * to_cols
    dk_row = beta_r * (dlk + jnp.concatenate(off_lk, axis=0))
    dq_in = dq_in + jnp.concatenate(off_q, axis=0)
    dbeta_r = dbeta_r + jnp.concatenate(off_beta, axis=0)
    dbeta = dbeta + jnp.sum(jnp.where(r == c, dbeta_r, 0.0), axis=0,
                            keepdims=True)

    # the elementwise terms, and G's cotangent: A and B do not depend on the
    # reference points, so there G_r takes q_r dq_r + k_r dk_r (k_r as a
    # row) - k_r dk_r (as a column); then g's, summed in reverse in the chunk
    dqg, dkd = dqg_ref[0, 0], dkd_ref[0, 0]
    g_last = g[chunk - 1:chunk, :]
    to_last = jnp.exp(g_last - g)
    kd = dkd * k * to_last                    # dKd * Kd
    dq_ref[0] = (dqg * gam + dq_in).astype(dq_ref.dtype)
    dk_ref[0] = (d_kg * gam + dkd * to_last + dk_row
                 + dk_col).astype(dk_ref.dtype)
    d_gc = (dqg * q * gam + d_kg * k * gam - kd + q * dq_in + k * dk_row
            - k * dk_col)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0) == chunk - 1
    d_gc = d_gc + jnp.where(last, jnp.sum(kd, axis=0, keepdims=True)
                            + dgam_ref[0, 0] * jnp.exp(g_last), 0.0)
    dg_ref[0] = _mm_cot(lo(jnp.where(c >= r, 1.0, 0.0)), d_gc, ((1,), (0,)),
                        mm_dtype)
    dbeta_ref[0, 0] = dbeta


def _prepare_bwd_pallas(q, k, v, gc, beta, x, d_ops, *, interpret,
                        mm_dtype):
    """Stages 1-2 differentiated by hand, chunk by chunk, fed by the forward's
    X and stage 3's six cotangents (`_state_bwd_pallas`) -> those of q, k, v
    (in their dtypes), g [BH, S, dk] and beta [BH, S] (float32)."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    nc = s // CHUNK
    dqg, dw, duv, db, dkd, dgam = d_ops
    ins = (q, k, v, gc, beta.reshape(bh, nc, 1, CHUNK), x, dqg, dw, duv, db,
           dkd, dgam.reshape(bh, nc, 1, dk))
    row = lambda d: pl.BlockSpec((1, CHUNK, d), lambda i, c: (i, c, 0))
    per_chunk = lambda r, d: pl.BlockSpec((1, 1, r, d),
                                          lambda i, c: (i, c, 0, 0))
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_prepare_bwd_kernel, chunk=CHUNK, sub=SUB,
                          mm_dtype=mm_dtype),
        grid=(bh, nc),
        in_specs=[row(dk), row(dk), row(dv), row(dk), per_chunk(1, CHUNK),
                  per_chunk(CHUNK, CHUNK), per_chunk(CHUNK, dk),
                  per_chunk(CHUNK, dk), per_chunk(CHUNK, dv),
                  per_chunk(CHUNK, CHUNK), per_chunk(CHUNK, dk),
                  per_chunk(1, dk)],
        out_specs=[row(dk), row(dk), row(dv), row(dk), per_chunk(1, CHUNK)],
        out_shape=[_sds(q.shape, q.dtype, *ins), _sds(k.shape, k.dtype, *ins),
                   _sds(v.shape, v.dtype, *ins),
                   _sds(gc.shape, jnp.float32, *ins),
                   _sds((bh, nc, 1, CHUNK), jnp.float32, *ins)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*ins)
    return dq, dk_, dv_, dg, dbeta.reshape(bh, s)


def _prepare(q, k, v, g, beta, mm_dtype):
    """Stages 1-2 and the operands of stage 3 in jax.numpy, for chunks
    [n, C, d]: (Qg, W, Uv, B, Kd, gamma). Differentiated by the backward."""
    gc = jnp.cumsum(g, axis=1)
    a, b = _intra_xla(q, k, gc, mm_dtype)
    m = _ut_transform(a, beta)[0].astype(mm_dtype)
    gam = jnp.exp(gc)
    kf = k.astype(jnp.float32)
    mm = functools.partial(jnp.matmul, precision=HIGHEST,
                           preferred_element_type=jnp.float32)
    w = mm(m, (kf * gam).astype(mm_dtype))
    uv = mm(m, v.astype(mm_dtype))
    qg = q.astype(jnp.float32) * gam
    kd = kf * jnp.exp(gc[:, -1:, :] - gc)
    return qg, w, uv, b, kd, jnp.exp(gc[:, -1, :])


def _states_xla(ops, mm_dtype):
    """Stage 3 in jax.numpy over [BH, NC, ...] operands: o, and S at every
    chunk's start."""
    qg, w, uv, b, kd, gam = ops
    bh, _, _, dk = qg.shape
    dv = uv.shape[-1]
    lo = lambda x: x.astype(mm_dtype)
    mm = functools.partial(jnp.einsum, precision=HIGHEST,
                           preferred_element_type=jnp.float32)

    def step(s, x):
        qg, w, uv, b, kd, gam = x
        sb = lo(s)
        u = uv - mm("ncd,nde->nce", lo(w), sb)
        o = mm("ncd,nde->nce", lo(qg), sb) + mm("ncj,nje->nce", lo(b), lo(u))
        return s * gam[:, :, None] + mm("ncd,nce->nde", lo(kd), lo(u)), (o, s)

    _, (o, h) = jax.lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32),
                             jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0),
                                          (qg, w, uv, b, kd, gam)))
    return jnp.moveaxis(o, 0, 1), jnp.moveaxis(h, 0, 1)


def _state_bwd_xla(ops, st, do, mm_dtype):
    """Stage 3's cotangents in jax.numpy, a reverse scan over the chunks:
    `_prepare`'s results, S at every chunk's start and the cotangent of o,
    all [BH, NC, ..] -> the cotangents of `_prepare`'s results."""
    bh, _, dk, dv = st.shape
    lo = lambda x: x.astype(mm_dtype)
    mm = functools.partial(jnp.einsum, precision=HIGHEST,
                           preferred_element_type=jnp.float32)

    def step(ds, x):    # ds: cotangent of the state AFTER this chunk
        qg, w, uv, b, kd, gam, s, do = x
        sb = lo(s)
        u = lo(uv - mm("ncd,nde->nce", lo(w), sb))
        du = mm("ncj,nce->nje", lo(b), lo(do)) + mm("ncd,nde->nce", lo(kd),
                                                    lo(ds))
        d_ops = (mm("nce,nde->ncd", lo(do), sb),            # d Qg
                 -mm("nce,nde->ncd", lo(du), sb),           # d W
                 du,                                        # d Uv
                 mm("nce,nje->ncj", lo(do), u),             # d B
                 mm("nce,nde->ncd", u, lo(ds)),             # d Kd
                 jnp.sum(ds * s, axis=-1))                  # d gamma
        ds = (mm("ncd,nce->nde", lo(qg), lo(do)) + ds * gam[:, :, None]
              - mm("ncd,nce->nde", lo(w), lo(du)))
        return ds, d_ops

    chunks_first = lambda t: jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), t)
    _, d_ops = jax.lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32),
                            chunks_first((*ops, st, do)), reverse=True)
    return jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), d_ops)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def _chunks(x):
    bh, s = x.shape[:2]
    return x.reshape(bh * (s // CHUNK), CHUNK, *x.shape[2:])


def _cumulative(g):
    """The log-decay summed inside each chunk: [BH, S, dk] -> the same."""
    bh, s, dk = g.shape
    return jnp.cumsum(g.reshape(bh, s // CHUNK, CHUNK, dk),
                      axis=2).reshape(bh, s, dk)


def _forward(q, k, v, g, beta, pallas, interpret, mm_dtype, emit_states):
    """-> o, the state S^T at every chunk's start (if asked for), and on the
    kernels' path the M and B that stage 3 read and X (the backward reads
    them again)."""
    bh, s, dk = q.shape
    nc = s // CHUNK
    TRACED_SOLVE.append("kernel" if pallas else "xla")
    if not pallas:
        ops = _prepare(*(_chunks(x) for x in (q, k, v, g, beta)), mm_dtype)
        ops = jax.tree.map(lambda x: x.reshape(bh, nc, *x.shape[1:]), ops)
        o, h = _states_xla(ops, mm_dtype)
        return (o.reshape(bh, s, -1).astype(v.dtype),
                jnp.swapaxes(h, -1, -2), None)
    gc = _cumulative(g)
    a, b = _intra_pallas(q, k, gc, interpret=interpret, mm_dtype=mm_dtype)
    m, x = _ut_pallas(a, beta.reshape(bh, nc, CHUNK), interpret=interpret)
    o, h = _state_pallas(q, k, v, gc, m, b, emit_states=emit_states,
                         interpret=interpret, mm_dtype=mm_dtype)
    return o, h, (m, b, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, pallas, interpret, mm_dtype):
    return _forward(q, k, v, g, beta, pallas, interpret, mm_dtype, False)[0]


def _kda_fwd(q, k, v, g, beta, pallas, interpret, mm_dtype):
    o, h, mbx = _forward(q, k, v, g, beta, pallas, interpret, mm_dtype, True)
    return o, (q, k, v, g, beta, h, mbx)


def _kda_bwd(pallas, interpret, mm_dtype, res, do):
    TRACED_BACKWARD.append("kernel" if pallas else "xla")
    with jax.named_scope("kda_backward"):
        return _backward(pallas, interpret, mm_dtype, res, do)


def _backward(pallas, interpret, mm_dtype, res, do):
    """One state per chunk (h, written by the forward). On the kernels'
    path two kernels: stage 3's cotangents, chunks in reverse, fed by the
    forward's M and B, then stages 1-2's, chunk by chunk, fed by its X.
    Elsewhere the reverse scan, fed by `_prepare`, then stages 1-2
    differentiated by JAX, BACKWARD_GROUP chunks of every head at a time."""
    q, k, v, g, beta, h, mbx = res
    if pallas:
        m, b, x = mbx
        gc = _cumulative(g)
        d_ops = _state_bwd_pallas(q, k, v, gc, m, b, h, do,
                                  interpret=interpret, mm_dtype=mm_dtype)
        return _prepare_bwd_pallas(q, k, v, gc, beta, x, d_ops,
                                   interpret=interpret, mm_dtype=mm_dtype)
    bh, s, dk = q.shape
    nc = s // CHUNK
    group = min(BACKWARD_GROUP, nc)
    while nc % group:
        group -= 1
    ng = nc // group

    def to_groups(x):     # [BH, NC, ..] -> [ng, BH * group, ..]
        x = x.reshape(bh, ng, group, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0).reshape(ng, bh * group, *x.shape[3:])

    def from_groups(x):   # [ng, BH * group, ..] -> [BH, NC, ..]
        x = x.reshape(ng, bh, group, *x.shape[2:])
        return jnp.moveaxis(x, 0, 1).reshape(bh, nc, *x.shape[3:])

    def grouped(x):       # [BH, S, ..] -> [ng, BH * group, C, ..]
        return to_groups(x.reshape(bh, nc, CHUNK, *x.shape[2:]))

    xs = tuple(grouped(x) for x in (q, k, v, g, beta))
    prepare = lambda *x: _prepare(*x, mm_dtype)
    ops = jax.tree.map(from_groups, jax.lax.map(lambda x: prepare(*x), xs))
    d_ops = _state_bwd_xla(
        ops, jnp.swapaxes(h, -1, -2),                # S [BH, NC, dk, dv]
        do.astype(jnp.float32).reshape(bh, nc, CHUNK, -1), mm_dtype)

    def back(x):
        ins, cts = x
        return jax.vjp(prepare, *ins)[1](cts)

    grads = jax.lax.map(back, (xs, jax.tree.map(to_groups, d_ops)))

    return tuple(from_groups(x).reshape(like.shape).astype(like.dtype)
                 for x, like in zip(grads, (q, k, v, g, beta)))


_kda.defvjp(_kda_fwd, _kda_bwd)


def _kernels() -> tuple[bool, bool]:
    """(pallas, interpret): the kernels where they compile (a TPU target) or
    where the interpreter was asked for, jax.numpy elsewhere."""
    if FORCE_INTERPRET:
        return True, True
    return pallas_compat.target_platform() == "tpu", False


def chunk_kda(q, k, v, g, beta, *, mm_dtype=jnp.bfloat16):
    """q, k [B, S, H, dk] (q scaled, both l2-normalised by the caller),
    v [B, S, H, dv], g [B, S, H, dk] float32 log-decay (<= 0), beta
    [B, S, H] -> o [B, S, H, dv]. S is padded to a multiple of 64 with
    positions that change nothing (g = 0, beta = 0, k = 0)."""
    b, s, h, dk = q.shape
    pad = -s % CHUNK
    heads_first = lambda x: jnp.moveaxis(x, 2, 1).reshape(
        b * h, s, *x.shape[3:])
    args = [heads_first(x) for x in (q, k, v, g.astype(jnp.float32),
                                     beta.astype(jnp.float32))]
    if pad:
        args = [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in args]
    o = _kda(*args, *_kernels(), jnp.dtype(mm_dtype))
    o = o[:, :s].reshape(b, h, s, -1)
    return jnp.moveaxis(o, 1, 2)
