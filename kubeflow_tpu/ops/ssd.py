"""Pallas (Mosaic) kernels of a Mamba-2 state-space layer, as a serving
engine runs it: a chunked scan for a prompt and one step of the recurrence
for a decode step. Per head, with a state h of [P, N] (P channels of the
head, N the state size) and the head's group's B and C:

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T        (a < 0, dt_t >= 0)
    y_t = h_t C_t                                     (D x_t: the caller's)

THE SCAN (`ssd_scan`, a prompt): chunks of Q = 128 positions in order, the
state carried across them in VMEM in float32. Inside a chunk with the
cumulative log-decay l_t = sum_{s <= t} dt_s a (l_s >= l_t for s <= t):

    y_t  = sum_{s <= t} (C_t . B_s) exp(l_t - l_s) dt_s x_s
           + exp(l_t) h C_t                           (the state at its start)
    h'   = exp(l_Q) h + sum_s exp(l_Q - l_s) dt_s x_s B_s^T

A grid step is one (sequence, group, chunk): C B^T once for the group's
heads, then each head's masked decay products. No exponent is positive. The
caller's `lengths` zero dt past each row's end, so the bucket's pad neither
decays nor feeds the state, and the state that comes out is the one at the
prompt's true length. It runs under the named scope `ssm_scan`.

THE STEP (`ssm_state_step`, a decode step): the stacked state slab of every
state-space layer `[L, slots, H, P, N]` is the operand and the result
(`input_output_aliases`): the index map picks the layer, the slot and the
group of heads, so one layer's states are read and written where they lie
and nothing of slab size is copied. The grid runs over slots x groups. It
runs under the named scope `ssm_state`.

The kernels run where they compile (a TPU target) and, for the tests, under
the interpreter (FORCE_INTERPRET). Elsewhere the same functions compute the
recurrence position by position (`ssd_recurrence`, `ssm_step_xla`): the CPU
path, which nothing selects by hand, and the kernels' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import pallas_compat
from kubeflow_tpu.ops.pallas_compat import sds_with_vma as _sds

#: positions a scan's grid step takes: the published `chunk_size`
CHUNK = 128

#: the kernels' names, which a device trace shows them by
SCAN_KERNEL = "ssd_chunk_scan"
STEP_KERNEL = "ssm_state_update"

# Tests on the CPU set this to run the kernels under the Pallas interpreter.
FORCE_INTERPRET = False


def _kernels() -> tuple[bool, bool]:
    """(pallas, interpret): the kernels where they compile (a TPU target) or
    where the interpreter was asked for, jax.numpy elsewhere."""
    if FORCE_INTERPRET:
        return True, True
    return pallas_compat.target_platform() == "tpu", False


def _mm(x, y, dims):
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the plain recurrence (the CPU path and the oracle)
# ---------------------------------------------------------------------------

def _heads_of_groups(m, heads: int):
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssd_recurrence(x, dt, a, bm, cm, h0):
    """The recurrence position by position: x [B, S, H, P], dt [B, S, H]
    float32, a [H] float32, bm / cm [B, S, G, N], h0 [B, H, P, N] float32
    -> (y [B, S, H, P] in x.dtype, the state after the last position)."""
    heads = x.shape[2]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h, y = ssm_step_xla(h, xt, dtt, a, bt, ct, heads)
        return h, y

    seq = lambda v: jnp.moveaxis(v, 1, 0)                     # noqa: E731
    h, y = jax.lax.scan(step, h0.astype(jnp.float32),
                        (seq(x), seq(dt), seq(bm), seq(cm)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), h


def ssm_step_xla(h, x, dt, a, bm, cm, heads: int | None = None):
    """One step: h [B, H, P, N] (any float dtype), x [B, H, P], dt [B, H],
    bm / cm [B, G, N] -> (the new state float32, y [B, H, P] float32)."""
    heads = x.shape[1] if heads is None else heads
    bh = _heads_of_groups(bm.astype(jnp.float32), heads)
    ch = _heads_of_groups(cm.astype(jnp.float32), heads)
    dt = dt.astype(jnp.float32)
    h = (jnp.exp(dt * a)[..., None, None] * h.astype(jnp.float32)
         + (dt[..., None] * x.astype(jnp.float32))[..., None]
         * bh[:, :, None, :])
    return h, jnp.einsum("bhpn,bhn->bhp", h, ch,
                         precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------

def _scan_kernel(x_ref, b_ref, c_ref, lc_ref, dc_ref, lr_ref, dr_ref, h0_ref,
                 y_ref, h_ref, st_ref, *, heads, p):
    """One (sequence, group, chunk): x [Q, heads * P], B / C [Q, N], the
    log-decay's cumulative sum and dt as columns [Q, heads] and as rows
    [heads, Q], the state of the group's heads in `st_ref` [heads, P, N]."""
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _init():
        st_ref[:] = h0_ref[0]

    q = x_ref.shape[1]
    mm_dtype = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]
    cb = _mm(cm, bm, ((1,), (1,)))                            # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    lane = lambda rows: jax.lax.broadcasted_iota(               # noqa: E731
        jnp.int32, (rows, q), 1)
    for j in range(heads):
        l_col, d_col = lc_ref[0, 0, :, j:j + 1], dc_ref[0, 0, :, j:j + 1]
        l_row, d_row = lr_ref[0, 0, j:j + 1, :], dr_ref[0, 0, j:j + 1, :]

        def l_last(rows):
            """The chunk's last l as a column [rows, 1]: Mosaic broadcasts
            a [1, 1] along one axis at a time, never into both, and took
            no slice of such a column."""
            return jnp.sum(jnp.where(lane(rows) == q - 1, l_row, 0.0),
                           axis=1, keepdims=True)
        m = cb * jnp.exp(jnp.where(causal, l_col - l_row, -jnp.inf)) * d_row
        xj = x_ref[0, :, j * p:(j + 1) * p]                   # [Q, P]
        h = st_ref[j]                                         # [P, N] f32
        y = (_mm(m.astype(mm_dtype), xj, ((1,), (0,)))
             + jnp.exp(l_col) * _mm(cm, h.astype(mm_dtype), ((1,), (1,))))
        y_ref[0, :, j * p:(j + 1) * p] = y.astype(y_ref.dtype)
        xw = xj.astype(jnp.float32) * (jnp.exp(l_last(q) - l_col) * d_col)
        st_ref[j] = (jnp.exp(l_last(p)) * h
                     + _mm(xw.astype(mm_dtype), bm, ((0,), (0,))))

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _done():
        h_ref[0] = st_ref[:]


def _scan_pallas(x, dt, cum, bm, cm, h0, *, interpret):
    """x [B, S, H, P], dt / cum [B, S, H] float32, bm / cm [B, S, G, N],
    h0 [B, H, P, N] float32; S a multiple of CHUNK."""
    b, s, heads, p = x.shape
    groups, n = bm.shape[2:]
    hg = heads // groups
    nc = s // CHUNK
    cols = lambda v: v.reshape(b, s, groups, hg).transpose(0, 2, 1, 3)  # noqa
    rows = lambda v: v.reshape(b, s, groups, hg).transpose(0, 2, 3, 1)  # noqa
    col_spec = pl.BlockSpec((1, 1, CHUNK, hg), lambda i, g, c: (i, g, c, 0))
    row_spec = pl.BlockSpec((1, 1, hg, CHUNK), lambda i, g, c: (i, g, 0, c))
    state_spec = pl.BlockSpec((1, hg, p, n), lambda i, g, c: (i, g, 0, 0))
    x_spec = pl.BlockSpec((1, CHUNK, hg * p), lambda i, g, c: (i, c, g))
    bc_spec = pl.BlockSpec((1, CHUNK, n), lambda i, g, c: (i, c, g))
    itemsize = jnp.dtype(x.dtype).itemsize
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, heads=hg, p=p),
        name=SCAN_KERNEL,
        grid=(b, groups, nc),
        in_specs=[x_spec, bc_spec, bc_spec, col_spec, col_spec, row_spec,
                  row_spec, state_spec],
        out_specs=[x_spec, state_spec],
        out_shape=[_sds((b, s, heads * p), x.dtype, x, bm, cm, h0),
                   _sds((b, heads, p, n), jnp.float32, x, bm, cm, h0)],
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * (groups * CHUNK * n
                               + heads * (CHUNK * p + 2 * n * p)),
            bytes_accessed=(b * s * (2 * heads * p + 2 * groups * n)
                            * itemsize + 2 * b * heads * p * n * 4),
            transcendentals=b * s * heads * CHUNK),
        interpret=interpret,
    )(x.reshape(b, s, heads * p), bm.reshape(b, s, groups * n),
      cm.reshape(b, s, groups * n), cols(cum), cols(dt), rows(cum), rows(dt),
      h0)
    return y.reshape(b, s, heads, p), h


def ssd_scan(x, dt, a, bm, cm, h0, lengths):
    """A prompt's scan: x [B, S, H, P] (the model dtype), dt [B, S, H]
    (softplus'd), a [H] (negative), bm / cm [B, S, G, N], h0 [B, H, P, N]
    float32 (the state before the first position), lengths [B]: row b's
    positions >= lengths[b] change nothing (dt there is zeroed). Returns
    (y [B, S, H, P] in x.dtype, the state at each row's length [B, H, P,
    N] float32)."""
    b, s = x.shape[:2]
    live = jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)
    a = a.astype(jnp.float32)
    pallas, interpret = _kernels()
    if not pallas:
        return ssd_recurrence(x, dt, a, bm, cm, h0)
    pad = -s % CHUNK
    if pad:
        widen = lambda v: jnp.pad(                              # noqa: E731
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, bm, cm = widen(x), widen(dt), widen(bm), widen(cm)
    ds = dt * a
    chunks = ds.reshape(b, -1, CHUNK, ds.shape[-1])
    cum = jnp.cumsum(chunks, axis=2).reshape(ds.shape)
    y, h = _scan_pallas(x, dt, cum, bm, cm, h0.astype(jnp.float32),
                        interpret=interpret)
    return y[:, :s], h


# ---------------------------------------------------------------------------
# one decode step, in place
# ---------------------------------------------------------------------------

def _step_kernel(x_ref, b_ref, c_ref, dt_ref, dec_ref, st_in_ref, y_ref,
                 st_ref, *, heads):
    """One (slot, group): x [P, heads] (a head a column), B / C [1, N], dt
    and exp(dt a) [1, heads], the group's states [heads, P, N]."""
    bm = b_ref[0, 0].astype(jnp.float32)
    cm = c_ref[0, 0].astype(jnp.float32)
    x = x_ref[0, 0].astype(jnp.float32)
    dt, decay = dt_ref[0, 0], dec_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(heads):
        h = (decay[:, j:j + 1] * st_in_ref[0, 0, j].astype(jnp.float32)
             + (x[:, j:j + 1] * dt[:, j:j + 1]) * bm)         # [P, N]
        st_ref[0, 0, j] = h.astype(st_ref.dtype)
        y = jnp.where(lane == j, jnp.sum(h * cm, axis=1, keepdims=True), y)
    y_ref[0, 0] = y.astype(y_ref.dtype)


def _step_pallas(states, layer: int, x, dt, decay, bm, cm, *, interpret):
    slots, heads, p = x.shape
    groups, n = bm.shape[1:]
    hg = heads // groups
    per_group = lambda v: v.reshape(slots, groups, 1, -1)      # noqa: E731
    xg = x.reshape(slots, groups, hg, p).transpose(0, 1, 3, 2)  # [s,G,P,hg]
    small = lambda w: pl.BlockSpec((1, 1, 1, w),               # noqa: E731
                                   lambda s, g: (s, g, 0, 0))
    x_spec = pl.BlockSpec((1, 1, p, hg), lambda s, g: (s, g, 0, 0))
    st_spec = pl.BlockSpec((1, 1, hg, p, n),
                           lambda s, g: (layer, s, g, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, heads=hg),
        name=STEP_KERNEL,
        grid=(slots, groups),
        in_specs=[x_spec, small(n), small(n), small(hg), small(hg), st_spec],
        out_specs=[x_spec, st_spec],
        out_shape=[_sds((slots, groups, p, hg), jnp.float32, x, states),
                   _sds(states.shape, states.dtype, x, states)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=5 * slots * heads * p * n,
            bytes_accessed=2 * slots * heads * p * n
            * jnp.dtype(states.dtype).itemsize,
            transcendentals=0),
        interpret=interpret,
    )(xg, per_group(bm), per_group(cm), per_group(dt), per_group(decay),
      states)
    return states, y.transpose(0, 1, 3, 2).reshape(slots, heads, p)


def ssm_state_step(states, layer: int, x, dt, a, bm, cm):
    """A decode step of state-space layer `layer` for every slot, in place:
    states [L, slots, H, P, N] (the stacked slab), x [slots, H, P], dt
    [slots, H] (softplus'd), a [H], bm / cm [slots, G, N] -> (the slab with
    layer `layer` advanced one position, y [slots, H, P] float32)."""
    dt = dt.astype(jnp.float32)
    pallas, interpret = _kernels()
    if not pallas:
        h, y = ssm_step_xla(states[layer], x, dt, a.astype(jnp.float32), bm,
                            cm)
        return states.at[layer].set(h.astype(states.dtype)), y
    decay = jnp.exp(dt * a.astype(jnp.float32))
    return _step_pallas(states, layer, x, dt, decay, bm, cm,
                        interpret=interpret)
