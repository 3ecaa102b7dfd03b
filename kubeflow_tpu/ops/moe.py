"""Mixture-of-Experts routing + expert-parallel dispatch, TPU-first.

The reference platform has no MoE of its own — expert parallelism is L7 user
code there (SURVEY.md §2.2 parallelism table: "mesh `expert` axis + ragged
all-to-all" is the TPU-native equivalent to build). This module is that
equivalent, in the GShard/Switch formulation that XLA shards well:

  - static expert capacity (TPU = static shapes): each expert processes at
    most C = ceil(top_k * T / E * capacity_factor) tokens; overflow tokens
    are dropped from that expert (their combine weight is 0) — the standard
    trade that keeps every shape static;
  - dispatch/combine are one-hot einsums, NOT gathers: `[T,E,C]` masks
    contracted on the MXU. When the stacked expert weights are sharded over
    the `expert` mesh axis and tokens over `data/fsdp`, GSPMD lowers the
    dispatch einsum to exactly the all-to-all the ragged formulation would
    hand-write — no manual collectives needed;
  - auxiliary load-balance loss (Switch §2.2): E * Σ_e f_e · p_e, and router
    z-loss for logit stability.

Everything is jit/scan/remat-safe (pure functions, static shapes).

A second formulation beside it, `moe_share_mlp`, drops nothing and knows its
share: a sigmoid router over ALL the experts of the layer (top-k of score +
bias, weights renormalised over the chosen and scaled), the assignments
sorted by expert, the rows of the experts HELD HERE gathered, one grouped
matmul per projection over the experts held (megablox `gmm` on a TPU target,
which visits only tiles that hold rows; `jax.lax.ragged_dot` elsewhere), and
the results scattered back with their weights. What the experts held
elsewhere would add is left out: that partial sum is the layer's output on
one expert-parallel rank before the exchange, and no code stands in for the
exchange. Shapes stay static at any imbalance because the row buffer is sized
for the worst case (every assignment held here); a step whose rows fit the
eighth of it that balanced routing needs takes the small buffer instead
(`lax.cond`), so nothing is dropped by construction and little is moved.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops import pallas_compat

# Tests on the CPU set this to run megablox under the Pallas interpreter.
FORCE_INTERPRET = False


@dataclasses.dataclass(frozen=True)
class MoEArgs:
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_z_coef: float = 1e-3


def expert_capacity(tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    cap = int(tokens * top_k * capacity_factor / n_experts)
    return max(cap, top_k)  # never below top_k so tiny test shapes route


def route(gate_logits: jax.Array, args: MoEArgs):
    """Top-k routing with static capacity.

    gate_logits: [T, E] fp32. Returns (dispatch [T,E,C] bool-ish fp32,
    combine [T,E,C] fp32, aux_loss scalar).
    """
    t, e = gate_logits.shape
    cap = expert_capacity(t, e, args.top_k, args.capacity_factor)
    probs = jax.nn.softmax(gate_logits, axis=-1)  # [T, E]

    # iterative top-k (k is small and static): mask out chosen experts
    remaining = probs
    dispatch = jnp.zeros((t, e, cap), jnp.float32)
    combine = jnp.zeros((t, e, cap), jnp.float32)
    # per-expert running fill count, advanced after each of the k rounds
    fill = jnp.zeros((e,), jnp.int32)
    gates = []
    for _ in range(args.top_k):
        idx = jnp.argmax(remaining, axis=-1)  # [T]
        gate = jnp.take_along_axis(remaining, idx[:, None], axis=1)[:, 0]
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, e))
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [T, E]
        # position of each token within its chosen expert's buffer this round
        pos_in_e = jnp.cumsum(onehot, axis=0) - 1 + fill[None, :]  # [T, E]
        fill = fill + jnp.sum(onehot, axis=0)
        pos = jnp.sum(pos_in_e * onehot, axis=-1)  # [T]
        keep = pos < cap  # overflow tokens dropped for this expert
        slot = jax.nn.one_hot(pos, cap) * keep[:, None]  # [T, C]
        d = onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        gates.append(gate)

    # renormalize combine weights over the experts that actually kept the token
    denom = jnp.maximum(jnp.sum(combine, axis=(1, 2), keepdims=True), 1e-9)
    combine = combine / denom

    # load-balance aux loss over the FIRST choice (Switch): fraction of
    # tokens routed to e  ·  mean router prob of e
    first_idx = jnp.argmax(probs, axis=-1)
    f_e = jnp.mean(jax.nn.one_hot(first_idx, e), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = args.aux_loss_coef * e * jnp.sum(f_e * p_e)
    z = args.router_z_coef * jnp.mean(
        jax.nn.logsumexp(gate_logits, axis=-1) ** 2)
    return dispatch, combine, aux + z


def moe_mlp(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, args: MoEArgs,
            dtype: Any = jnp.bfloat16):
    """SwiGLU expert MLP with top-k routing.

    x: [B, S, D]; router_w: [D, E]; w_gate/w_up: [E, D, F]; w_down: [E, F, D]
    (stack sharded over the `expert` mesh axis via logical rules).
    Returns (out [B, S, D], aux_loss scalar).
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gate_logits = (xt @ router_w.astype(jnp.float32)).astype(jnp.float32)
    dispatch, combine, aux = route(gate_logits, args)

    dispatch = dispatch.astype(dtype)
    # [T,E,C] x [T,D] -> [E,C,D]: the expert-parallel all-to-all lives here
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)
    g = jnp.einsum("ecd,edf->ecf", expert_in, w_gate.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(dtype))
    h = jax.nn.silu(g) * u
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
    # combine back: [T,E,C] x [E,C,D] -> [T,D]
    out = jnp.einsum("tec,ecd->td", combine.astype(dtype), expert_out)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# dropless routing over a share of the experts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShareArgs:
    n_router_experts: int          # the router's width: all experts
    top_k: int
    n_held: int                    # experts whose weights are here
    first_expert: int = 0          # the first of them, in the router's order
    scale: float = 1.0             # routed_scaling_factor
    renormalize: bool = True       # weights / their sum over the chosen


ROW_TILE = 256    # the grouped matmul's tile of rows, at most
#: where every expert is held, every assignment is a row here: the sorted
#: rows then go through the experts this many at a time, which bounds the
#: temporaries of a wide prefill wave
ALL_HELD_SLICE = 32768


def row_tile(assignments: int) -> int:
    """The grouped matmul's tile of rows for a call of `assignments` rows:
    ROW_TILE for a training step or a prefill chunk; a decode step's few
    hundred rows (about one an expert) get a tile an eighth of them at
    most, 32 at least, so that an expert's visit multiplies its one or two
    rows by the weights and not a training tile of padding."""
    tile = ROW_TILE
    while tile > 32 and tile * 8 > assignments:
        tile //= 2
    return tile


def sigmoid_route(x: jax.Array, router_w: jax.Array, router_bias: jax.Array,
                  args: ShareArgs):
    """x [T, D] -> (expert ids [T, k], weights [T, k] float32): top-k of
    sigmoid(x Wr) + bias over all experts; the weight is the score without
    the bias, renormalised over the chosen, times the scale. Float32 at the
    highest precision: the choice is discontinuous, so rounding must not
    make it."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(
        router_bias.astype(jnp.float32)), args.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if args.renormalize:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx, w * args.scale


def _gmm_tiling(tm: int, k: int, n: int) -> tuple[int, int, int]:
    def tile(size, most):
        return max(t for t in range(128, most + 1, 128) if size % t == 0)
    return tm, tile(k, 768), tile(n, 1024)


def _grouped_matmul(rows, w, group_sizes, dtype, tm=ROW_TILE):
    """rows [M, K] sorted by group, w [G, K, N], group_sizes [G + 1] (the
    last group is the rows no expert here takes: they come out zero); `tm`
    the tile of rows, which divides M."""
    interpret = FORCE_INTERPRET
    if interpret or pallas_compat.target_platform() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        # the tiles as a tuple: a static argument of megablox's jit, which
        # has to compare equal from call to call
        return megablox.gmm(rows, w.astype(dtype), group_sizes, dtype,
                            _gmm_tiling(tm, rows.shape[1], w.shape[2]),
                            None, None, False, interpret)
    sizes = group_sizes[:-1]
    out = jax.lax.ragged_dot(rows, w.astype(dtype), sizes,
                             preferred_element_type=dtype)
    live = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
    return jnp.where(live[:, None], out, 0)


def moe_share_mlp(x: jax.Array, router_w: jax.Array, router_bias: jax.Array,
                  w_gate: jax.Array | None, w_up: jax.Array, w_down: jax.Array,
                  args: ShareArgs, dtype: Any = jnp.bfloat16,
                  layer: int | None = None,
                  expert_x: jax.Array | None = None):
    """The routed experts' part of a layer, as the rank that holds experts
    [first_expert, first_expert + n_held) computes it.

    x [B, S, D]; router_w [D, E_all]; router_bias [E_all] (a buffer: no
    gradient); w_gate / w_up [n_held, D, F]; w_down [n_held, F, D]; an
    expert is a SwiGLU, silu(x w_gate) * (x w_up) w_down, or with `w_gate`
    None a squared ReLU, relu(x w_up)^2 w_down. The router reads x; the
    experts read `expert_x` [B, S, D_e] where it is given (a latent the
    caller projected x into: w_up is then [n_held, D_e, F] and w_down
    [n_held, F, D_e], and so is the result), else x. Or,
    with `layer` (static), the STACKS of every layer's experts
    `[layers, n_held, ...]`, read in place: the grouped matmul takes the
    stack as `layers * n_held` groups whose sizes are zero outside this
    layer's, and visits no empty group (a slice of the stack would reach
    the kernel, a custom call, as a copy of a layer's experts). Returns
    (out [B, S, D], counters): `rows_here` the assignments this rank took,
    `rows_dropped` those it took and did not compute (0, or the step is
    wrong), `load_max_over_mean` over the experts held,
    `top1_share_max` the largest share of tokens whose first choice is one
    expert, over all experts, `experts_touched` how many of the experts
    held took a row (each one's weights are read for them)."""
    b, s, _ = x.shape
    t, k, held = b * s, args.top_k, args.n_held
    before = after = 0
    if layer is not None:
        before, after = layer * held, (w_up.shape[0] - 1 - layer) * held
        w_gate, w_up, w_down = (None if w is None
                                else w.reshape((-1,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    tile = row_tile(t * k)
    xt = x.reshape(t, x.shape[-1])
    rows_in = xt if expert_x is None else expert_x.reshape(t, -1)
    d = w_down.shape[-1]
    with jax.named_scope("moe_route"):
        idx, w = sigmoid_route(xt, router_w, router_bias, args)
        local = idx - args.first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        total = -(-t * k // tile) * tile
        key = jnp.pad(key.reshape(t * k), (0, total - t * k),
                      constant_values=held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                        axis=0)
        rows_here = jnp.sum(sizes[:held])
        w_flat = jnp.pad(w.reshape(t * k), (0, total - t * k))

    # the sorted rows go through the experts a slice at a time: the first
    # slice holds what balanced routing sends here eight times over; the
    # others run only when the rows reach them, under a rematerialised scan,
    # so the worst case costs no memory until it happens
    m = -(-max(total // 8, 1) // tile) * tile
    if held == args.n_router_experts:   # every row is here, every time
        m = min(total, ALL_HELD_SLICE)
    n_slices = -(-total // m)
    order = jnp.pad(order, (0, n_slices * m - total),
                    constant_values=t * k)      # past every row: no expert
    starts = jnp.cumsum(sizes[:held]) - sizes[:held]

    def slice_out(lo, xt, w_flat, w_gate, w_up, w_down):
        """Rows [lo, lo + m) of the sorted order -> their part of [T, D]
        (float32) and how many of them an expert here computed."""
        sel = jax.lax.dynamic_slice_in_dim(order, lo, m)
        tok = jnp.minimum(sel // k, t - 1)
        rows = xt[tok].astype(dtype)
        here = (jnp.clip(starts + sizes[:held], lo, lo + m)
                - jnp.clip(starts, lo, lo + m))
        # the order is sorted, so a slice is experts' rows (its first one
        # possibly the tail of an expert's) and then rows no expert here
        # takes: the groups start at the slice's first row
        groups = jnp.concatenate([
            jnp.zeros((before,), here.dtype), here,
            jnp.zeros((after,), here.dtype), (m - jnp.sum(here))[None]])
        mm = functools.partial(_grouped_matmul, group_sizes=groups,
                               dtype=dtype, tm=tile)
        if w_gate is None:
            act = jnp.square(jax.nn.relu(
                mm(rows, w_up).astype(jnp.float32))).astype(dtype)
        else:
            gate, up = mm(rows, w_gate), mm(rows, w_up)
            act = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(dtype)
        wt = jnp.take(w_flat, jnp.minimum(sel, total - 1))
        y = mm(act, w_down).astype(jnp.float32) * wt[:, None]
        return (jnp.zeros((t, d), jnp.float32).at[tok].add(y),
                jnp.sum(here))

    operands = (rows_in, w_flat, w_gate, w_up, w_down)
    with jax.named_scope("moe_experts"):
        out, done = slice_out(0, *operands)
        if n_slices > 1:
            def rest(out, done, *operands):
                def body(carry, lo):
                    o, n = jax.checkpoint(slice_out)(lo, *operands)
                    return (carry[0] + o, carry[1] + n), None
                return jax.lax.scan(body, (out, done),
                                    m * jnp.arange(1, n_slices))[0]
            out, done = jax.lax.cond(
                rows_here > m, rest, lambda out, done, *_: (out, done),
                out, done, *operands)
        out, dropped = out.astype(dtype), rows_here - done
    load = sizes[:held].astype(jnp.float32)
    first = jnp.sum(jax.nn.one_hot(idx[:, 0], args.n_router_experts,
                                   dtype=jnp.int32), axis=0)
    counters = {
        "rows_here": rows_here.astype(jnp.float32),
        "rows_dropped": dropped.astype(jnp.float32),
        "load_max_over_mean": jnp.max(load) / jnp.maximum(jnp.mean(load),
                                                          1e-9),
        "top1_share_max": jnp.max(first).astype(jnp.float32) / t,
        "experts_touched": jnp.sum(sizes[:held] > 0).astype(jnp.float32),
    }
    return out.reshape(b, s, d), counters
