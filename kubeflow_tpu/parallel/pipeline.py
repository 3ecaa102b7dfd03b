"""Pipeline parallelism: GPipe-style microbatch pipelining over the `stage`
mesh axis (SURVEY.md §2.2 — the reference only ever launches DeepSpeed/
Megatron containers for PP; here it is a framework primitive).

TPU-first shape: the model's stacked-layer tensors ([L, ...], the lax.scan
axis) are sharded over `stage`, so each stage device holds a contiguous
L/n_stages slab. A *partial-manual* ``jax.shard_map`` (manual over `stage`
ONLY, ``axis_names={"stage"}``) runs the classic GPipe schedule as a
``lax.scan`` over M + S - 1 ticks:

  tick t: stage 0 ingests microbatch t; every stage applies its layer slab
  to its current activation; ``ppermute`` rotates activations one stage down
  the ICI ring; the last stage banks finished microbatches.

Because only `stage` is manual, every OTHER mesh axis stays in GSPMD-land
inside the stage body: batch stays sharded over data/fsdp, the slab weights
keep their fsdp/tensor shardings from the logical-axis rules (ZeRO-3
all-gathers and megatron-style tensor collectives are inserted by XLA per
matmul), and the embedding/LM-head run OUTSIDE the pipeline region entirely.
That is what makes pp x dp x fsdp x tp a rule change instead of a rewrite —
the r1 NotImplementedError guards (pipeline.py:105-115 then) are gone.

All control flow is static (clipped dynamic slices + where-masks instead of
data-dependent branches), so XLA compiles ONE tick body and the schedule is
a rolled loop — compile time is O(1) in both depth and microbatch count.
Warmup/drain bubbles execute with garbage inputs and are masked out, the
standard SPMD trade (bubble fraction (S-1)/(M+S-1)).

Gradients: plain autodiff through the scan + ppermute — the backward pass
is automatically the reverse pipeline (activations rotate back up the ring).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "stage"

def resolve_schedule(configured: str | None = None) -> str:
    """Stage-schedule selection policy (ISSUE 20): the configured value
    ("sync"/"overlapped"), else "sync": overlap stays opt-in until a chip
    run has timed it. Static per engine — the decode drivers bake the
    schedule into their dispatch loop."""
    if configured is None:
        return "sync"
    if configured not in ("sync", "overlapped"):
        raise ValueError(
            f"unknown stage schedule {configured!r} "
            "(want 'sync' or 'overlapped')")
    return configured


def gpipe(
    stage_fn: Callable[..., jax.Array],
    stage_params: Any,
    x_mb: jax.Array,
    *,
    extras: Any = None,
    axis_name: str = AXIS,
) -> jax.Array:
    """Run the GPipe schedule *inside* shard_map (manual over `axis_name`).

    stage_fn(stage_params, x, extras_t) -> y applies one stage's layer slab.
    x_mb: [M, ...] microbatches (replicated across stage devices).
    extras: optional pytree of [M, ...] per-microbatch side inputs (e.g.
    segment ids); each tick the entry for the microbatch CURRENTLY at this
    stage (index t - stage) is passed to stage_fn — side inputs don't rotate
    around the ring, they're indexed locally.
    Returns [M, ...] outputs, valid on the LAST stage (zeros elsewhere —
    callers mask by stage index and psum).
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    ticks = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        buf, out = carry
        feed = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        cur = jnp.where(stage == 0, feed, buf)
        # the microbatch at stage s during tick t is t - s (clip: bubbles
        # run garbage that the out-mask discards anyway)
        ex_idx = jnp.clip(t - stage, 0, m - 1)
        if extras is None:
            y = stage_fn(stage_params, cur)
        else:
            ex = jax.tree.map(
                lambda e: jax.lax.dynamic_index_in_dim(
                    e, ex_idx, axis=0, keepdims=False), extras)
            y = stage_fn(stage_params, cur, ex)
        mb_idx = t - (n_stages - 1)
        done = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(mb_idx, 0, m - 1), axis=0)
        out = jnp.where((mb_idx >= 0) & (stage == n_stages - 1), done, out)
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, out), None

    # zeros are stage-invariant but the tick outputs vary per stage — mark
    # the carry as varying over the stage axis or scan rejects the types
    # (no-op if the input was already pcast to varying by the caller)
    def _varying(z):
        if axis_name in getattr(z.aval, "vma", set()):
            return z
        return jax.lax.pcast(z, (axis_name,), to="varying")

    init = jax.tree.map(_varying,
                        (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb)))
    (_, out), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
    return out


def microbatch(x: jax.Array, n: int) -> jax.Array:
    """[B, ...] -> [n, B/n, ...]."""
    if x.shape[0] % n:
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"{n} microbatches")
    return x.reshape(n, x.shape[0] // n, *x.shape[1:])


def pipelined_llama_loss(params, batch, cfg, mesh: Mesh,
                         n_microbatches: int | None = None):
    """Pipelined forward+loss for llama-family params on a `stage` mesh.

    Numerically identical to llama.loss_fn (same layer math, same shift);
    only the execution schedule differs. Composes with data/fsdp/tensor
    sharding AND the seq-parallel attention islands: the shard_map is
    manual over `stage` alone, so GSPMD keeps partitioning everything else
    inside the stage body, and ring/ulysses attention nests as a
    partial-manual island over the remaining axes. Packed-sequence
    segment_ids and loss_mask are supported (segment ids ride alongside
    each microbatch; the mask applies at the loss, outside the pipe).
    """
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.ops.norms import rms_norm
    from kubeflow_tpu.parallel.mesh import mesh_shape

    shape = mesh_shape(mesh)
    n_stages = shape.get(AXIS, 1)
    m = n_microbatches or n_stages
    tokens = batch["tokens"]
    seg = batch.get("segment_ids")
    # sequence parallelism composes by manualizing `sequence` ALONGSIDE
    # `stage` (Shardy rejects a nested manual island whose axes follow
    # `stage` in the mesh order): activations enter seq-sharded, RoPE uses
    # per-shard global positions, and the ring/ulysses per-device bodies
    # run directly inside the stage body (models/llama.py _attention).
    seq_par = (shape.get("sequence", 1) > 1
               and cfg.attention_impl in ("ring", "ulysses"))

    def pipe(layers, x_mb, seg_mb):
        if seq_par:
            s_loc = x_mb.shape[2]
            positions = (jax.lax.axis_index("sequence") * s_loc
                         + jnp.arange(s_loc))
        else:
            positions = jnp.arange(x_mb.shape[2])

        def stage_fn(layers, h, seg_mb=None):
            def layer_body(carry, layer):
                return llama._layer_body(cfg, carry, layer, positions,
                                         seg_mb)

            fn = layer_body
            if cfg.remat:
                policy = {
                    "minimal":
                        jax.checkpoint_policies
                        .checkpoint_dots_with_no_batch_dims,
                    "full": jax.checkpoint_policies.nothing_saveable,
                    "none": jax.checkpoint_policies.everything_saveable,
                }[cfg.remat_policy]
                fn = jax.checkpoint(fn, policy=policy)
            h, _ = jax.lax.scan(fn, h, layers)
            return h

        # keep every stage-collective in f32: XLA:CPU's AllReducePromotion
        # pass CHECK-fails cloning bf16 all-reduces ("Invalid binary
        # instruction opcode copy"), so (a) the invariant->varying pcast —
        # whose transpose is the psum of the input cotangent — happens
        # BEFORE the bf16 cast, and (b) the region exits in f32 so the
        # stage-dim gather all-reduce below is f32 too. On TPU the ring
        # ppermutes inside gpipe stay bf16 either way.
        x_mb = jax.lax.pcast(x_mb, (AXIS,), to="varying")
        if seq_par:
            # weights are sequence-INVARIANT; their cotangent psums over
            # `sequence`. pcast them varying in f32 (param dtype) so that
            # psum is f32 — the bf16 form trips the same XLA:CPU
            # AllReducePromotion CHECK as above
            layers = jax.tree.map(
                lambda w: jax.lax.pcast(w, ("sequence",), to="varying"),
                layers)
        out = gpipe(stage_fn, layers, x_mb.astype(cfg.dtype), extras=seg_mb)
        # leave the manual region with a leading per-stage dim (out_specs
        # P(stage)); the caller slices stage -1 in GSPMD-land — cheaper
        # than an activation psum (only the last shard moves)
        return out[None].astype(jnp.float32)

    # embed outside the pipe (GSPMD shards vocab/fsdp as usual), microbatch
    # to [M, Bm, S, D]; layer slabs enter manual-over-stage via their
    # leading axis, everything else keeps its automatic sharding.
    # f32 across the entry boundary: x_mb is stage-replicated, so its
    # COTANGENT psums over `stage` in the backward — a bf16 psum there
    # miscompiles the CPU backend's partial-manual path (hlo_instruction
    # CHECK "Invalid binary instruction opcode copy"); the cast is one
    # convert, and the psum'd cotangent is zeros except from stage 0
    x = params["embed"].astype(cfg.dtype)[tokens]
    x_mb = microbatch(x, m).astype(jnp.float32)
    seg_mb = None if seg is None else microbatch(seg, m)
    layer_spec = jax.tree.map(lambda _: P(AXIS), params["layers"])
    manual = frozenset({AXIS, "sequence"} if seq_par else {AXIS})
    seq_ax = "sequence" if seq_par else None
    x_spec = P(None, None, seq_ax) if seq_par else P()
    seg_spec = P(None, None, seq_ax) if seq_par else P()
    staged = jax.shard_map(
        pipe, mesh=mesh,
        in_specs=(layer_spec, x_spec, seg_spec),
        out_specs=P(AXIS, None, None, seq_ax) if seq_par else P(AXIS),
        axis_names=manual,
    )(params["layers"], x_mb, seg_mb)
    # only the LAST stage's bank is the pipeline output; back to model dtype
    h_mb = staged[-1].astype(cfg.dtype)

    # loss tail identical to llama.loss_fn, in plain GSPMD-land
    h = h_mb.reshape(tokens.shape[0], tokens.shape[1], -1)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(
        logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(token_loss) if mask is None else mask[:, 1:]
    total = jnp.sum(token_loss * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return total / denom, {"loss": total / denom, "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Inference-mode stage plan (ISSUE 14): MPMD stage-sharded SERVING.
#
# Training uses the SPMD gpipe schedule above — one program, shard_map
# over `stage`. Serving wants the opposite shape: per-stage COMPILED
# PROGRAMS on per-stage sub-meshes, host-chained, so (a) the KV cache is
# threaded per-stage (stage s owns [L_s, slots, max_len, kv, hd] — the
# 31B-class cache never exists whole anywhere), (b) decode microbatches
# flow MPMD-style (stage k decodes microbatch i while stage k-1 runs
# microbatch i+1 — async dispatch onto disjoint device groups overlaps
# them for real), and (c) each stage's tensor collectives stay inside its
# own sub-mesh ICI group. The plan below is the geometry + accounting
# half; the engine drivers live in serving/multichip.py and reuse the
# models/llama.py *_inner bodies so stage-sharded output is byte-exact
# against the single-program engine.
# ---------------------------------------------------------------------------


def stage_bounds(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous [start, end) layer slabs per stage. Uneven splits put
    the remainder on the EARLIEST stages (stage 0 also owns the embed
    gather — cheap — so front-loading one layer beats starving the
    last stage, which owns the lm_head matmul)."""
    if not 1 <= n_stages <= n_layers:
        raise ValueError(
            f"n_stages must be 1..n_layers ({n_layers}), got {n_stages}")
    base, extra = divmod(n_layers, n_stages)
    bounds, start = [], 0
    for s in range(n_stages):
        size = base + (1 if s < extra else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


def microbatch_ranges(n_slots: int, n_stages: int) -> list[tuple[int, int]]:
    """Decode-wave microbatches as contiguous (start, size) slot ranges:
    one per stage so the pipe can fill, capped at one slot per
    microbatch when stage-count exceeds the wave width (pp > n_slots —
    the degenerate-but-legal geometry). Uneven splits front-load like
    stage_bounds."""
    m = min(max(1, n_stages), n_slots)
    base, extra = divmod(n_slots, m)
    out, start = [], 0
    for i in range(m):
        size = base + (1 if i < extra else 0)
        out.append((start, size))
        start += size
    return out


def wavefront(n_microbatches: int, n_stages: int):
    """GPipe tick schedule: yields (tick, stage, microbatch) triples in
    dispatch order — at tick t, stage s works microbatch t - s. With
    async dispatch onto per-stage device groups this order IS the
    overlap: within one tick every stage's program runs concurrently."""
    for t in range(n_microbatches + n_stages - 1):
        for s in range(n_stages):
            m = t - s
            if 0 <= m < n_microbatches:
                yield t, s, m


def split_stage_params(params: Any, bounds: Sequence[tuple[int, int]]
                       ) -> list[dict]:
    """init()-shaped llama params → per-stage slabs: every stage gets its
    contiguous layer slice; stage 0 additionally owns `embed`, the last
    stage `final_norm` + `lm_head` (the pipeline's entry/exit tensors).
    Works on quantized leaves too ({"q", "s"} subtrees slice on their
    leading layer axis like any other leaf)."""
    n = len(bounds)
    slabs: list[dict] = []
    for s, (lo, hi) in enumerate(bounds):
        slab: dict = {"layers": jax.tree.map(lambda p: p[lo:hi],
                                             params["layers"])}
        if s == 0:
            slab["embed"] = params["embed"]
        if s == n - 1:
            slab["final_norm"] = params["final_norm"]
            slab["lm_head"] = params["lm_head"]
        slabs.append(slab)
    return slabs


class StagePerf:
    """Per-stage busy/idle accounting for the decode pipeline — the
    committed `pipeline_bubble_frac` input. Two views, both exposed:

    - schedule ticks (always on, deterministic): each decode step runs
      M + S - 1 ticks and every stage is busy for M of them, so the
      schedule's bubble fraction is (S-1)/(M+S-1) by construction —
      recorded as a cross-check, not a measurement;
    - wall timestamps (opt-in `stage_timing`): the driver brackets every
      stage-program execution with perf_counter() and blocks on its
      output, so `stage_busy_s[s]` is stage s's measured busy wall and
      bubble_frac = 1 - sum(busy) / (stages * window) is the measured
      pipeline bubble. Blocking serializes the overlap, so timing mode
      is for the bench/profiler, never live traffic.
    """

    def __init__(self, n_stages: int):
        self.n_stages = n_stages
        #: which dispatch schedule produced the busy numbers ("sync":
        #: per-program blocking brackets; "overlapped": per-stage
        #: dispatch→drain windows — overlap-inclusive, so the measured
        #: bubble reflects the schedule the live engine actually runs)
        self.schedule = "sync"
        self.reset()

    def reset(self) -> None:
        self.stage_busy_s = [0.0] * self.n_stages
        self.stage_ticks = [0] * self.n_stages
        self.window_s = 0.0
        self.steps = 0
        self.ticks_total = 0

    def record_step(self, n_microbatches: int, wall_s: float) -> None:
        """One decode step's schedule accounting (M+S-1 ticks, every
        stage busy for M of them) + its measured wall window."""
        self.steps += 1
        self.ticks_total += n_microbatches + self.n_stages - 1
        for s in range(self.n_stages):
            self.stage_ticks[s] += n_microbatches
        self.window_s += wall_s

    def record_stage(self, stage: int, busy_s: float) -> None:
        self.stage_busy_s[stage] += busy_s

    def bubble_frac(self) -> float | None:
        """Measured bubble fraction over the accumulated window: the
        share of stage-seconds spent idle. None until a timed window
        accumulated (stage_timing off = no measured busy wall)."""
        if self.window_s <= 0 or not any(self.stage_busy_s):
            return None
        busy = sum(self.stage_busy_s)
        return max(0.0, min(1.0, round(
            1.0 - busy / (self.n_stages * self.window_s), 4)))

    def schedule_bubble_frac(self) -> float | None:
        """The schedule's structural bubble: idle stage-ticks over total
        stage-ticks, (S-1)/(M+S-1) per uniform step."""
        if not self.ticks_total:
            return None
        busy = sum(self.stage_ticks)
        return round(1.0 - busy / (self.n_stages * self.ticks_total), 4)

    def snapshot(self) -> dict:
        return {
            "stages": self.n_stages,
            "steps": self.steps,
            "schedule": self.schedule,
            "stage_busy_s": [round(b, 4) for b in self.stage_busy_s],
            "window_s": round(self.window_s, 4),
            "bubble_frac": self.bubble_frac(),
            "schedule_bubble_frac": self.schedule_bubble_frac(),
        }


class InferenceStagePlan:
    """Geometry + placement for stage-sharded serving: layer bounds,
    per-stage sub-meshes (None = virtual staging on the default device —
    the program decomposition and schedule run identically, just without
    physical placement; the parity tests' shape), microbatch ranges, and
    the cross-stage transfer helper.

    `tensor` > 1 shards each slab tensor-parallel INSIDE its stage's
    sub-mesh via the standard logical-axis rules (`layers` remapped to
    None — a slab is the stage's whole local stack), the serving twin of
    the dp x pp x fsdp x tp trainer composition."""

    def __init__(self, n_layers: int, n_stages: int, n_slots: int, *,
                 tensor: int = 1,
                 devices: Sequence[jax.Device] | None = None):
        from kubeflow_tpu.parallel.mesh import MeshConfig, make_mesh, \
            stage_submeshes

        if tensor < 1:
            raise ValueError("tensor must be >= 1")
        self.n_stages = int(n_stages)
        self.tensor = int(tensor)
        self.bounds = stage_bounds(n_layers, n_stages)
        self.mb_ranges = microbatch_ranges(n_slots, n_stages)
        if devices is None:
            devices = jax.devices()
        needed = self.n_stages * self.tensor
        if len(devices) >= needed and needed > 1:
            self.mesh = make_mesh(MeshConfig(stage=n_stages, tensor=tensor),
                                  devices=devices[:needed])
            self.submeshes: list[Mesh | None] = stage_submeshes(self.mesh)
        else:
            if self.tensor > 1:
                raise ValueError(
                    f"tensor={tensor} needs {needed} devices "
                    f"({len(devices)} available); stage-only layouts "
                    "degrade to virtual staging, tensor sharding cannot")
            # virtual staging: every stage on the default device — same
            # programs, same schedule, no physical placement
            self.mesh = None
            self.submeshes = [None] * self.n_stages
        self._repl = [None if sm is None
                      else NamedSharding(sm, P())
                      for sm in self.submeshes]
        self.perf = StagePerf(self.n_stages)

    @property
    def n_microbatches(self) -> int:
        return len(self.mb_ranges)

    def replicated(self, stage: int):
        return self._repl[stage]

    def to_stage(self, x, stage: int):
        """Move an array onto `stage`'s sub-mesh (replicated). Identity
        under virtual staging — and for host numpy inputs, which jit
        places itself."""
        sh = self._repl[stage]
        if sh is None or x is None:
            return x
        return jax.device_put(x, sh)

    def shard_slab(self, slab: dict, stage: int, logical_tree: dict):
        """Place one stage's params slab: tensor-sharded by the logical
        rules on the stage's sub-mesh (layers → None: the slab IS the
        local stack), or left as-is under virtual staging."""
        sm = self.submeshes[stage]
        if sm is None:
            return jax.tree.map(jnp.asarray, slab)
        from kubeflow_tpu.parallel.sharding import (shard_tree,
                                                    tree_logical_to_sharding)

        shardings = tree_logical_to_sharding(
            logical_tree, sm, rules={"layers": None})
        return shard_tree(slab, shardings)

    def cache_sharding(self, stage: int, name: str):
        """Sharding of KV-slab array `name` on the stage sub-mesh:
        kv-heads over `tensor` (dim 3 of the 5D payloads, dim 2 of the
        lane-major scale planes), the single-program engine's layout
        per stage."""
        from kubeflow_tpu.models.llama import cache_kv_spec

        sm = self.submeshes[stage]
        if sm is None:
            return None
        return NamedSharding(sm, cache_kv_spec(name))

    def describe(self) -> dict:
        """The /healthz `mesh` section's geometry half."""
        return {
            "stages": self.n_stages,
            "tensor": self.tensor,
            "virtual": self.mesh is None,
            "device_count": (self.n_stages * self.tensor
                             if self.mesh is not None else 1),
            "stage_layers": [hi - lo for lo, hi in self.bounds],
            "microbatches": [list(r) for r in self.mb_ranges],
        }


class StageClock:
    """Timing bracket for one stage-program execution: measures busy
    wall into a StagePerf when armed, a no-op pass-through otherwise
    (blocking for the timestamp would serialize the very overlap the
    schedule exists for)."""

    def __init__(self, perf: StagePerf, enabled: bool):
        self.perf = perf
        self.enabled = enabled

    def run(self, stage: int, thunk):
        if not self.enabled:
            return thunk()
        t0 = time.perf_counter()
        out = thunk()
        jax.block_until_ready(out)
        self.perf.record_stage(stage, time.perf_counter() - t0)
        return out
