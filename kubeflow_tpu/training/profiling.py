"""Profiling/tracing hooks — the platform's TensorBoard-profiler analog
(SURVEY.md §5.1: the reference delegates workload profiling to TF/torch
profilers surfaced through the tensorboard-controller; here `jax.profiler`
is first-class and the trace windows are part of the trainer config).

Two surfaces:

- `trace(logdir)`: context manager around arbitrary device work.
- `StepProfiler`: step-windowed capture for the training loop — starts at
  `start_step`, captures `num_steps` steps, then stops and writes a
  `PROFILE_DONE` marker; the Tensorboard CR can point at the same logdir
  (tensorboard-plugin-profile reads the plugins/profile subdir).

The captured dir is the artifact; callers register it in the metadata
store for lineage like any pipeline output (SURVEY.md §5.1 "artifact =
trace dir registered in the metadata store").
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable

import numpy as np


@contextlib.contextmanager
def trace(logdir: str):
    """jax.profiler.trace with the dir created up front; yields the dir."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


class StepProfiler:
    """Capture a [start_step, start_step + num_steps) window of the train
    loop. `maybe_stop` takes a sync thunk because dispatch returns before
    the device finishes — the caller fences the trace (a scalar fetch or
    block_until_ready on the step's outputs)."""

    def __init__(self, logdir: str, start_step: int = 2, num_steps: int = 3):
        if num_steps < 1:
            raise ValueError("profile_num_steps must be >= 1")
        self.logdir = logdir
        self.start_step = start_step
        self.end_step = start_step + num_steps
        self.active = False
        self.done = False

    def maybe_start(self, step: int) -> None:
        if self.done or self.active or step < self.start_step:
            return
        import jax

        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self.active = True

    def maybe_stop(self, step: int,
                   sync: Callable[[], Any] | None = None) -> None:
        if not self.active or step + 1 < self.end_step:
            return
        import jax

        if sync is not None:
            sync()  # fence: device work for the window must have retired
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        with open(os.path.join(self.logdir, "PROFILE_DONE"), "w") as f:
            f.write(f"steps {self.start_step}..{self.end_step - 1}\n")

    def close(self) -> None:
        """Stop a still-open window (loop ended early)."""
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False


# -- serving-side decode-step attribution ------------------------------------
#
# The 8B roofline gap (ROADMAP #2): plain decode measured ~30 ms/step
# against a 9.2 ms weight-read floor, with nothing attributing the other
# ~21 ms. serving_decode_breakdown() closes the attribution hole: it
# drives the live engine's OWN compiled decode programs (plus two probe
# programs) and splits one decode step's wall time into the five buckets
# a serving step is made of. Differential timing, not trace parsing —
# the buckets come from executing program VARIANTS that differ by
# exactly one stage, so no profiler-proto tooling is needed at runtime;
# a jax.profiler trace of the full step is captured alongside as the
# registered artifact when trace_dir is given.


def _median_time(run, iters: int):
    import time

    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def serving_decode_breakdown(engine, *, steps: int | None = None,
                             fill_len: int | None = None, iters: int = 5,
                             trace_dir: str | None = None,
                             hbm_gbps: float | None = None) -> dict:
    """Attribute one batched decode step of a (warmed, idle) LLMEngine.

    Returns a machine-readable dict whose `buckets_ms` splits a decode
    step into:

      weight_read          — measured: a jitted reduction that reads every
                             non-embed weight byte once and nothing else
                             (the HBM floor decode cannot beat);
      attention_kv_update  — the rest of the sampling-stripped forward:
                             attention over the KV span, cache update,
                             norms/activations (nosample-variant time
                             minus the weight read); sub-attributed by
                             two probe programs (ISSUE 15):
                             `attn_kernel` — the selected decode-
                             attention impl (xla einsum or the Pallas
                             flash-decode kernel) once per layer over
                             the live span at S_v=1, and `attn_dequant`
                             — reading + dequantizing the same int8
                             span and nothing else (0.0 on unquantized
                             caches; both None when the cache isn't a
                             single-program slab or is mesh-sharded).
                             Probes, not a partition: the bucket also
                             carries cache writes + MLP — but the
                             xla-vs-flash A/B delta lands in
                             attn_kernel while every other bucket
                             stays put, which is what makes the
                             serving_kernels record explainable;
      sampling_penalties   — full program minus the sampling-stripped
                             variant (_decode(sample=False));
      dispatch_rtt         — a trivial-program host->device->host round
                             trip, amortized per step over the chunk;
      host_fetch_replay    — the engine's live perf counters (fetch +
                             Python replay wall per step), None until the
                             engine has served decode traffic.

    The engine's slot state is junk during the run and reset after
    (exactly like warmup) — call only while idle. `fill_len` positions
    the synthetic slots mid-generation so the attention span is
    realistic; `hbm_gbps` adds the analytic weight-read floor next to
    the measured one."""
    import jax
    import jax.numpy as jnp

    n_slots = engine.n_slots
    if steps is None:
        steps = 1
        while steps * 2 <= engine.decode_chunk:
            steps *= 2
    # every (untimed + timed) run's KV writes must fit max_len so no
    # state reset is needed INSIDE a timed window (a reset is host
    # transfers — RTTs — that would pollute the chunk timing). Small
    # caches clamp steps, then iters, rather than silently profiling a
    # degenerate everything-clamped-at-max_len program state.
    def rows_needed(s, it):
        return (2 * it + 4) * s + 2
    while steps > 1 and rows_needed(steps, iters) > engine.max_len:
        steps //= 2
    while iters > 1 and rows_needed(steps, iters) > engine.max_len:
        iters -= 1
    if rows_needed(steps, iters) > engine.max_len:
        raise ValueError(
            f"max_len {engine.max_len} cannot hold one profiled chunk "
            f"(steps={steps}, iters={iters})")
    if fill_len is None:
        fill_len = max(1, min(engine.max_len // 2,
                              engine.max_len - rows_needed(steps, iters)))
    span = engine._pick_span(min(fill_len + steps, engine.max_len))

    def reset_state():
        engine.lengths = engine._put(
            np.full((n_slots,), fill_len, np.int32))
        engine.last_tokens = engine._put(np.ones((n_slots,), np.int32))
        engine.samp = engine._put(engine._samp_reset())

    active = engine._put(np.ones((n_slots,), bool))

    def run_decode(fn):
        def go():
            (engine.cache, engine.lengths, engine.last_tokens,
             engine.samp, engine.rng_key, out) = fn(
                engine.params, engine.cache, engine.lengths,
                engine.last_tokens, engine.samp, engine.rng_key, active,
                *engine._extra())
            float(np.asarray(out).flat[0])   # value fetch: the program
            # that produced it has finished (see StepProfiler)
        return go

    fn_full = engine._decode_fn(steps, span)
    # the sampling-stripped variant comes from the ENGINE (LLMEngine
    # jits its _decode with sample=False; the stage-sharded engine
    # returns its pipelined driver twin) so the differential stays
    # apples-to-apples per engine kind
    fn_nosample = engine._decode_nosample_fn(steps, span)

    # pure weight read: reduce every non-embed leaf to one scalar — reads
    # each byte exactly once, FLOPs are negligible, so its wall time IS
    # the achievable weight-read time of this chip (embed is excluded
    # because decode gathers a handful of its rows, never the table).
    # Stage-sharded engines hold params as a LIST of per-stage slabs —
    # strip each slab's embed the same way.
    params = engine.params
    if isinstance(params, dict):
        read_trees = [{k: v for k, v in params.items() if k != "embed"}]
    elif isinstance(params, list):
        # stage-sharded engine: one slab per stage, each on ITS OWN
        # device group — one jitted read per slab (a single program
        # spanning device groups is rejected), dispatched together so
        # per-stage reads overlap exactly like the pipeline's
        read_trees = [{k: v for k, v in slab.items() if k != "embed"}
                      for slab in params]
    else:
        read_trees = [params]
    read_bytes = int(sum(l.nbytes for t in read_trees
                         for l in jax.tree.leaves(t)))

    @jax.jit
    def read_all(p):
        tot = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(p):
            tot = tot + jnp.sum(leaf).astype(jnp.float32)
        return tot

    def run_read():
        outs = [read_all(t) for t in read_trees]   # dispatch all first
        for o in outs:
            float(np.asarray(o))

    # trivial round trip: dispatch + scalar fetch of a one-add program —
    # the per-dispatch host<->device overhead every chunk pays once
    tiny = engine._put(np.zeros((), np.float32))
    tiny_fn = jax.jit(lambda x: x + 1.0)

    def run_rtt():
        float(np.asarray(tiny_fn(tiny)))

    # one untimed call per program: compiles (nosample/read/rtt are not
    # in the warmup menu) and faults pages before the timed iterations.
    # State is reset ONCE up front; fill_len left enough KV headroom for
    # every run's writes, so no host transfer lands inside a timed window
    reset_state()
    for warm in (run_decode(fn_full), run_decode(fn_nosample), run_read,
                 run_rtt):
        warm()

    t_rtt = _median_time(run_rtt, iters)
    if hasattr(engine, "pipeline_perf"):
        engine.pipeline_perf(reset=True)   # bracket the timed window
    t_full = _median_time(run_decode(fn_full), iters)
    # pipeline_bubble bucket (ISSUE 14 satellite): per-stage idle wall
    # per decode step, from the stage-sharded engine's own per-stage
    # timestamps (None for single-program engines, and None when the
    # engine runs with stage_timing off — the schedule-derived fraction
    # still rides the `pipeline` sub-record either way)
    pipe_bubble_ms = None
    pipe_snap = None
    if hasattr(engine, "pipeline_perf"):
        pipe_snap = engine.pipeline_perf(reset=True)
        if pipe_snap["steps"] and pipe_snap["bubble_frac"] is not None:
            n_st = pipe_snap["stages"]
            idle = (n_st * pipe_snap["window_s"]
                    - sum(pipe_snap["stage_busy_s"]))
            pipe_bubble_ms = round(
                max(idle, 0.0) / (n_st * pipe_snap["steps"]) * 1e3, 4)
    t_nosample = _median_time(run_decode(fn_nosample), iters)
    t_read = max(_median_time(run_read, iters) - t_rtt, 0.0)

    # kv_handoff bucket (ISSUE 13 satellite): the cost of moving one
    # radix block of finished prefill KV between engines — raw extract
    # (the banker's slice program) + zero-copy insert through the same
    # KVHandoff interface the disaggregated coordinator uses — so the
    # handoff's price sits NEXT TO weight-read/attention/sampling in the
    # committed breakdown instead of folding into dispatch-RTT. None on
    # engines without a prefix cache (no blocks to move), and on paged
    # engines — paged banking is refcount bookkeeping on pool blocks
    # (serving/paged.py _bank_prefix_blocks), there is no slice-out
    # handoff program to time.
    kv_handoff_ms = None
    if getattr(engine, "prefix_cache_enabled", False) \
            and engine.kvcache is not None \
            and getattr(engine, "_bank_uses_raw_extract", True):
        from kubeflow_tpu.kvcache import RadixKVCache
        from kubeflow_tpu.serving.disagg import KVHandoff

        bt = engine.prefix_block_tokens
        scratch = RadixKVCache(bt, 4)
        handoff = KVHandoff(lambda: scratch)
        probe_tokens = list(range(1, bt + 1))

        def run_handoff():
            parts = engine._extract_raw_fn(bt)(engine.cache, 0)
            payload = engine._payload_slice(parts, 0, bt)
            scratch.clear()   # nothing pins the scratch between runs
            handoff.send(probe_tokens, [payload])
            float(np.asarray(jax.tree.leaves(parts)[0]).flat[0])
            # ^ value-fetch sync

        run_handoff()   # compile + fault pages, untimed
        kv_handoff_ms = round(
            max(_median_time(run_handoff, iters) - t_rtt, 0.0) * 1e3, 4)

    # attn_kernel / attn_dequant sub-attribution (ISSUE 15 satellite):
    # the attention+KV bucket is a differential (nosample forward minus
    # weight read) — it cannot say what the ATTENTION itself costs vs
    # the int8 dequant riding it, which is exactly the split an
    # xla-vs-flash A/B needs to be explainable per bucket.
    attn_kernel_ms = None
    attn_dequant_ms = None
    prefill_attn_ms = None
    kv_gather_ms = None
    cfg = getattr(engine, "cfg", None)
    cache_obj = getattr(engine, "cache", None)
    if (cfg is not None and getattr(engine, "mesh", None) is None
            and isinstance(cache_obj, dict) and "k" in cache_obj):
        import jax.numpy as jnp

        from kubeflow_tpu.models import llama as _llama

        quantized = "k_s" in cache_obj
        # paged engines (serving/paged.py) keep pool blocks, not slot
        # rows: the probes read KV through the slot block tables — the
        # same indirection the decode program pays
        paged = "tbl" in cache_obj
        bt_blk = int(cache_obj["k"].shape[2]) if paged else 0
        nb = min(span // bt_blk, int(cache_obj["tbl"].shape[1])) \
            if paged else 0
        n_layers = int(cache_obj["k"].shape[0])
        q_probe = jax.random.normal(
            jax.random.key(7),
            (n_slots, 1, cfg.n_heads, cfg.head_dim)).astype(cfg.dtype)

        def _layer_span(cache, name, li):
            """One layer's rows as the probes below take them: payload
            [slots, span, kv, hd], scales [slots, span, kv] (the cache
            keeps scales lane-major, [.., kv, max_len])."""
            rows_all = jax.lax.dynamic_index_in_dim(
                cache[name], li, axis=0, keepdims=False)
            if name.endswith("_s"):
                rows_all = jnp.swapaxes(rows_all, 1, 2)
            if paged:
                return rows_all   # whole pool layer; the table slices
            return jax.lax.slice_in_dim(rows_all, 0, span, axis=1)

        kv_names = ("k", "v", "k_s", "v_s") if quantized else ("k", "v")

        @jax.jit
        def attn_probe(cache, lengths):
            positions = lengths[:, None]   # S_v=1: one decode step
            tbl_b = cache["tbl"][:, :nb] if paged else None
            kv = {name: cache[name] for name in kv_names}

            def body(acc, li):
                # the decode program's own call: the cache whole, the
                # layer by its index
                out = _llama.decode_attention(
                    cfg, q_probe, kv, li, positions, span=span,
                    tables=tbl_b)
                return acc + jnp.sum(out.astype(jnp.float32)), None

            acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                                  jnp.arange(n_layers))
            return acc

        def run_attn():
            float(np.asarray(attn_probe(engine.cache, engine.lengths)))

        run_attn()   # compile + fault pages, untimed
        attn_kernel_ms = round(
            max(_median_time(run_attn, iters) - t_rtt, 0.0) * 1e3, 4)

        # prefill_attn probe (ISSUE 20 satellite): one continuation
        # CHUNK of the selected prefill-attention impl (xla masked mha
        # or the Pallas flash-prefill kernel) per layer against the
        # live span — the TTFT-side twin of attn_kernel, so the
        # serving_prefill_kernels A/B delta has a bucket to land in.
        # Paged-aware: the probe reads KV through the slot block
        # tables, exactly like the chunked-prefill program.
        span_p = nb * bt_blk if paged else span
        pchunk = max(1, min(32, span_p))
        q_off = span_p - pchunk
        qp_probe = jax.random.normal(
            jax.random.key(11),
            (n_slots, pchunk, cfg.n_heads, cfg.head_dim)).astype(cfg.dtype)

        @jax.jit
        def prefill_probe(cache):
            tbl_b = cache["tbl"][:, :nb] if paged else None

            def body(acc, li):
                out = _llama.prefill_attention(
                    cfg, qp_probe,
                    _layer_span(cache, "k", li),
                    _layer_span(cache, "v", li),
                    _layer_span(cache, "k_s", li) if quantized else None,
                    _layer_span(cache, "v_s", li) if quantized else None,
                    q_offset=q_off, tables=tbl_b)
                return acc + jnp.sum(out.astype(jnp.float32)), None

            acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                                  jnp.arange(n_layers))
            return acc

        def run_prefill_attn():
            float(np.asarray(prefill_probe(engine.cache)))

        run_prefill_attn()   # compile + fault pages, untimed
        prefill_attn_ms = round(
            max(_median_time(run_prefill_attn, iters) - t_rtt, 0.0)
            * 1e3, 4)

        def _gathered_span(cache, name, li):
            """The slot×span KV volume through the block tables (the
            paged read path): [slots, nb*bt, ...]."""
            pool = jax.lax.dynamic_index_in_dim(
                cache[name], li, axis=0, keepdims=False)
            g = jnp.take(pool, cache["tbl"][:, :nb], axis=0)
            if name.endswith("_s"):   # pool scales: [N, kv, bt]
                g = jnp.swapaxes(g, 2, 3)
            return g.reshape((g.shape[0], nb * bt_blk) + g.shape[3:])

        if quantized:
            @jax.jit
            def dequant_probe(cache):
                def body(acc, li):
                    read = _gathered_span if paged else _layer_span
                    k = _llama.dequantize_kv(
                        read(cache, "k", li),
                        read(cache, "k_s", li), cfg.dtype)
                    v = _llama.dequantize_kv(
                        read(cache, "v", li),
                        read(cache, "v_s", li), cfg.dtype)
                    return acc + (jnp.sum(k.astype(jnp.float32))
                                  + jnp.sum(v.astype(jnp.float32))), None

                acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                                      jnp.arange(n_layers))
                return acc

            def run_dequant():
                float(np.asarray(dequant_probe(engine.cache)))

            run_dequant()   # compile + fault pages, untimed
            attn_dequant_ms = round(
                max(_median_time(run_dequant, iters) - t_rtt, 0.0) * 1e3,
                4)
        else:
            attn_dequant_ms = 0.0   # nothing to dequantize, by definition

        if paged:
            # kv_gather (ISSUE 19 satellite): what the block-table
            # INDIRECTION itself costs — the same slot×span KV volume
            # read once through the tables (jnp.take over the block
            # axis) and once as a contiguous block range. The
            # difference is the tax paged residency puts on every
            # decode step's KV read; None on slab engines, where reads
            # are contiguous by construction.
            vol = min(n_slots * nb, int(cache_obj["k"].shape[1]))

            @jax.jit
            def gather_read(cache):
                def body(acc, li):
                    gk = _gathered_span(cache, "k", li)
                    gv = _gathered_span(cache, "v", li)
                    return acc + (jnp.sum(gk.astype(jnp.float32))
                                  + jnp.sum(gv.astype(jnp.float32))), None

                acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                                      jnp.arange(n_layers))
                return acc

            @jax.jit
            def contig_read(cache):
                def body(acc, li):
                    kl = jax.lax.dynamic_index_in_dim(
                        cache["k"], li, axis=0, keepdims=False)
                    vl = jax.lax.dynamic_index_in_dim(
                        cache["v"], li, axis=0, keepdims=False)
                    ck = jax.lax.slice_in_dim(kl, 0, vol, axis=0)
                    cv = jax.lax.slice_in_dim(vl, 0, vol, axis=0)
                    return acc + (jnp.sum(ck.astype(jnp.float32))
                                  + jnp.sum(cv.astype(jnp.float32))), None

                acc, _ = jax.lax.scan(body, jnp.float32(0.0),
                                      jnp.arange(n_layers))
                return acc

            def run_gather():
                float(np.asarray(gather_read(engine.cache)))

            def run_contig():
                float(np.asarray(contig_read(engine.cache)))

            run_gather(); run_contig()   # compile, untimed
            kv_gather_ms = round(
                max(_median_time(run_gather, iters)
                    - _median_time(run_contig, iters), 0.0) * 1e3, 4)

    per_step = 1e3 / steps
    dev_full_ms = max(t_full - t_rtt, 0.0) * per_step
    dev_nosample_ms = max(t_nosample - t_rtt, 0.0) * per_step
    weight_read_ms = t_read * 1e3
    sampling_ms = max(dev_full_ms - dev_nosample_ms, 0.0)
    attn_kv_ms = max(dev_nosample_ms - weight_read_ms, 0.0)

    perf = engine.perf_counters()
    host_ms = None
    dispatch_host_ms = None
    if perf.get("decode_steps"):
        host_ms = round(perf["fetch_replay_s"] * 1e3
                        / perf["decode_steps"], 4)
        dispatch_host_ms = round(perf["dispatch_s"] * 1e3
                                 / perf["decode_steps"], 4)

    out = {
        "steps": steps, "span": span, "n_slots": n_slots,
        "fill_len": fill_len, "iters": iters,
        "chunk_wall_ms": round(t_full * 1e3, 4),
        "device_step_ms": round(dev_full_ms, 4),
        "dispatch_rtt_ms": round(t_rtt * 1e3, 4),
        "weight_read_bytes": read_bytes,
        "weight_read_gbps": round(read_bytes / max(t_read, 1e-9) / 1e9, 1),
        "buckets_ms": {
            "weight_read": round(weight_read_ms, 4),
            "attention_kv_update": round(attn_kv_ms, 4),
            # probe-based sub-attribution of attention_kv_update (the
            # xla-vs-flash A/B lever vs the int8 read+convert tax); not
            # part of the bucket partition
            "attn_kernel": attn_kernel_ms,
            "attn_dequant": attn_dequant_ms,
            # one continuation chunk of the selected prefill-attention
            # impl per layer over the live span (per CHUNK, not per
            # decode step — it rides prefill cadence); None when the
            # cache isn't a single-program slab/pool
            "prefill_attn": prefill_attn_ms,
            "sampling_penalties": round(sampling_ms, 4),
            "dispatch_rtt_per_step": round(t_rtt * per_step, 4),
            "host_fetch_replay_per_step": host_ms,
            # per BLOCK handed off, not per step: the handoff rides
            # prefill completion, so its cadence is per-request
            "kv_handoff": kv_handoff_ms,
            # block-table indirection tax on the decode-span KV read
            # (gather through slot tables minus contiguous read of the
            # same volume); None on slab engines, whose reads are
            # contiguous by construction
            "kv_gather": kv_gather_ms,
            # per-stage idle wall per decode step (stage-sharded
            # engines with stage_timing armed; None elsewhere)
            "pipeline_bubble": pipe_bubble_ms,
        },
        # live engine counters for the host-side buckets (per-chunk wall
        # the host spent dispatching vs fetching+replaying, amortized)
        "host_dispatch_per_step_ms": dispatch_host_ms,
        "perf_counters": perf,
    }
    if pipe_snap is not None:
        out["pipeline"] = pipe_snap
    if hbm_gbps:
        floor_ms = read_bytes / (hbm_gbps * 1e9) * 1e3
        out["weight_read_floor_ms"] = round(floor_ms, 4)
        out["weight_read_frac_of_peak"] = round(
            floor_ms / max(weight_read_ms, 1e-9), 4)
    if trace_dir:
        # the trace artifact: one full chunk under jax.profiler (the
        # breakdown above is what bench records; the trace is for humans
        # in tensorboard-plugin-profile, registered like any other dir)
        try:
            reset_state()
            with trace(trace_dir):
                run_decode(fn_full)()
            with open(os.path.join(trace_dir, "PROFILE_DONE"), "w") as f:
                f.write(f"decode chunk steps={steps} span={span}\n")
            out["trace_dir"] = trace_dir
        except Exception as e:   # profiling must never kill the bench
            out["trace_error"] = f"{type(e).__name__}: {e}"

    # leave the engine exactly as warmup does: slot state reset, host
    # mirrors zeroed (the junk cache rows are dead — the next prefill
    # into a slot rewrites them). The pipeline counters reset too: the
    # nosample/trace runs above fired record_step after the committed
    # snapshot, and profiler junk must not leak into the next live
    # metrics()["pipeline"] read.
    if hasattr(engine, "pipeline_perf"):
        engine.pipeline_perf(reset=True)
    engine.lengths = engine._put(np.zeros((n_slots,), np.int32))
    engine.last_tokens = engine._put(np.zeros((n_slots,), np.int32))
    engine.samp = engine._put(engine._samp_reset())
    engine._host_lengths[:] = 0
    engine._pending = None
    engine._inflight[:] = 0
    engine._active_host = None
    engine._active_dev = None
    return out
