"""Stage-sharded multichip serving (ISSUE 14, ROADMAP #2): tp×pp decode.

A 31B-class int8 llama geometry does not fit one chip, and a pure
tensor-parallel layout stops paying past the ICI-efficient group size —
the remaining single-replica scaling axis is PIPELINE stages. This
module promotes the GPipe stage split (parallel/pipeline.py) from a
training schedule to a first-class serving configuration:

  - `LLMEngine`'s compiled-program menu is re-pointed at PER-STAGE
    programs: stage s holds layers [lo_s, hi_s) as a params slab
    (tensor-sharded over its own sub-mesh when `tensor` > 1 — the
    `("stage", "tensor")` mesh spec) plus that slab's KV cache
    [L_s, slots, max_len, kv, hd] — the cache is threaded per-stage,
    never materialized whole;
  - decode runs MPMD-style: the active wave splits into pp microbatches
    of slots and flows through the stages on the GPipe wavefront
    (parallel/pipeline.wavefront), so stage k decodes microbatch i while
    stage k-1 decodes microbatch i+1 — per-stage programs dispatch async
    onto disjoint device groups, which is what overlaps them on real
    hardware. Prefill waves pipeline through the same stages (each
    wave's stage-0 program dispatches before earlier waves fetch), so
    chunked prefill chains fill decode's bubbles instead of stalling
    behind a monolithic program;
  - sampling/penalties/stop/cancel/radix logic is NOT duplicated: the
    drivers reuse every host-side engine mechanism and the models/llama
    `*_inner` bodies, so greedy/seeded output is byte-exact against the
    single-program engine (tests/test_multichip_serving.py);
  - prefix-KV reuse stays correct under pp: blocks bank per-stage with
    the stage id IN the radix block key (kvcache.StagePartitionedKVCache
    — namespace (ns, stage)), so a cached chain always materializes the
    right slab slices and uneven eviction truncates to the common
    prefix.

Like every engine, `StageShardedEngine` may only be constructed inside
a supervisor factory (scripts/check_dataplane.py lints the name);
`llm_runtime` builds it from `config.parallel: {tensor: T, stage: P}`.

Not supported (loudly): speculative decoding and multi-adapter LoRA —
both thread extra per-step device state (history buffer, adapter
stacks) through the single program; their stage-sharded forms are
follow-on work, and the single-program engine keeps serving them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.kvcache import RadixKVCache, StagePartitionedKVCache
from kubeflow_tpu.models import llama
from kubeflow_tpu.obs.trace import TRACER
from kubeflow_tpu.parallel.mesh import active_mesh
from kubeflow_tpu.parallel.pipeline import (InferenceStagePlan, StageClock,
                                            resolve_schedule,
                                            split_stage_params, wavefront)
from kubeflow_tpu.serving.llm import LLMEngine, named_program


class StageShardedEngine(LLMEngine):
    """Continuous-batching engine whose model forward is decomposed into
    `stage` per-stage compiled programs, each optionally tensor-sharded
    over its own sub-mesh. Drop-in for LLMEngine everywhere the
    dataplane cares (submit/step/cancel/metrics/request_timing), with
    byte-exact greedy/seeded output."""

    role = "stage_sharded"

    def __init__(self, params, cfg: llama.LlamaConfig, *, stage: int = 2,
                 tensor: int = 1, devices=None, stage_timing: bool = False,
                 stage_schedule: str | None = None, **kw):
        if kw.get("speculative"):
            raise ValueError(
                "speculative decoding is not supported with stage "
                "parallelism (the history buffer threads the single "
                "program); serve spec traffic on the single-program "
                "engine")
        if kw.get("adapters"):
            raise ValueError(
                "multi-adapter serving is not supported with stage "
                "parallelism yet")
        if kw.get("mesh") is not None:
            raise ValueError(
                "StageShardedEngine owns its mesh: pass stage=/tensor=, "
                "not mesh=")
        kw.pop("mesh", None)
        if kw.pop("kv_layout", "slab") != "slab":
            # ISSUE 19 boundary: the paged block pool is single-device
            # (one pool, one table, one donation chain); per-stage
            # pools are a follow-up. Stage KV stays slab rows.
            raise ValueError(
                "StageShardedEngine keeps per-stage KV SLABS: "
                "kv_layout=paged is not supported with stage "
                "parallelism (serving/paged.py is single-program)")
        if tensor > 1 and cfg.n_kv_heads % tensor:
            raise ValueError(
                f"n_kv_heads={cfg.n_kv_heads} must divide by the tensor "
                f"axis ({tensor}) to shard the per-stage KV slabs")
        n_slots = int(kw.get("n_slots", 4))
        if tensor > 1 and cfg.decode_attention_impl == "auto":
            # per-stage programs with tensor > 1 are GSPMD-sharded over
            # the stage sub-mesh — same reason the base engine's mesh
            # path pins "auto" to the einsum: a pallas custom call has
            # no SPMD partitioning rule yet (ROADMAP #5's remaining
            # half). tensor == 1 stages run whole on one device and
            # take the kernel like the single-program engine.
            import dataclasses

            cfg = dataclasses.replace(cfg, decode_attention_impl="xla")
        if tensor > 1 and cfg.prefill_attention_impl == "auto":
            # same boundary for the prefill kernel (ISSUE 20): "auto"
            # pins to the mha einsum under tensor sharding; an explicit
            # "flash" is honored — the operator owns the layout claim
            import dataclasses

            cfg = dataclasses.replace(cfg, prefill_attention_impl="xla")
        # -- stage schedule (ISSUE 20): "sync" walks the wavefront with
        # per-program blocking when timing is armed (the r13 shape);
        # "overlapped" keeps every dispatch async — stage s's program
        # for microbatch m+1 enters the queue while m's outputs are
        # still in flight — and times per-stage dispatch→drain windows
        # instead. Resolution: the explicit ctor arg, else sync.
        self.stage_schedule = resolve_schedule(stage_schedule)
        # geometry + placement first: _alloc_cache/_put run inside the
        # base __init__ and need the plan
        self._plan = InferenceStagePlan(cfg.n_layers, stage, n_slots,
                                        tensor=tensor, devices=devices)
        self._plan.perf.schedule = self.stage_schedule
        self.n_stages = self._plan.n_stages
        self.tensor = self._plan.tensor
        self.stage_timing = bool(stage_timing)
        self._home_sharding = self._plan.replicated(self.n_stages - 1)
        self._cnt_sh_stage = None
        last_sm = self._plan.submeshes[-1]
        if last_sm is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # penalty counts shard over vocab on the LAST stage's
            # sub-mesh, like the lm_head logits they edit (the base
            # engine's _cnt_sh, scoped to the tail programs' mesh)
            self._cnt_sh_stage = NamedSharding(last_sm, P(None, "tensor"))
        self._stage_progs: dict[tuple, Any] = {}
        self._tail_progs: dict[tuple, Any] = {}
        self._slabs: list[dict] | None = None
        super().__init__(params, cfg, **kw)
        # split the (possibly int8-quantized) stack into per-stage slabs
        # placed on their sub-meshes; the full tree is dropped — drivers
        # only ever read self._slabs (self.params aliases it so close()
        # and the profiler's weight-read probe see the real residency)
        log_full = llama.logical_axes_for(self.params, cfg)
        raw = split_stage_params(self.params, self._plan.bounds)
        slabs = []
        for s, slab in enumerate(raw):
            logical = {"layers": log_full["layers"]}
            if s == 0:
                logical["embed"] = log_full["embed"]
            if s == self.n_stages - 1:
                logical["final_norm"] = log_full["final_norm"]
                logical["lm_head"] = log_full["lm_head"]
            slabs.append(self._plan.shard_slab(slab, s, logical))
        self._slabs = slabs
        self.params = slabs
        if self._home_sharding is not None:
            self.rng_key = jax.device_put(self.rng_key,
                                          self._home_sharding)
        if self.prefix_cache_enabled and self.kvcache is not None:
            # stage-id enters the radix block key: one shared pool, each
            # logical block stored once per stage slab. Capacity scales
            # by pp so the LOGICAL capacity the operator configured is
            # preserved (a logical block costs pp physical blocks).
            self.kvcache = StagePartitionedKVCache(
                RadixKVCache(self.prefix_block_tokens,
                             self.kvcache.capacity_blocks * self.n_stages),
                self.n_stages)

    # -- placement ------------------------------------------------------------

    def _put(self, x):
        """Host array → the engine's HOME devices (the last stage's
        sub-mesh, where the sampler tail runs); plain asarray under
        virtual staging."""
        if self._home_sharding is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._home_sharding)

    def _constrain_cnt(self, cnt):
        if self._cnt_sh_stage is None:
            return cnt
        return jax.lax.with_sharding_constraint(cnt, self._cnt_sh_stage)

    def _alloc_cache(self):
        """Per-stage KV slabs [L_s, slots, max_len, kv, hd] (+ int8
        scale planes), each allocated on ITS stage's sub-mesh — a cache
        that only fits stage-sharded never exists whole. The sampler
        state (penalty counts) lives with the tail programs on the last
        stage."""
        stages = []
        for s, (lo, hi) in enumerate(self._plan.bounds):
            scfg = dataclasses.replace(self.cfg, n_layers=hi - lo)
            slab = llama.init_cache(scfg, self.n_slots, self.max_len,
                                    kv_quantize=self.kv_quantize)
            if self._plan.submeshes[s] is not None:
                slab = {k: jax.device_put(
                    v, self._plan.cache_sharding(s, k))
                    for k, v in slab.items()}
            stages.append(slab)
        cnt = jnp.zeros((self.n_slots, self.cfg.vocab_size), jnp.int32)
        if self._cnt_sh_stage is not None:
            cnt = jax.device_put(cnt, self._cnt_sh_stage)
        return {"stages": stages, "cnt": cnt}

    # -- per-stage compiled programs ------------------------------------------

    def _stage_jit(self, s: int, run, **jit_kw):
        """jit one stage program; its body traces with the stage's
        sub-mesh ambient (see llm._under_engine_mesh), so a
        tensor-sharded stage keeps the int8 matmul partitionable while a
        one-device stage takes the kernels like the single-program
        engine."""
        submesh = self._plan.submeshes[s]
        if submesh is None:
            return jax.jit(run, **jit_kw)

        def traced(*args):
            with active_mesh(submesh):
                return run(*args)

        return jax.jit(traced, **jit_kw)

    def _stage_prefill_prog(self, s: int, bucket: int, width: int):
        key = ("prefill", s, bucket, width)
        if key not in self._stage_progs:
            first = s == 0
            last = s == self.n_stages - 1

            def run(slab, cache_slab, wave, x_in):
                tokens, slots, prompt_lens, _row_samp, _aids = \
                    self._unpack_wave(wave)
                positions = jnp.arange(bucket)
                x = (slab["embed"].astype(self.cfg.dtype)[tokens]
                     if first else x_in)
                x, (ks, vs) = llama.prefill_inner(slab["layers"], x,
                                                  positions, self.cfg)
                cache_slab = dict(cache_slab)
                for i in range(width):   # W is static: unrolled updates
                    cache_slab = self._cache_write(
                        cache_slab, slots[i], 0, bucket, ks[:, i], vs[:, i])
                if last:
                    return cache_slab, llama.lm_head(
                        slab, x, self.cfg, prompt_lens - 1)
                return cache_slab, x

            if first:
                fn = self._stage_jit(
                    s, lambda slab, c, wave: run(slab, c, wave, None),
                    donate_argnums=(1,))
            else:
                fn = self._stage_jit(s, run, donate_argnums=(1,))
            self._stage_progs[key] = fn
        return self._stage_progs[key]

    def _stage_cont_prog(self, s: int, p: int, t: int, width: int):
        key = ("cont", s, p, t, width)
        if key not in self._stage_progs:
            first = s == 0
            last = s == self.n_stages - 1

            def run(slab, cache_slab, wave, k_prefix, v_prefix, x_in):
                tokens, slots, prompt_lens, _row_samp, _aids = \
                    self._unpack_wave(wave)
                positions = p + jnp.arange(t)
                x = (slab["embed"].astype(self.cfg.dtype)[tokens]
                     if first else x_in)
                x, (ks, vs) = llama.prefill_continue_inner(
                    slab["layers"], x, k_prefix, v_prefix, positions,
                    self.cfg)
                cache_slab = dict(cache_slab)
                for i in range(width):
                    cache_slab = self._cache_write(
                        cache_slab, slots[i], 0, p,
                        k_prefix[:, i], v_prefix[:, i])
                    cache_slab = self._cache_write(
                        cache_slab, slots[i], p, t, ks[:, i], vs[:, i])
                if last:
                    return cache_slab, llama.lm_head(
                        slab, x, self.cfg, prompt_lens - p - 1)
                return cache_slab, x

            if first:
                fn = self._stage_jit(
                    s, lambda slab, c, wave, kp, vp:
                    run(slab, c, wave, kp, vp, None),
                    donate_argnums=(1,))
            else:
                fn = self._stage_jit(s, run, donate_argnums=(1,))
            self._stage_progs[key] = fn
        return self._stage_progs[key]

    def _stage_dec_prog(self, s: int, m: int, span: int):
        """Stage s's decode program for microbatch m: embed (first) /
        activations in, slab-attention against the stage's KV slab rows
        [mb_start, mb_start+mb_size), logits out (last). The slab is the
        FULL-slot cache; verify_inner's slot_start windows it."""
        mb_start, mb_size = self._plan.mb_ranges[m]
        key = ("dec", s, mb_start, mb_size, span)
        if key not in self._stage_progs:
            first = s == 0
            last = s == self.n_stages - 1

            def run(slab, cache_slab, x_in, lengths):
                lengths_mb = jax.lax.slice_in_dim(
                    lengths, mb_start, mb_start + mb_size, axis=0)
                if first:
                    toks = jax.lax.slice_in_dim(
                        x_in, mb_start, mb_start + mb_size, axis=0)
                    x = slab["embed"].astype(self.cfg.dtype)[toks[:, None]]
                else:
                    x = x_in
                x, new_cache = llama.verify_inner(
                    slab["layers"], x, cache_slab, lengths_mb, self.cfg,
                    span=span, slot_start=mb_start)
                if last:
                    return new_cache, llama.lm_head(slab, x,
                                                    self.cfg)[:, 0]
                return new_cache, x

            self._stage_progs[key] = self._stage_jit(
                s, run, donate_argnums=(1,))
        return self._stage_progs[key]

    def _tail_prefill_prog(self, cols: int, width: int):
        """The shared sampler tail after a (continuation) prefill wave's
        last stage: exactly the single program's post-forward sequence —
        lengths/samp updates, _choose over the gathered last-row logits,
        penalty-count reset, packed output rows."""
        key = ("tail_prefill", cols, width)
        if key not in self._tail_progs:
            def run(stacked, wave, lengths, last_tokens, samp, key_, cnt):
                _toks, slots, prompt_lens, row_samp, _aids = \
                    self._unpack_wave(wave)
                for i in range(width):
                    lengths = lengths.at[slots[i]].set(prompt_lens[i])
                    samp = samp.at[slots[i]].set(row_samp[i])
                zero_cnt = jnp.zeros((width, cnt.shape[1]), cnt.dtype)
                key_, toks = self._choose(stacked, row_samp, key_, slots,
                                          zero_cnt, prompt_lens)
                for i in range(width):
                    last_tokens = last_tokens.at[slots[i]].set(toks[i])
                    cnt = cnt.at[slots[i]].set(jax.nn.one_hot(
                        toks[i], cnt.shape[1], dtype=cnt.dtype))
                return (lengths, last_tokens, samp, key_,
                        self._constrain_cnt(cnt),
                        self._pack_out(toks, stacked))

            self._tail_progs[key] = jax.jit(
                run, donate_argnums=(2, 3, 4, 5, 6))
        return self._tail_progs[key]

    def _tail_dec_prog(self):
        key = "tail_dec"
        if key not in self._tail_progs:
            def run(logits, lengths, last_tokens, samp, key_, cnt, active):
                slots = jnp.arange(self.n_slots)
                key_, toks = self._choose(logits, samp, key_, slots,
                                          cnt, lengths + 1)
                cnt = self._constrain_cnt(jax.lax.cond(
                    jnp.any((samp[:, 3] != 0) | (samp[:, 4] != 0)),
                    lambda c: c.at[slots, toks].add(
                        active.astype(c.dtype)),
                    lambda c: c, cnt))
                lengths = lengths + active.astype(jnp.int32)
                last_tokens = jnp.where(active, toks, last_tokens)
                return (lengths, last_tokens, key_, cnt,
                        self._pack_out(toks, logits))

            self._tail_progs[key] = jax.jit(
                run, donate_argnums=(1, 2, 4, 5))
        return self._tail_progs[key]

    # -- drivers (the engine menu's stage-sharded twins) ----------------------
    # Same call signatures as the single jitted programs, so step()/
    # warmup()/_do_decode() drive them unchanged. Dispatches
    # are async (the host never fetches inside a driver), so stage
    # programs of successive waves/microbatches overlap on disjoint
    # device groups; StageClock only blocks when stage_timing is armed.

    def _prefill_fn(self, bucket: int, width: int):
        if (bucket, width) not in self._prefill_fns:
            def driver(_params, cache, lengths, last_tokens, samp, key_,
                       wave):
                # no StageClock here: the bubble accounting is DECODE-
                # scoped (prefill waves pipeline through the same
                # stages, but their busy wall must not inflate the
                # decode pipeline's busy/idle split)
                clk = StageClock(self._plan.perf, False)
                stages = cache["stages"]
                x = None
                for s in range(self.n_stages):
                    prog = self._stage_prefill_prog(s, bucket, width)
                    wave_s = self._plan.to_stage(wave, s)
                    if s == 0:
                        res = clk.run(s, lambda p=prog, w=wave_s, s=s:
                                      p(self._slabs[s], stages[s], w))
                    else:
                        x_s = self._plan.to_stage(x, s)
                        res = clk.run(s, lambda p=prog, w=wave_s, x=x_s,
                                      s=s:
                                      p(self._slabs[s], stages[s], w, x))
                    stages[s], x = res
                (lengths, last_tokens, samp, key_, cache["cnt"], out) = \
                    self._tail_prefill_prog(wave.shape[1], width)(
                        x, wave, lengths, last_tokens, samp, key_,
                        cache["cnt"])
                return cache, lengths, last_tokens, samp, key_, out

            self._prefill_fns[bucket, width] = driver
        return self._prefill_fns[bucket, width]

    def _cont_fn(self, p: int, t: int, width: int):
        if (p, t, width) not in self._cont_fns:
            def driver(_params, cache, lengths, last_tokens, samp, key_,
                       wave, k_prefix, v_prefix):
                clk = StageClock(self._plan.perf, False)  # decode-scoped
                # timing, same as the prefill driver
                stages = cache["stages"]
                x = None
                for s in range(self.n_stages):
                    prog = self._stage_cont_prog(s, p, t, width)
                    wave_s = self._plan.to_stage(wave, s)
                    if s == 0:
                        res = clk.run(
                            s, lambda pr=prog, w=wave_s, s=s:
                            pr(self._slabs[s], stages[s], w,
                               k_prefix[s], v_prefix[s]))
                    else:
                        x_s = self._plan.to_stage(x, s)
                        res = clk.run(
                            s, lambda pr=prog, w=wave_s, x=x_s, s=s:
                            pr(self._slabs[s], stages[s], w,
                               k_prefix[s], v_prefix[s], x))
                    stages[s], x = res
                (lengths, last_tokens, samp, key_, cache["cnt"], out) = \
                    self._tail_prefill_prog(wave.shape[1], width)(
                        x, wave, lengths, last_tokens, samp, key_,
                        cache["cnt"])
                return cache, lengths, last_tokens, samp, key_, out

            self._cont_fns[p, t, width] = driver
        return self._cont_fns[p, t, width]

    def _decode_driver(self, steps: int, span: int):
        S, M = self.n_stages, self._plan.n_microbatches
        overlapped = self.stage_schedule == "overlapped"

        def driver(_params, cache, lengths, last_tokens, samp, key_,
                   active):
            clk = StageClock(self._plan.perf,
                             self.stage_timing and not overlapped)
            stages = cache["stages"]
            outs = []
            for _step in range(steps):
                t_step = time.perf_counter()
                # pre-step slot state, staged onto each sub-mesh; the
                # tail advances it once per step (one _choose per step =
                # the single program's key stream, so seeded sampling
                # parity survives microbatching)
                lengths_s = [self._plan.to_stage(lengths, s)
                             for s in range(S)]
                lt0 = self._plan.to_stage(last_tokens, 0)
                acts: list = [None] * M
                # overlapped timing: per-stage dispatch→drain windows
                # (first dispatch timestamp, last output blocked AFTER
                # the whole wavefront is in flight) instead of sync
                # mode's serializing per-program brackets — the windows
                # overlap, which is exactly what the bubble re-measure
                # is after (ISSUE 20)
                t_first: list = [None] * S
                last_out: list = [None] * S
                for _tick, s, m in wavefront(M, S):
                    prog = self._stage_dec_prog(s, m, span)
                    x_in = (lt0 if s == 0
                            else self._plan.to_stage(acts[m], s))
                    if overlapped:
                        # async dispatch, never block mid-wavefront:
                        # stage s's program for microbatch m+1 enters
                        # the stream while m's outputs are in flight
                        if t_first[s] is None:
                            t_first[s] = time.perf_counter()
                        res = prog(self._slabs[s], stages[s], x_in,
                                   lengths_s[s])
                    else:
                        res = clk.run(s, lambda p=prog, x=x_in, s=s:
                                      p(self._slabs[s], stages[s], x,
                                        lengths_s[s]))
                    stages[s], acts[m] = res
                    last_out[s] = acts[m]
                if overlapped and self.stage_timing:
                    for s in range(S):
                        jax.block_until_ready(last_out[s])
                        self._plan.perf.record_stage(
                            s, time.perf_counter() - t_first[s])
                logits = (acts[0] if M == 1
                          else jnp.concatenate(acts, axis=0))
                (lengths, last_tokens, key_, cache["cnt"], out) = \
                    self._tail_dec_prog()(
                        logits, lengths, last_tokens, samp, key_,
                        cache["cnt"], active)
                outs.append(out)
                self._plan.perf.record_step(
                    M, time.perf_counter() - t_step)
            return cache, lengths, last_tokens, samp, key_, outs

        return driver

    def _decode_fn(self, steps: int, span: int | None = None):
        span = self.max_len if span is None else span
        if (steps, span) not in self._decode_fns:
            self._decode_fns[steps, span] = self._decode_driver(
                steps, span)
        return self._decode_fns[steps, span]

    # -- prefix-KV plumbing (per-stage payloads) ------------------------------

    def _extract_fn(self, p: int):
        if p not in self._extract_fns:
            prog = jax.jit(named_program(
                "extract_prefix", self._extract_prefix, p=p))

            def driver(cache, slot):
                ks, vs = [], []
                for s in range(self.n_stages):
                    k, v = prog(cache["stages"][s], slot)
                    ks.append(k)
                    vs.append(v)
                return ks, vs

            self._extract_fns[p] = driver
        return self._extract_fns[p]

    def _extract_raw_fn(self, p: int):
        if p not in self._extract_raw_fns:
            prog = jax.jit(named_program(
                "extract_prefix_raw", self._extract_prefix_raw, p=p))

            def driver(cache, slot):
                return [prog(cache["stages"][s], slot)
                        for s in range(self.n_stages)]

            self._extract_raw_fns[p] = driver
        return self._extract_raw_fns[p]

    def _materialize_prefix(self, payloads: list):
        """payloads: list over blocks of per-stage payload tuples (the
        stage-keyed store's currency) → per-stage prefix arrays
        ([k_s, ...], [v_s, ...]) for the stage continuation programs."""
        ks, vs = [], []
        for blocks in zip(*payloads):   # [stage] -> that stage's chain
            k, v = self._materialize_payloads(
                list(blocks), self.kv_quantize, self.cfg.dtype)
            ks.append(k)
            vs.append(v)
        return ks, vs

    def _stack_prefix(self, entries: list):
        ks = [jnp.concatenate([e[0][s] for e in entries], axis=1)
              for s in range(self.n_stages)]
        vs = [jnp.concatenate([e[1][s] for e in entries], axis=1)
              for s in range(self.n_stages)]
        return ks, vs

    @staticmethod
    def _payload_slice(parts, s: int, e: int):
        """parts: per-stage raw-extract tuples; the block payload is the
        per-stage tuple of token-axis slices."""
        return tuple(tuple(a[:, :, s:e] for a in sp) for sp in parts)

    # -- observability --------------------------------------------------------

    def mesh_info(self) -> dict[str, Any]:
        d = self._plan.describe()
        slab_bytes = ([int(sum(l.nbytes for l in jax.tree.leaves(s)))
                       for s in self._slabs]
                      if self._slabs is not None else [])
        return {
            "layout": f"tp{self.tensor}xpp{self.n_stages}",
            "axes": {"stage": self.n_stages, "tensor": self.tensor},
            "device_count": d["device_count"],
            "virtual_stages": d["virtual"],
            "stage_layers": d["stage_layers"],
            "microbatches": d["microbatches"],
            "params_bytes": int(sum(slab_bytes)),
            "per_stage_params_bytes": slab_bytes,
        }

    def warmup(self) -> None:
        """Base warmup through the stage drivers, then a perf reset:
        warmup's junk decode chunks (and their XLA compiles, when
        stage_timing is armed) must not pollute the committed bubble
        accounting."""
        super().warmup()
        self._plan.perf.reset()

    def pipeline_perf(self, reset: bool = False) -> dict[str, Any]:
        """Per-stage busy/idle accounting (the pipeline_bubble_frac
        surface — measured when `stage_timing` is on, schedule-derived
        always)."""
        snap = self._plan.perf.snapshot()
        snap["microbatches"] = self._plan.n_microbatches
        snap["stage_timing"] = self.stage_timing
        if reset:
            self._plan.perf.reset()
        return snap

    def metrics(self) -> dict[str, Any]:
        out = super().metrics()
        out["pipeline"] = self.pipeline_perf()
        return out

    def _obs_finish(self, req_id: int) -> None:
        """Base per-request spans plus one retrospective ``stage`` span
        per pipeline stage over the request's decode window — emitted at
        finish from the plan geometry, NEVER from inside the wavefront
        loop (per-microbatch spans at decode rate are exactly what the
        sampling design forbids)."""
        trace = self._req_trace.get(req_id)
        first = self._first_token_t.get(req_id)
        fin = self._finish_t.get(req_id)
        super()._obs_finish(req_id)
        if trace is None or first is None or fin is None \
                or not TRACER.sampled(trace):
            return
        perf = self._plan.perf
        for s, (lo, hi) in enumerate(self._plan.bounds):
            TRACER.record_span(
                f"{self.role}.stage{s}", "stage", trace, first, fin,
                stage=s, layers=[lo, hi],
                microbatches=self._plan.n_microbatches,
                tensor=self.tensor,
                schedule_bubble_frac=perf.schedule_bubble_frac())

    def close(self) -> None:
        self._stage_progs.clear()
        self._tail_progs.clear()
        self._slabs = None
        super().close()
