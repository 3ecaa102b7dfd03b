"""Continuous-batching LLM engine — the KServe/Triton-GPU serving runtime
replaced by a TPU-native design (SURVEY.md §2.6, BASELINE config #5: the
Llama InferenceService TTFT metric runs through this engine).

Split into the two halves the hardware wants:

  - **Scheduling** (C++ core, serving/scheduler.py): request queue, decode
    slots, prefill-bucket choice. Decisions only — never touches tensors.
  - **Execution** (this module): a fixed menu of compiled XLA programs —
    one prefill program per bucket length plus ONE decode program over all
    slots — so serving never recompiles. Static shapes are the TPU
    constraint the whole design bends around: variable prompts are padded
    up to a bucket; the decode batch always runs full-width with inactive
    slots masked by the engine.

Prefill priority keeps TTFT low; decode always re-batches every step
(continuous batching), so finished slots refill immediately from the queue.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.kvcache import RadixKVCache
from kubeflow_tpu.models import llama as DEFAULT_FAMILY
from kubeflow_tpu.obs import metrics as obs_metrics
from kubeflow_tpu.obs.trace import TRACER, PhaseClock, StepAggregator
from kubeflow_tpu.ops import quant
from kubeflow_tpu.parallel.mesh import active_mesh
from kubeflow_tpu.serving.scheduler import (DecodeAction, PrefillAction,
                                            PromptTooLong, make_scheduler)


def _ngram_draft(hist, lengths, k: int, n: int):
    """Prompt-lookup drafting, vectorized over slots (device-side — no host
    round-trip, so it can live inside the scanned decode program).

    hist: [B, L] token history — positions 0..lengths[b] are real (the token
    at `lengths` is the pending last token, recorded by the caller just
    before drafting); beyond that is stale garbage the masks exclude.
    For each slot: find the LATEST position j < lengths where the n-gram
    hist[j-n+1..j] equals the context's trailing n-gram hist[lengths-n+1..
    lengths], and propose the k tokens that followed it. Returns
    (drafts [B, k] int32, count [B] int32) — count is how many proposals
    are real (0 when no match / not enough known continuation tokens).
    """
    b, l = hist.shape
    gram_pos = jnp.clip(lengths[:, None] + jnp.arange(1 - n, 1)[None],
                        0, l - 1)
    gram = jnp.take_along_axis(hist, gram_pos, axis=1)  # [B, n]
    # window ending at j matches iff hist[j-n+1+t] == gram[t] for all t;
    # n static slices — the whole match is a handful of [B, L] compares
    m = jnp.ones((b, l - n + 1), bool)
    for t in range(n):
        m = m & (hist[:, t:l - n + 1 + t] == gram[:, t:t + 1])
    jend = jnp.arange(n - 1, l)[None]  # window-end position per column
    valid = m & (jend < lengths[:, None]) & (lengths[:, None] >= n)
    j_best = jnp.max(jnp.where(valid, jend, -1), axis=1)  # [B]; -1 = none
    dpos = jnp.clip(j_best[:, None] + 1 + jnp.arange(k)[None], 0, l - 1)
    drafts = jnp.take_along_axis(hist, dpos, axis=1).astype(jnp.int32)
    # continuation tokens are only known through position `lengths`
    count = jnp.where(j_best >= 0, jnp.clip(lengths - j_best, 0, k), 0)
    return drafts, count.astype(jnp.int32)


def _fold_seed24(seed: int) -> int:
    """Fold an arbitrary non-negative seed onto the f32-exact 24-bit range
    the packed sampling row can carry, via the splitmix64 finalizer.
    Collisions necessarily exist (2^24 buckets), but — unlike the previous
    plain modulus — seeds differing only in high bits, or by a fixed
    stride, do not trivially alias. Pure integer ops: deterministic across
    restarts, platforms, and Python versions."""
    mask = (1 << 64) - 1
    z = (seed + 0x9E3779B97F4A7C15) & mask
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
    return (z ^ (z >> 31)) & 0xFFFFFF


#: EMA step for the speculative-decode acceptance estimators (per-slot
#: draft-length policy AND the engine-wide tokens-per-round estimate the
#: chunk sizing / drain heuristic consume). ~0.25 re-anchors in a handful
#: of rounds after a workload shift while still smoothing round noise.
SPEC_EMA_ALPHA = 0.25

#: COLD-START COST: the continuation-program menu is (block-multiple
#: prefix) × (tail bucket) × log2(n_slots) full-model programs. warmup()
#: pre-compiles only the first WARM_CONT_PAIRS (prefix, tail) pairs;
#: colder pairs compile lazily on their first hit (that one wave pays
#: ~seconds of XLA compile, subsequent hits are warm).
WARM_CONT_PAIRS = 4


class AdaptiveDraftLen:
    """Per-slot EMA of accepted drafts per verify round → the NEXT round's
    draft length k (host-side policy; the device programs stay static by
    compiling one verify program per k in a small menu).

    Why adapt: a verify forward carries k+1 query positions, so its FLOPs
    and KV/history scatter cost grow with k while only ACCEPTED drafts pay
    back — static k keeps paying verify cost for drafts that never land
    once the text gets hard. The EMA tracks live acceptance per slot; each
    round drafts the smallest menu k covering the most optimistic DRAFTING
    slot (plus headroom). A round that accepts all k drafts observes k+1
    (the round was truncated by k, not by the model), so the estimate can
    climb back to k_max after a low-acceptance phase instead of ratcheting
    down permanently. Slots whose requests sample or carry penalties draft
    nothing; a batch with no drafting slot verifies at the smallest k —
    near plain-decode cost instead of k_max dead verify positions."""

    def __init__(self, k_max: int, n_slots: int, *,
                 alpha: float = SPEC_EMA_ALPHA, headroom: float = 1.25):
        if k_max < 1:
            raise ValueError("k_max must be >= 1")
        self.k_max = k_max
        self.alpha = alpha
        self.headroom = headroom
        menu, k = [], 1
        while k < k_max:     # powers of two, then k_max itself: the same
            menu.append(k)   # small-menu shape the chunk sizes use
            k *= 2
        menu.append(k_max)
        self.menu: list[int] = menu
        # optimistic start (and per-slot reset): one round of observations
        # re-anchors; the worst case of optimism is one round's surplus
        # verify positions, never junk tokens
        self.ema = np.full(n_slots, float(k_max))

    def observe(self, slot: int, accepted: int, k_round: int) -> None:
        """One verify round's outcome for `slot`: `accepted` drafts landed
        out of the `k_round` proposed. Saturated rounds (all drafts
        accepted) observe accepted+1 — the truncation was k, not the
        model — capped at k_max so the estimate can never exceed the
        configured maximum."""
        obs = min(self.k_max,
                  accepted + (1 if accepted >= k_round else 0))
        self.ema[slot] += self.alpha * (obs - self.ema[slot])

    def reset_slot(self, slot: int) -> None:
        """A new request entered the slot: its text is unknown — back to
        optimistic."""
        self.ema[slot] = float(self.k_max)

    def pick(self, drafting_slots) -> int:
        """Draft length for a round whose drafting-eligible slots are
        `drafting_slots` (greedy, penalty-free). The most optimistic slot
        sets k (acceptance is per-slot, cost is batch-wide but small next
        to the weight read); no drafting slot → smallest k."""
        slots = list(drafting_slots)
        if not slots:
            return self.menu[0]
        want = max(self.ema[s] for s in slots) * self.headroom
        for k in self.menu:
            if k >= want:
                return k
        return self.k_max


def _under_engine_mesh(program):
    """Trace an engine program body with the engine's mesh ambient
    (parallel.active_mesh — what the Trainer does around its step).
    Kernel selection inside the model bodies reads it: under a GSPMD
    mesh XLA partitions the program, so the int8 matmul must keep its
    partitionable XLA lowering (ops/quant.py), and an AOT trace for a
    TPU topology sees the target platform. Single-chip engines
    (mesh None) trace as they always did."""
    @functools.wraps(program)
    def traced(self, *args, **kw):
        if self.mesh is None:
            return program(self, *args, **kw)
        with active_mesh(self.mesh):
            return program(self, *args, **kw)
    return traced


def _row(kv, i: int):
    """Batch row `i` of a family's prefill KV: each leaf is
    [layers, batch, rows, ...]."""
    return jax.tree.map(lambda a: a[:, i], kv)


def named_program(name: str, fn, **static):
    """`fn` with `static` bound, under a function name: jax calls the
    compiled module `jit_<name>` — one name per KIND of engine program,
    whatever its chunk, span or bucket, so a device trace's modules and
    idle-gap labels are few (a bare functools.partial has no name and
    compiles as `jit__unknown`)."""
    prog = functools.partial(fn, **static)
    prog.__name__ = prog.__qualname__ = name
    return prog


def pin_attention_impls(cfg, *, sharded: bool, family=DEFAULT_FAMILY):
    """The config an engine actually runs: both serving attention impls
    resolved to "xla" or "flash", once, at construction. A program
    compiled lazily after warmup (cold span/chunk combos) re-traces the
    model bodies, and an env flip at THAT moment must not hand one engine
    a mixed-impl menu — nor let metrics()/healthz report an impl the
    warmed programs don't run.

    `sharded` (a GSPMD mesh): a pallas custom call has no SPMD
    partitioning rule, so "auto" must not hand the sharded-cache programs
    to the kernels (the int8 matmul kernel keeps the same boundary from
    the ambient mesh — _under_engine_mesh, ops/quant.py); an EXPLICIT
    "flash" is honored — the operator owns the layout claim. Otherwise
    "auto" resolves to flash only where the kernel compiles (TPU target,
    a head_dim it tiles), and an explicit "flash" the TPU compiler would
    refuse raises HERE with the reason, not at the first prefill."""
    if sharded:
        cfg = dataclasses.replace(cfg, **{
            f: "xla" for f in ("decode_attention_impl",
                               "prefill_attention_impl")
            if getattr(cfg, f) == "auto"})
    return dataclasses.replace(
        cfg,
        decode_attention_impl=family.resolve_decode_attn(cfg),
        prefill_attention_impl=family.resolve_prefill_attn(cfg))


class LLMEngine:
    """Continuous-batching generation over a model family's params:
    greedy by default, per-request temperature/top-k/top-p sampling, stop
    sequences, logprobs, and chunk-boundary cancellation.

    THE FAMILY SEAM: `family` is the model family's module (default
    `models.llama`), and everything the engine knows of a model it asks
    that module by these names: `init_cache`, `cache_write`,
    `extract_prefix`, `cache_stats` (the KV layout), `prefill`,
    `prefill_continue`, `decode_step`, `verify_step` (the bodies),
    `quantize_params`, `QUANT_LEAVES`, `quantize_kv`, `dequantize_kv`,
    `cache_kv_spec`, `logical_axes_for` (weights and sharding),
    `resolve_decode_attn`, `resolve_prefill_attn` (kernel selection) and
    `STEP_COUNTERS` (what a decode step counts), and where the family has
    it `prompt_counters` (what the prompt tokens computed count). A
    family's prefill KV is any pytree of `[layers, batch, ...]` leaves
    whose first leaf is `[layers, batch, rows, ...]`."""

    #: obs component label (overridden by role engines: prefill/decode/
    #: stage_sharded) — the `component=` of every engine-side metric and
    #: the role attribute of engine spans
    role = "engine"

    #: the model family's module (THE FAMILY SEAM); an instance may be
    #: given another
    family = DEFAULT_FAMILY

    #: KV residency: "slab" = preallocated [n_slots, max_len] rows;
    #: serving/paged.py overrides to "paged" (block pool + tables)
    kv_layout = "slab"

    #: the prefix banker extracts raw slot KV (slab layout), so warmup
    #: pre-compiles the raw-extract menu; the paged engine banks block
    #: ids instead (zero-copy) and has no such menu to warm
    _bank_uses_raw_extract = True
    #: continuation programs re-write the prefix KV into the slot rows
    #: (slab layout); the paged engine's spliced table blocks already
    #: hold those bytes, so it skips the write
    _cont_writes_prefix = True

    def __init__(self, params, cfg, *, n_slots: int = 4,
                 max_len: int = 512, buckets: Sequence[int] = (64, 128, 256),
                 max_queue: int = 1024, eos_id: int | None = None,
                 prefer_native: bool = True, decode_chunk: int = 8,
                 mesh=None, sample_seed: int = 0,
                 prefix_cache: bool = False, max_prefixes: int = 4,
                 prefix_cache_blocks: int | None = None,
                 quantize: str | None = None,
                 kv_quantize: str | None = None,
                 decode_attention_impl: str | None = None,
                 prefill_attention_impl: str | None = None,
                 speculative: int | None = None,
                 spec_ngram: int = 3,
                 spec_adaptive: bool = True,
                 adapters: dict[str, dict[str, Any]] | None = None,
                 logprobs_topk: int = 0,
                 sample_k_max: int = 64,
                 pipeline_decode: bool = True,
                 prefill_wave_max: int | None = None,
                 warm_chain: bool = False,
                 family=DEFAULT_FAMILY):
        self.family = family
        # the most prompts one prefill wave (one batched program) takes;
        # None = every slot. A cap keeps the menu of (bucket, width)
        # programs small and a wave short where prompts are long: the
        # decode steps of the slots that are running wait for it.
        if prefill_wave_max is not None and prefill_wave_max < 1:
            raise ValueError("prefill_wave_max must be >= 1")
        self.prefill_wave_max = min(n_slots, prefill_wave_max or n_slots)
        # warmup() also compiles the chunked-prefill chain (every
        # continuation pair a prompt longer than the largest bucket can
        # meet, and the extracts that feed them): for a deployment whose
        # traffic holds such prompts, where the first of each length class
        # would otherwise wait out a compile of its own
        self.warm_chain = warm_chain
        if max(buckets) >= max_len:
            raise ValueError("largest bucket must leave room to decode")
        if quantize not in (None, "int8"):
            raise ValueError(f"unknown quantize mode {quantize!r}")
        if kv_quantize not in (None, "int8"):
            raise ValueError(f"unknown kv_quantize mode {kv_quantize!r}")
        if speculative is not None and not 1 <= speculative <= 16:
            raise ValueError("speculative must be 1..16 draft tokens")
        if not 1 <= spec_ngram <= 8:
            # an upper bound too: a gram longer than the history window
            # would trace a zero-size reduction in _ngram_draft — fail
            # loudly at construction, not deep inside warmup
            raise ValueError("spec_ngram must be 1..8")
        if not 0 <= logprobs_topk <= 16:
            raise ValueError("logprobs_topk must be 0..16")
        if sample_k_max < 1:
            raise ValueError("sample_k_max must be >= 1")
        # -- sampling parity (⊘ kserve huggingfaceserver, SURVEY §2.4): the
        # decode/prefill/verify programs sample with per-request
        # temperature + top-k + top-p INSIDE the compiled programs (static
        # shapes: nucleus filtering runs over the top `sample_k_max`
        # candidates via lax.top_k — requests may not ask for a larger
        # top_k). Every program also emits the chosen token's raw-model
        # logprob; logprobs_topk > 0 additionally emits the top-N
        # alternatives per position (a static program-output width, so it
        # is an engine-level knob, not a per-request one).
        self.logprobs_topk = logprobs_topk
        self.sample_k_max = sample_k_max
        # -- speculative decoding (prompt-lookup/n-gram drafting, fully
        # device-resident): each "decode" dispatch becomes a scan of verify
        # steps — draft k tokens by matching the context's trailing n-gram
        # against a device-side token-history buffer, verify all k+1
        # positions in ONE forward (the family's verify_step), accept the
        # longest argmax-matching prefix. Greedy output is EXACTLY the
        # non-speculative output (tested); the win is tokens-per-dispatch
        # on copy-heavy / low-entropy text where drafts accept. Drafting,
        # verification, and acceptance all run inside the compiled program;
        # the host only fetches (count, tokens) rows, one round trip
        # per chunk of rounds.
        self.spec = speculative
        self.spec_ngram = spec_ngram
        # programs keyed by (rounds, attention span, draft length k): the
        # adaptive-k policy dispatches smaller-k members of the same menu
        self._spec_fns: dict[tuple[int, int, int], Any] = {}
        self._spec_tokens = 0
        self._spec_verifies = 0
        # -- adaptive draft length (per-slot EMA acceptance): the verify
        # forward's cost grows with k but only accepted drafts pay back,
        # so each round drafts the smallest compiled k covering the live
        # acceptance estimate (AdaptiveDraftLen). Off (or k_max == 1) →
        # static k, the pre-r6 behavior.
        self.spec_adaptive = bool(spec_adaptive and speculative
                                  and speculative > 1)
        self._spec_adapt = (AdaptiveDraftLen(speculative, n_slots)
                            if self.spec_adaptive else None)
        self._spec_last_k = speculative or 0
        # EMA of delivered tokens per verify round (ADVICE r5 #2: the
        # lifetime average never decayed, so chunk sizing and the drain
        # heuristic tracked a long-dead workload after a shift)
        self._spec_round_ema: float | None = None
        # -- multi-adapter LoRA serving (S-LoRA-style, XLA-shaped): many
        # fine-tunes of ONE base share the continuous batch. adapters =
        # {name: {"lora": {target: {"a": [L,d,r], "b": [L,r,out]}},
        #         "alpha": float}} — stacked on device as [L, A+1, ...]
        # with index 0 the all-zero adapter (base-only rows), b pre-scaled
        # by alpha/rank so no per-adapter scalar rides the programs. Every
        # program gathers each row's (a, b) by the slot's adapter id; the
        # low-rank bypass is tiny next to the W reads decode is bound on.
        self.adapters = None
        self._adapter_idx: dict[str, int] = {}
        self._req_aids: dict[int, int] = {}
        self._raw_adapters = dict(adapters) if adapters else None
        if adapters:
            self._adapter_idx = {n: i + 1
                                 for i, n in enumerate(sorted(adapters))}
        # packed wave rows end with [slot, prompt_len, temp_milli, top_k,
        # top_p_micro, presence_milli, freq_milli, seed] and, under
        # multi-adapter serving, an adapter-id column
        self._row_extra = 9 if adapters else 8
        # -- serving attention impls: "xla" (the mha/einsum reference) vs
        # the fused Pallas "flash" kernels over the KV layout
        # (ops/flash_decode.py, ops/flash_prefill.py). The ctor args are
        # convenience overrides of the LlamaConfig fields, so A/B
        # pairs and runtime configs need not rebuild the config.
        overrides = {}
        if decode_attention_impl is not None:
            overrides["decode_attention_impl"] = decode_attention_impl
        if prefill_attention_impl is not None:
            overrides["prefill_attention_impl"] = prefill_attention_impl
        cfg = pin_attention_impls(dataclasses.replace(cfg, **overrides),
                                  sharded=mesh is not None, family=family)
        # int8 KV cache: decode re-reads the whole (span of the) cache
        # every step, so int8 storage halves that HBM traffic vs bf16 and
        # halves cache residency (2x slots or context at 8B scale);
        # per-token-per-head scales, bf16 attention compute
        self.kv_quantize = kv_quantize
        if quantize == "int8":
            # weight-only int8 (the family's quantize_params): decode is
            # HBM-bound on weight reads, so int8 storage is the serving
            # throughput lever; done BEFORE sharding so the shards are int8
            params = family.quantize_params(params)
        self.quantize = quantize
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.buckets = tuple(sorted(buckets))
        self.eos_id = eos_id
        self.scheduler = make_scheduler(n_slots, self.buckets, max_queue,
                                        prefer_native=prefer_native)
        self.mesh = None
        if mesh is not None:
            self._shard_over(mesh)
        if self._raw_adapters:
            # after mesh setup so the stack lands replicated on the mesh
            self.adapters = self._stack_adapters(self._raw_adapters)
            del self._raw_adapters
        self.cache = self._alloc_cache()
        self.lengths = self._put(np.zeros((n_slots,), np.int32))
        self.last_tokens = self._put(np.zeros((n_slots,), np.int32))
        # per-slot sampling state [temperature, top_k, top_p,
        # presence_penalty, frequency_penalty, seed] (0/0/0/0/0/-1 =
        # greedy, filters + penalties off, engine-keyed sampling) + the
        # program-threaded PRNG key: both live on device like the rest of
        # the slot state. seed >= 0 switches that row's sampling keys to
        # request-seeded derivation (reproducible across restarts); it
        # rides the f32 samp row, so seeds are quantized to < 2^24 at
        # submit (f32-exact integers).
        self.samp = self._put(self._samp_reset())
        self.rng_key = (jax.random.key(sample_seed) if self.mesh is None
                        else jax.device_put(jax.random.key(sample_seed),
                                            self._repl))
        # per-request (temperature, top_k, top_p, presence, frequency,
        # seed) mirror for wave packing
        self._req_samp: dict[int, tuple] = {}
        # host-side stop-sequence suffix matching at chunk boundaries
        self._req_stop: dict[int, list[list[int]]] = {}
        self._host_lengths = np.zeros((n_slots,), np.int64)
        self.decode_chunk = max(1, decode_chunk)
        # the chunk menu warmup compiles (powers of two up to this);
        # set_decode_chunk clamps here post-warmup
        self._decode_chunk_warm = self.decode_chunk
        # -- decode pipelining: one dispatched-but-unfetched chunk may be
        # in flight; _inflight tracks its planned KV rows per slot so the
        # next chunk's headroom/span see through the lag
        self.pipeline_decode = pipeline_decode
        self._pending: tuple | None = None
        self._inflight = np.zeros((n_slots,), np.int64)
        # device-resident copy of the decode active mask: the mask only
        # changes at prefill/finish boundaries, so re-uploading it every
        # chunk paid a host->device transfer per chunk for identical
        # bytes
        self._active_host: np.ndarray | None = None
        self._active_dev = None
        self._warmed = False
        self._quant_matmul_sites: dict[str, int] = {}
        self._max_new: dict[int, int] = {}
        self._finish_reasons: dict[int, str] = {}

        self._prompts: dict[int, list[int]] = {}
        # rid -> instant its prefill left the queue (the engine popped
        # its PrefillAction): the queue_wait/prefill/decode phase split
        # request_timing() reports (the bench's interference attribution)
        self._prefill_start_t: dict[int, float] = {}
        self._results: dict[int, list[int]] = {}
        self._logprobs: dict[int, list[float]] = {}
        self._toplogprobs: dict[int, list[dict[int, float]]] = {}
        self._submit_t: dict[int, float] = {}
        self._first_token_t: dict[int, float] = {}
        self._done: set[int] = set()
        # -- cancellation (SURVEY §2.6 Triton-class runtimes support
        # request cancellation; a CB engine without it leaks decode
        # capacity under dropped clients). cancel() only QUEUES the id —
        # the engine thread applies it at the next chunk boundary (top of
        # step()), so no lock covers a device dispatch.
        self._cancel_pending: list[int] = []
        self._deadlines: dict[int, float] = {}
        self._cancelled_count = 0
        self._ttft_window: collections.deque[float] = collections.deque(
            maxlen=1024)
        # -- multi-tenant accounting (loadgen subsystem, ROADMAP #4): a
        # request may carry a tenant name; the scheduler sees a stable
        # integer id (max-min fair queue pop + admission caps live THERE —
        # the engine only maps names and surfaces per-request timing).
        self._tenant_idx: dict[str, int] = {}
        self._req_tenant: dict[int, str | None] = {}
        # per-request finish wall time (with _submit_t/_first_token_t this
        # is the TTFT/TPOT record the loadgen runner reads via
        # request_timing() BEFORE release())
        self._finish_t: dict[int, float] = {}
        # -- observability: optional per-request trace ids and the engine
        # thread's PHASE CLOCK (obs.trace.PhaseClock: every instant of a
        # driven step lies in one named phase; per-dispatch counter bumps
        # only — the one decode span a request gets is emitted
        # retrospectively at finish; check_observability.py lints that
        # no span objects are minted on the step/_do_decode paths).
        # _phase_mark reads the clock at a request's first token,
        # _phase_fin holds (usage `engine` object, decode-step window)
        # from its finish until release(). _append_t stamps a request's
        # newest append (tokens it had before, time.monotonic): one store
        # per request per chunk, for the stream thread that picks the
        # tokens up to time itself against (last_append).
        self._req_trace: dict[int, str] = {}
        self.phase_clock = PhaseClock(self.role, self._stall_context)
        self._phase_mark: dict[int, Any] = {}
        self._phase_fin: dict[int, tuple[dict[str, Any], dict[str, int]]] \
            = {}
        self._append_t: dict[int, tuple[int, float]] = {}
        # queue-depth gauges are pull-model: refreshed from the scheduler
        # at scrape time (weakref-held, so a dropped engine unregisters
        # itself)
        obs_metrics.add_scrape_hook(self, LLMEngine._obs_publish)
        # Guards submit vs. the engine-loop thread: held across
        # scheduler.submit + request-dict population so scheduler.next()
        # (also taken under it) can never hand out a prefill whose request
        # dicts aren't populated yet.
        self._submit_lock = threading.Lock()
        self._prefill_fns: dict[tuple[int, int], Any] = {}
        self._decode_fns: dict[int, Any] = {}
        # what the family's decode steps count (STEP_COUNTERS), summed or
        # last seen, from the packed rows' trailing columns
        self._step_counts = np.zeros((len(family.STEP_COUNTERS),))
        # -- prefix KV reuse (the kvcache tentpole, vLLM/SGLang-style and
        # TPU-shaped): a radix/block-trie index (kvcache.RadixKVCache)
        # over token sequences maps to ref-counted device KV blocks of
        # `prefix_block_tokens` tokens each (gcd of the buckets, so every
        # bucket is a whole number of blocks). On admission the engine
        # takes the LONGEST cached block-aligned prefix, skips its
        # prefill compute, and runs a continuation program over the tail
        # only; after any prefill the prompt's aligned prefix is banked
        # block-by-block (deduplicated — a multi-turn session stores only
        # each turn's new suffix blocks). Blocks stay quantized when the
        # cache is int8 (half the residency); LRU eviction never reclaims
        # a block pinned by an in-flight admission.
        self.prefix_cache_enabled = prefix_cache
        self.max_prefixes = max_prefixes
        self.prefix_block_tokens = 0
        self.kvcache: RadixKVCache | None = None
        if prefix_cache:
            bt = math.gcd(*self.buckets)
            self.prefix_block_tokens = bt
            if prefix_cache_blocks is None:
                # legacy sizing: max_prefixes was "whole largest-bucket
                # prefixes"; the block pool holds the same token volume
                prefix_cache_blocks = max(1, max_prefixes) \
                    * (self.buckets[-1] // bt)
            self.kvcache = RadixKVCache(bt, prefix_cache_blocks)
        self._prefix_hits = 0
        self._prefix_misses = 0
        # rid -> reused prefix length, set at prefill dispatch (the
        # cached_tokens / request_timing surface); rid -> prompt length
        # survives the prompt pop at finish for the same surface
        self._cached_prefix: dict[int, int] = {}
        self._req_plen: dict[int, int] = {}
        # prefill-compute accounting (tracked with or without the cache:
        # the cold bench run needs the denominator too)
        self._prefill_computed_tokens = 0
        self._prefill_reused_tokens = 0
        self._cont_fns: dict[tuple[int, int], Any] = {}
        self._extract_fns: dict[int, Any] = {}
        self._extract_raw_fns: dict[int, Any] = {}

    def _samp_reset(self) -> np.ndarray:
        """Idle per-slot sampling state: all-zero except the seed column's
        -1 sentinel (unseeded)."""
        s = np.zeros((self.n_slots, 6), np.float32)
        s[:, 5] = -1.0
        return s

    def _shard_over(self, mesh) -> None:
        """Tensor-parallel serving (BASELINE #5 at 8B scale: one engine
        spanning a slice). Params shard by the model's logical axes
        (heads/mlp/vocab over `tensor`), the KV cache by kv-heads; GSPMD
        propagates the layout through the compiled prefill/decode programs
        and inserts the ICI collectives — the serving twin of the
        trainer's sharding path (training/trainer.py)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kubeflow_tpu.parallel import MeshConfig
        from kubeflow_tpu.parallel.mesh import make_mesh
        from kubeflow_tpu.parallel.sharding import (shard_tree,
                                                    tree_logical_to_sharding)

        if isinstance(mesh, MeshConfig):
            mesh = make_mesh(mesh)
        tp = mesh.shape.get("tensor", 1)
        if self.cfg.n_kv_heads % max(tp, 1):
            raise ValueError(
                f"n_kv_heads={self.cfg.n_kv_heads} must divide by the "
                f"tensor axis ({tp}) to shard the KV cache")
        self.mesh = mesh
        self.params = shard_tree(
            self.params,
            tree_logical_to_sharding(
                self.family.logical_axes_for(self.params, self.cfg), mesh))
        # kv heads over `tensor` (the family's cache_kv_spec: no trailing
        # None, or every program would retrace on its first post-warmup call)
        self._cache_sh = {name: NamedSharding(
                              mesh, self.family.cache_kv_spec(name))
                          for name in ("k", "v", "k_s", "v_s")}
        self._repl = NamedSharding(mesh, P())
        # penalty counts shard over the vocab axis like the lm_head logits
        # they edit; every program pins this layout (a free-floating GSPMD
        # choice on the output would retrace the menu after warmup)
        self._cnt_sh = NamedSharding(mesh, P(None, "tensor"))

    def _alloc_cache(self):
        """KV cache in its final layout. Under a mesh each device allocates
        only ITS shard (make_array_from_callback) — an 8B-scale cache that
        only fits sharded must never be materialized whole on one device."""
        if self.mesh is None:
            cache = self.family.init_cache(
                self.cfg, self.n_slots, self.max_len,
                kv_quantize=self.kv_quantize, chunk=self.buckets[-1])
            # per-slot generated-token counts (int32 over the vocab) back
            # the presence/frequency penalties: ~0.5 MB/slot at 8B vocab,
            # read once per sampled row — noise next to the weight read
            cache["cnt"] = jnp.zeros((self.n_slots, self.cfg.vocab_size),
                                     jnp.int32)
            if self.spec:
                cache["hist"] = jnp.zeros((self.n_slots, self.max_len),
                                          jnp.int32)
            if self.adapters is not None:
                cache["aids"] = jnp.zeros((self.n_slots,), jnp.int32)
            return cache
        # schema derives from init_cache — ONE source of truth for the
        # cache layout (shared with serving/contract.py)
        leaves = jax.eval_shape(lambda: self.family.init_cache(
            self.cfg, self.n_slots, self.max_len,
            kv_quantize=self.kv_quantize))

        def zeros_shard(sds):
            def cb(index):
                shard = tuple(len(range(*sl.indices(dim)))
                              for sl, dim in zip(index, sds.shape))
                return np.zeros(shard, sds.dtype)
            return cb

        cache = {
            name: jax.make_array_from_callback(
                sds.shape, self._cache_sh[name], zeros_shard(sds))
            for name, sds in leaves.items()}
        cache["cnt"] = jax.device_put(
            np.zeros((self.n_slots, self.cfg.vocab_size), np.int32),
            self._cnt_sh)
        if self.spec:
            # the token-history buffer is tiny: replicate it
            cache["hist"] = jax.device_put(
                np.zeros((self.n_slots, self.max_len), np.int32), self._repl)
        if self.adapters is not None:
            cache["aids"] = jax.device_put(
                np.zeros((self.n_slots,), np.int32), self._repl)
        return cache

    def _put(self, x):
        """Host array → device; replicated across the mesh when sharded
        (uncommitted single-device inputs would fight GSPMD's layouts)."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._repl)

    def _stack_adapters(self, adapters: dict[str, dict]):
        """{name: {"lora": {t: {"a","b"}}, "alpha": f}} → device stacks
        {t: {"a": [L, A+1, d_in, r], "b": [L, A+1, r, d_out]}}. Index 0 is
        the all-zero adapter (base-only rows); b carries alpha/rank so the
        programs need no per-adapter scalar. All adapters must agree on
        rank and targets (they share one compiled gather shape)."""
        names = sorted(adapters)
        first = adapters[names[0]]["lora"]
        targets = sorted(first)
        bad = set(targets) - set(self.family.QUANT_LEAVES)
        if bad:
            # mirror LoraLlamaConfig.__post_init__: a typo'd target (e.g.
            # 'Wq') through the direct engine API must fail loudly here —
            # _adapted would otherwise silently serve the base weights
            raise ValueError(f"unknown adapter targets {sorted(bad)}; "
                             f"known: {sorted(self.family.QUANT_LEAVES)}")
        rank = first[targets[0]]["a"].shape[-1]
        stack = {}
        for t in targets:
            a_rows, b_rows = [], []
            for n in names:
                tree = adapters[n]["lora"]
                if sorted(tree) != targets:
                    raise ValueError(
                        f"adapter {n!r} targets {sorted(tree)} != {targets}")
                a, b = tree[t]["a"], tree[t]["b"]
                if a.shape[-1] != rank:
                    raise ValueError(
                        f"adapter {n!r} rank {a.shape[-1]} != {rank}; "
                        "all adapters in one engine share a rank")
                scale = float(adapters[n].get("alpha", rank)) / rank
                a_rows.append(np.asarray(a, np.float32))
                b_rows.append(np.asarray(b, np.float32) * scale)
            a0 = np.zeros_like(a_rows[0])
            b0 = np.zeros_like(b_rows[0])
            # [L, A+1, ...]: layer-leading for the lax.scan over layers
            stack[t] = {
                "a": self._put(np.stack([a0] + a_rows, axis=1)),
                "b": self._put(np.stack([b0] + b_rows, axis=1)),
            }
        return stack

    # -- compiled programs ---------------------------------------------------
    # params are an explicit argument, never a closure: a closed-over pytree
    # would be inlined into the HLO as constants (hundreds of MB shipped to
    # the compiler and frozen into the executable). All slot state (cache,
    # lengths, last_tokens) lives on device and is updated inside the jitted
    # programs — the host loop does exactly ONE device->host fetch per
    # iteration (the new tokens), which is what keeps per-step latency at
    # dispatch cost instead of several host<->device round trips.

    def _choose(self, logits, samp, key, slots, counts, positions):
        """ONE sampler for every program. logits [R, V] f32 raw model
        logits; samp [R, 6] = (temperature, top_k, top_p, presence,
        frequency, seed) per row; slots [R] per-row slot ids — unseeded
        sampling keys derive from the SLOT id, so padded duplicate rows
        (same slot, same data) sample identically and duplicate writes
        stay idempotent; counts [R, V] int32 per-row generated-token
        counts (the penalty state); positions [R] the generation position
        being sampled (prompt_len + #generated — the seeded-key input).
        Returns (next_key, tokens).

        Per-row semantics (mixing freely within one continuous batch):
          temp == 0              → greedy (bit-exact argmax over the
                                   penalized logits; with penalties off
                                   `x - 0.0` is bitwise x, so the
                                   greedy-exactness contract holds)
          temp > 0, no filters   → categorical over the full vocab
          top_k > 0 / top_p < 1  → nucleus/top-k over the top
                                   `sample_k_max` candidates (lax.top_k —
                                   the static-shape TPU form; submit()
                                   rejects top_k > sample_k_max, and a
                                   top_p nucleus wider than sample_k_max
                                   candidates is truncated there).
                                   Exact probability ties AT the cutoff
                                   admit every tied token (threshold-mass
                                   comparison), so a tie can widen the
                                   nucleus beyond the requested top_k /
                                   top_p — acceptable for f32 real-model
                                   logits where exact ties are rare.
          presence/frequency ≠ 0 → OpenAI penalties as logit edits over
                                   GENERATED tokens only (the vLLM
                                   convention): logits - presence·1[cnt>0]
                                   - frequency·cnt, applied before
                                   temperature/filters; greedy rows argmax
                                   the penalized logits (OpenAI applies
                                   penalties at temperature 0 too)
          seed >= 0              → that row's key derives from
                                   (seed, position) alone — deterministic
                                   across restarts, slots, and chunking
        top_p uses the standard smallest-prefix rule: keep candidate j
        while the cumulative mass BEFORE j is < p (so the first candidate
        always survives)."""
        temps, topks, topps = samp[:, 0], samp[:, 1], samp[:, 2]
        pres, freq = samp[:, 3], samp[:, 4]
        seeds = samp[:, 5].astype(jnp.int32)
        key, sub = jax.random.split(key)
        unseeded = jax.vmap(lambda s: jax.random.fold_in(sub, s))(slots)
        seeded = jax.vmap(
            lambda sd, pos: jax.random.fold_in(
                jax.random.fold_in(jax.random.key(sd), pos), 0x5eed))(
            jnp.maximum(seeds, 0), positions.astype(jnp.int32))
        row_keys = jax.random.wrap_key_data(jnp.where(
            (seeds >= 0)[:, None], jax.random.key_data(seeded),
            jax.random.key_data(unseeded)))
        # penalties: pres/freq == 0 rows subtract exactly 0.0, keeping
        # greedy argmax bit-identical to the raw logits. The whole edit —
        # two [R, V] f32 conversions of the count buffer plus the
        # multiply-subtracts — rides a lax.cond on "any row penalized":
        # the common all-unpenalized batch skips reading the counts at
        # all (identity branch returns logits bitwise unchanged, so the
        # greedy-exactness contract is preserved either way).
        def penalize(lg):
            return (lg
                    - pres[:, None] * (counts > 0).astype(jnp.float32)
                    - freq[:, None] * counts.astype(jnp.float32))

        logits = jax.lax.cond(jnp.any((pres != 0) | (freq != 0)),
                              penalize, lambda lg: lg, logits)
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)

        # The whole sampling pipeline (softmax + top_k window +
        # categorical over the vocab) is gated behind lax.cond on "any
        # row sampling": an all-greedy batch — the common serving case —
        # skips it entirely, which at 8B vocab is a measurable slice of
        # every decode step. The key chain advances BEFORE the cond
        # (split above), so seeded determinism is branch-independent.
        def sample_branch(logits):
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            # ONE categorical serves both modes: the filters reduce to a
            # per-row probability THRESHOLD (the smallest admitted
            # candidate's mass, from the sorted top-sample_k_max
            # prefix), and rows with filters off get threshold 0 — the
            # mask is then all-pass and the draw is BIT-IDENTICAL to an
            # unfiltered categorical, so the "top_p=1/top_k=0 matches
            # plain sampling" contract holds by construction, not by a
            # second code path.
            kmax = min(self.sample_k_max, logits.shape[-1])
            probs = jax.nn.softmax(scaled, axis=-1)
            top_vals, _ = jax.lax.top_k(probs, kmax)     # sorted desc
            cum = jnp.cumsum(top_vals, axis=-1)
            # admit candidate j while the mass BEFORE j is < p (p off =>
            # 2.0 admits all) and j < top_k (off => kmax)
            keep_p = (cum - top_vals) < jnp.where(
                (topps > 0) & (topps < 1), topps, 2.0)[:, None]
            kk = jnp.where(topks > 0, jnp.minimum(topks, kmax), kmax)
            keep = keep_p & (jnp.arange(kmax)[None] < kk[:, None])
            n_keep = jnp.maximum(jnp.sum(keep, axis=-1), 1)
            thr = jnp.take_along_axis(top_vals, n_keep[:, None] - 1,
                                      axis=1)[:, 0]
            use_filter = (topks > 0) | ((topps > 0) & (topps < 1))
            thr = jnp.where(use_filter, thr, 0.0)
            masked = jnp.where(probs >= thr[:, None], scaled, -jnp.inf)
            sampled = jax.vmap(
                lambda rk, row: jax.random.categorical(rk, row))(
                row_keys, masked).astype(jnp.int32)
            return jnp.where(temps > 0, sampled, greedy)

        toks = jax.lax.cond(jnp.any(temps > 0), sample_branch,
                            lambda _: greedy, logits)
        return key, toks

    def _pack_out(self, toks, logits, counters=None):
        """Program output row per sampled token: [tok, logprob(, top-N ids,
        top-N logprobs)] as ONE f32 array — a single packed fetch keeps the
        host loop at one RTT per iteration (token ids are exact in f32 for
        any vocab < 2^24). Logprobs are of the RAW model distribution
        (temperature-independent), the OpenAI convention. `counters` (a
        decode step's f32 vector, the family's STEP_COUNTERS) rides every
        row's trailing columns: no fetch of its own."""
        lse = jax.nn.logsumexp(logits, axis=-1)
        lp = jnp.take_along_axis(logits, toks[..., None],
                                 axis=-1)[..., 0] - lse
        cols = [toks.astype(jnp.float32)[..., None], lp[..., None]]
        if self.logprobs_topk:
            tv, tid = jax.lax.top_k(logits, self.logprobs_topk)
            cols += [tid.astype(jnp.float32), tv - lse[..., None]]
        if counters is not None:
            cols.append(jnp.broadcast_to(
                counters.astype(jnp.float32), toks.shape + counters.shape))
        return jnp.concatenate(cols, axis=-1)

    @property
    def _out_cols(self) -> int:
        return 2 + 2 * self.logprobs_topk

    def _unpack_out(self, row):
        """Host twin of _pack_out: np row → (tok, lp, top|None) where top
        is a {token_id: logprob} dict of the top-N alternatives."""
        tok, lp = int(row[0]), float(row[1])
        if not self.logprobs_topk:
            return tok, lp, None
        n = self.logprobs_topk
        return tok, lp, {int(t): float(l)
                         for t, l in zip(row[2:2 + n], row[2 + n:2 + 2 * n])}

    def _unpack_wave(self, wave):
        """Row layout: tokens ++ [slot, prompt_len, temp_milli, top_k,
        top_p_micro, presence_milli, freq_milli, seed(, aid)]. Returns
        (tokens, slots, prompt_lens, row_samp [W, 6], aids|None)."""
        ex = self._row_extra
        tokens = wave[:, :-ex]
        slots, prompt_lens = wave[:, -ex], wave[:, -ex + 1]
        row_samp = jnp.stack([
            wave[:, -ex + 2].astype(jnp.float32) / 1000.0,
            wave[:, -ex + 3].astype(jnp.float32),
            wave[:, -ex + 4].astype(jnp.float32) / 1e6,
            wave[:, -ex + 5].astype(jnp.float32) / 1000.0,
            wave[:, -ex + 6].astype(jnp.float32) / 1000.0,
            wave[:, -ex + 7].astype(jnp.float32),
        ], axis=1)
        aids = wave[:, -1] if self.adapters is not None else None
        return tokens, slots, prompt_lens, row_samp, aids

    @_under_engine_mesh
    def _prefill(self, params, cache, lengths, last_tokens, samp, key,
                 wave, lora=None):
        """Batched prefill wave. `wave` is ONE packed int32 array
        [W, bucket+ex] — row i = prompt tokens (right-padded) ++ [slot,
        prompt_len, temp_milli, top_k, top_p_micro] (++ adapter id under
        multi-adapter serving) — because every host->device transfer
        is a round trip of its own: one packed transfer + one dispatch
        covers a whole burst of arrivals. Padded wave rows
        duplicate a real row (same slot, same data) and sampling keys
        derive from the slot id, so duplicate writes are idempotent even
        for sampled requests. Returns packed [W, out_cols] rows
        (_pack_out)."""
        tokens, slots, prompt_lens, row_samp, aids = self._unpack_wave(wave)
        # only each prompt's last row is sampled: project just those
        # (the all-position logits of a wide wave do not fit a chip)
        stacked, ks, vs = self.family.prefill(params, tokens, self.cfg,
                                              lora=lora, ids=aids,
                                              logit_rows=prompt_lens - 1)
        bucket = tokens.shape[1]
        cache = dict(cache)
        for i in range(tokens.shape[0]):   # W is static: unrolled updates
            cache = self._cache_write(cache, slots[i], 0, bucket,
                                      _row(ks, i), _row(vs, i))
            lengths = lengths.at[slots[i]].set(prompt_lens[i])
            samp = samp.at[slots[i]].set(row_samp[i])
            if aids is not None:
                cache["aids"] = cache["aids"].at[slots[i]].set(aids[i])
        # penalties count GENERATED tokens only: the first sampled token
        # sees zero counts, and the slot's counts reset to exactly its
        # one-hot (idempotent under padded duplicate rows, unlike .add)
        cnt = cache["cnt"]
        zero_cnt = jnp.zeros((tokens.shape[0], cnt.shape[1]), cnt.dtype)
        key, toks = self._choose(stacked, row_samp, key, slots, zero_cnt,
                                 prompt_lens)
        for i in range(tokens.shape[0]):
            last_tokens = last_tokens.at[slots[i]].set(toks[i])
            cnt = cnt.at[slots[i]].set(
                jax.nn.one_hot(toks[i], cnt.shape[1], dtype=cnt.dtype))
        cache["cnt"] = self._constrain_cnt(cnt)
        if self.spec:
            # token-history mirror of the KV writes (n-gram drafting reads
            # it); pad garbage past prompt_len is never read — the matcher
            # masks positions > lengths
            hist = cache["hist"]
            for i in range(tokens.shape[0]):
                hist = hist.at[slots[i], :bucket].set(tokens[i])
            cache["hist"] = hist
        return (cache, lengths, last_tokens, samp, key,
                self._pack_out(toks, stacked))

    def _cache_write(self, cache, slot, start: int, count: int, ks, vs):
        """Write one prompt's KV rows (the family's prefill output, its
        batch row taken) into a slot's [start, start+count) range,
        quantizing when the cache is int8. start/count are static."""
        return self.family.cache_write(cache, slot, start, count, ks, vs,
                                       kv_quantize=self.kv_quantize)

    @_under_engine_mesh
    def _prefill_cont(self, params, cache, lengths, last_tokens, samp, key,
                      wave, k_prefix, v_prefix, lora=None):
        """Batched continuation prefill against cached prefixes. `wave` is
        [W, T+ex] — tail tokens (prompt[P:], right-padded to the tail
        bucket) ++ [slot, full_prompt_len, temp_milli, top_k, top_p_micro
        (, aid)] per row; k/v_prefix: [L, W, P, kv, hd] (row i's prefix —
        different requests may hit DIFFERENT store entries of the same P).
        With speculative decoding on, rows are [tail(T) ++ prefix(P) ++
        extras] — the prefix KV alone can't populate the token-history
        buffer the n-gram drafter reads, so the prefix TOKENS ride the
        same packed transfer. Writes prefix+tail KV into each slot and
        samples next tokens from the tails' last rows; padded duplicate
        rows repeat their source row (idempotent writes), exactly like
        _prefill. Returns packed [W, out_cols] rows."""
        tokens_all, slots, prompt_lens, row_samp, aids = \
            self._unpack_wave(wave)
        p = jax.tree.leaves(k_prefix)[0].shape[2]
        t_bucket = tokens_all.shape[1] - (p if self.spec else 0)
        tokens = tokens_all[:, :t_bucket]
        stacked, ks, vs = self.family.prefill_continue(
            params, tokens, k_prefix, v_prefix, self.cfg, lora=lora,
            ids=aids, logit_rows=prompt_lens - p - 1)
        cache = dict(cache)
        for i in range(tokens.shape[0]):   # W is static: unrolled updates
            if self._cont_writes_prefix:
                cache = self._cache_write(cache, slots[i], 0, p,
                                          _row(k_prefix, i),
                                          _row(v_prefix, i))
            cache = self._cache_write(cache, slots[i], p, t_bucket,
                                      _row(ks, i), _row(vs, i))
            lengths = lengths.at[slots[i]].set(prompt_lens[i])
            samp = samp.at[slots[i]].set(row_samp[i])
            if aids is not None:
                cache["aids"] = cache["aids"].at[slots[i]].set(aids[i])
        cnt = cache["cnt"]
        zero_cnt = jnp.zeros((tokens.shape[0], cnt.shape[1]), cnt.dtype)
        key, toks = self._choose(stacked, row_samp, key, slots, zero_cnt,
                                 prompt_lens)
        for i in range(tokens.shape[0]):
            last_tokens = last_tokens.at[slots[i]].set(toks[i])
            cnt = cnt.at[slots[i]].set(
                jax.nn.one_hot(toks[i], cnt.shape[1], dtype=cnt.dtype))
        cache["cnt"] = self._constrain_cnt(cnt)
        if self.spec:
            hist = cache["hist"]
            prefix_toks = tokens_all[:, t_bucket:]
            for i in range(tokens.shape[0]):
                hist = hist.at[slots[i], :p].set(prefix_toks[i])
                hist = hist.at[slots[i], p:p + t_bucket].set(tokens[i])
            cache["hist"] = hist
        return (cache, lengths, last_tokens, samp, key,
                self._pack_out(toks, stacked))

    def _extract_prefix(self, cache, slot, *, p: int):
        """Slice a freshly prefilled slot's first `p` KV rows into a
        store-shaped [L, 1, P, kv, hd] entry (stays on device; entries are
        kept dequantized — the store is tiny next to the cache, and cont
        prefill re-quantizes on write)."""
        return self.family.extract_prefix(self.cfg, cache, slot, p,
                                          kv_quantize=self.kv_quantize,
                                          dtype=self.cfg.dtype)

    #: a slot's first `p` per-token scales, store-shaped [L, 1, p, kv]
    _slot_scales = staticmethod(DEFAULT_FAMILY.slot_scales)

    def _extract_prefix_raw(self, cache, slot, *, p: int):
        """Raw-layout twin of _extract_prefix for the radix block store:
        returns the slot's first `p` KV rows WITHOUT dequantizing —
        (k, v) in cache dtype, or (kq, k_scale, vq, v_scale) when the
        cache is int8 — so stored blocks keep the int8 residency win.
        All arrays are [L, 1, p, ...]; the block insert slices them
        along the token axis (axis 2)."""
        def take(name):
            return jax.lax.dynamic_index_in_dim(
                cache[name], slot, axis=1, keepdims=False)[:, :p][:, None]

        if self.kv_quantize == "int8":
            return (take("k"), self._slot_scales(cache["k_s"], slot, p),
                    take("v"), self._slot_scales(cache["v_s"], slot, p))
        return take("k"), take("v")

    @_under_engine_mesh
    def _decode(self, params, cache, lengths, last_tokens, samp, key,
                active, lora=None, *, steps: int, span: int | None = None):
        """`steps` chained decode iterations inside ONE program (lax.scan):
        a K-token chunk costs one dispatch round-trip instead of K. Slots
        that finish (EOS) mid-chunk keep decoding on device; the host drops
        their surplus tokens, and the slot's next prefill resets its
        state. `span` statically bounds the attention window (length-aware
        decode — see the family's decode_step). Emits packed
        [steps, n_slots, out_cols] rows (_pack_out)."""
        slots = jnp.arange(self.n_slots)

        def body(carry, _):
            cache, lengths, last_tokens, key = carry
            aids = cache.get("aids")
            cnt = cache["cnt"]
            logits, kv = self.family.decode_step(
                params, last_tokens, cache, lengths, self.cfg, span=span,
                lora=lora, ids=aids, active=active)
            counters = (kv.pop("counters") if self.family.STEP_COUNTERS
                        else None)
            if aids is not None:
                kv["aids"] = aids  # decode never re-assigns slots
            # seeded-key position: this step samples generated token
            # #(lengths - prompt_len + 2) at absolute position
            # lengths + 1 (prefill sampled token #1 AT position
            # prompt_len == lengths, so passing bare `lengths` would
            # reuse prefill's key)
            key, toks = self._choose(logits, samp, key, slots, cnt,
                                     lengths + 1)
            # the generated-token counts only feed the penalty logit
            # edits, and every prefill resets its slot's counts — so
            # an all-unpenalized batch skips the [slots, vocab]
            # scatter (read+write of the whole count buffer) entirely
            kv["cnt"] = self._constrain_cnt(jax.lax.cond(
                jnp.any((samp[:, 3] != 0) | (samp[:, 4] != 0)),
                lambda c: c.at[slots, toks].add(active.astype(c.dtype)),
                lambda c: c, cnt))
            cache = kv
            lengths = lengths + active.astype(jnp.int32)
            last_tokens = jnp.where(active, toks, last_tokens)
            return ((cache, lengths, last_tokens, key),
                    self._pack_out(toks, logits, counters))

        (cache, lengths, last_tokens, key), out = jax.lax.scan(
            body, (cache, lengths, last_tokens, key), None, length=steps)
        return cache, lengths, last_tokens, samp, key, out

    @_under_engine_mesh
    def _spec_decode(self, params, cache, lengths, last_tokens, samp, key,
                     active, lora=None, *, steps: int, span: int,
                     k_spec: int | None = None):
        """`steps` speculative verify rounds inside ONE program: each round
        records the pending token into the history buffer, drafts up to
        `k_spec` tokens by n-gram lookup (_ngram_draft), verifies all
        drafts in one verify_step forward of the family, and accepts the
        longest argmax-matching prefix plus the model's own bonus token —
        1..k+1 tokens per round per slot, at ~one decode-step's HBM cost. Greedy
        slots get EXACT greedy output (verification IS the greedy model);
        sampled slots (temp>0) draft nothing and sample the bonus (through
        the same top-k/top-p filters as plain decode), i.e. degrade to
        plain decode. Emits [steps, B, 1 + (k+1)*out_cols] f32 rows:
        count ++ flattened _pack_out rows per emit position.

        `k_spec` defaults to the engine's configured maximum; the
        adaptive-k policy dispatches smaller-k members of the menu when
        measured acceptance doesn't cover the configured draft count (any
        k is exact — fewer drafts only shortcut fewer dispatches)."""
        k_spec = self.spec if k_spec is None else k_spec
        rows = jnp.arange(self.n_slots)
        max_len = self.max_len
        temps = samp[:, 0]
        pens = (samp[:, 3] != 0) | (samp[:, 4] != 0)

        def body(carry, _):
            cache, lengths, last_tokens, key = carry
            hist = cache["hist"]
            # record the pending token at its cache position (inactive
            # slots' writes are dropped — their hist is dead state anyway,
            # but a clamped write at max_len-1 could land on a live row)
            hist = hist.at[rows, jnp.where(active, lengths, max_len)].set(
                last_tokens, mode="drop")
            drafts, count = _ngram_draft(hist, lengths, k_spec,
                                         self.spec_ngram)
            # sampled rows AND penalized rows draft nothing: penalties
            # evolve per emitted token, so parallel verification against
            # raw argmax would diverge from the sequential penalized
            # greedy — those rows degrade to plain (1-token) decode,
            # exactly like sampling does
            count = jnp.where(active & (temps <= 0) & ~pens, count, 0)
            tokens_in = jnp.concatenate([last_tokens[:, None], drafts],
                                        axis=1)
            aids = cache.get("aids")
            kv = {k: v for k, v in cache.items() if k != "hist"}
            logits, kv = self.family.verify_step(
                params, tokens_in, kv, lengths, self.cfg, span=span,
                lora=lora, ids=aids, active=active)
            preds = jnp.argmax(logits, -1).astype(jnp.int32)  # [B, k+1]
            match = ((preds[:, :k_spec] == drafts)
                     & (jnp.arange(k_spec)[None] < count[:, None]))
            # length of the leading all-True run = accepted drafts
            n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                            axis=1)
            bonus_greedy = jnp.take_along_axis(preds, n_acc[:, None],
                                               axis=1)[:, 0]
            cnt = cache["cnt"]
            # sampled rows accept no drafts, so the bonus is generated
            # token #(lengths+1 - prompt_len + 1) at absolute position
            # lengths + 1 — the same offset plain decode uses (bare
            # `lengths` would collide with the prefill-sampled key)
            key, bonus_chosen = self._choose(logits[:, 0], samp, key, rows,
                                             cnt, lengths + 1)
            # _choose returns penalized argmax for (temp=0, penalties-on)
            # rows and a filtered sample for temp>0 rows; pure-greedy rows
            # keep the verify forward's own prediction
            bonus = jnp.where((temps > 0) | pens, bonus_chosen,
                              bonus_greedy)
            jj = jnp.arange(k_spec + 1)[None]
            drafts_pad = jnp.concatenate(
                [drafts, jnp.zeros((self.n_slots, 1), jnp.int32)], axis=1)
            emit = jnp.where(jj < n_acc[:, None], drafts_pad,
                             jnp.where(jj == n_acc[:, None],
                                       bonus[:, None], 0))
            emit_count = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
            # emitted tokens enter the penalty counts (scatter-add; masked
            # positions add 0 at token id 0, duplicates accumulate) — but
            # only when some row actually carries a penalty: the counts
            # feed nothing else, and prefill resets them per slot, so the
            # all-unpenalized batch skips the [slots, vocab] scatter
            emit_mask = (jj < emit_count[:, None]).astype(cnt.dtype)
            kv["cnt"] = self._constrain_cnt(jax.lax.cond(
                jnp.any(pens),
                lambda c: c.at[rows[:, None], emit].add(emit_mask),
                lambda c: c, cnt))
            # accepted drafts enter the history now; the bonus token lands
            # next round as the pending last_token
            wpos = lengths[:, None] + 1 + jnp.arange(k_spec)[None]
            wmask = (jnp.arange(k_spec)[None] < n_acc[:, None]) \
                & active[:, None]
            hist = hist.at[rows[:, None],
                           jnp.where(wmask, wpos, max_len)].set(
                drafts, mode="drop")
            kv["hist"] = hist
            if aids is not None:
                kv["aids"] = aids
            new_len = lengths + emit_count
            new_last = jnp.where(active, bonus, last_tokens)
            # emitted token j's distribution is logits[:, j] (the verify
            # forward consumed tokens_in[:j+1] to produce it), so one
            # _pack_out over [B, k+1] yields every emit's logprob row
            out_rows = self._pack_out(emit, logits)  # [B, k+1, out_cols]
            packed = jnp.concatenate(
                [emit_count[:, None].astype(jnp.float32),
                 out_rows.reshape(self.n_slots, -1)], axis=1)
            return (kv, new_len, new_last, key), packed

        (cache, lengths, last_tokens, key), out = jax.lax.scan(
            body, (cache, lengths, last_tokens, key), None, length=steps)
        return cache, lengths, last_tokens, samp, key, out

    def _spec_fn(self, steps: int, span: int | None = None,
                 k: int | None = None):
        """Compiled speculative program per (rounds, attention span, draft
        length) — the spec-mode twin of _decode_fn's menu. k defaults to
        the engine's configured maximum (the static-k program)."""
        span = self.max_len if span is None else span
        k = self.spec if k is None else k
        if (steps, span, k) not in self._spec_fns:
            self._spec_fns[steps, span, k] = jax.jit(
                named_program("decode_spec", self._spec_decode,
                              steps=steps, span=span, k_spec=k),
                donate_argnums=(1, 2, 3, 4, 5))
        return self._spec_fns[steps, span, k]

    def _prefill_fn(self, bucket: int, width: int):
        """One compiled program per (bucket, wave-width) pair; widths are
        powers of two so a burst of any size maps onto a tiny program menu."""
        if (bucket, width) not in self._prefill_fns:
            self._prefill_fns[bucket, width] = jax.jit(
                named_program("prefill", self._prefill),
                donate_argnums=(1, 2, 3, 4, 5))
        return self._prefill_fns[bucket, width]

    def _cont_fn(self, p: int, t: int, width: int):
        """One continuation program per (prefix bucket, tail bucket, wave
        width); the prefix KV args are NOT donated — store entries are
        reused (the stacked per-wave copy IS donatable, but stays alive
        only within the dispatch)."""
        if (p, t, width) not in self._cont_fns:
            self._cont_fns[p, t, width] = jax.jit(
                named_program("prefill_cont", self._prefill_cont),
                donate_argnums=(1, 2, 3, 4, 5))
        return self._cont_fns[p, t, width]

    def _extract_fn(self, p: int):
        if p not in self._extract_fns:
            self._extract_fns[p] = jax.jit(
                named_program("extract_prefix", self._extract_prefix, p=p))
        return self._extract_fns[p]

    def _extract_raw_fn(self, p: int):
        if p not in self._extract_raw_fns:
            self._extract_raw_fns[p] = jax.jit(
                named_program("extract_prefix_raw",
                              self._extract_prefix_raw, p=p))
        return self._extract_raw_fns[p]

    def _tail_bucket(self, tail_len: int) -> int | None:
        cands = [b for b in self.buckets if b >= tail_len]
        return min(cands) if cands else None

    def _prefix_lookup(self, action):
        """(match, p, t) when the prompt's longest cached block chain
        yields a legal continuation dispatch (>= 1 tail token must
        remain to produce next-token logits, and the tail must fit a
        bucket inside max_len — shrinking the reused prefix block by
        block when the full match would overflow the cache). None on a
        miss. The returned match is PINNED: eviction cannot reclaim its
        blocks until the caller releases it after the dispatch."""
        prompt = self._prompts[action.req_id]
        bt = self.prefix_block_tokens
        if len(prompt) - 1 < bt:
            return None   # too short to carry even one block: not an
            # eligible admission, so neither a hit nor a miss
        tenant = self._req_tenant.get(action.req_id)
        m = self.kvcache.match(prompt, max_tokens=len(prompt) - 1,
                               namespace=self._req_aids.get(
                                   action.req_id, 0))
        p = m.tokens
        t = None
        while p > 0:
            t = self._tail_bucket(len(prompt) - p)
            if t is None:   # tail over the largest bucket: shrinking p
                p = 0       # only grows it — the chunked path owns this
                break
            if p + t <= self.max_len:
                break
            p -= bt
        if p <= 0:
            self.kvcache.release(m)
            self.kvcache.record_miss(tenant)
            self._prefix_misses += 1
            return None
        return m, p, t

    @staticmethod
    def _materialize_payloads(payloads: list, kv_quantize, dtype):
        """Block-payload chain → (k, v) prefix arrays [L, 1, P, kv, hd]
        in model dtype: concatenate along the token axis, dequantizing
        int8 blocks at the last moment (the store keeps them int8 — half
        the residency). Device-to-device only; nothing crosses the host.
        Static so the stage-sharded engine can run it per layer slab."""
        if kv_quantize == "int8":
            kq = jnp.concatenate([b[0] for b in payloads], axis=2)
            ks = jnp.concatenate([b[1] for b in payloads], axis=2)
            vq = jnp.concatenate([b[2] for b in payloads], axis=2)
            vs = jnp.concatenate([b[3] for b in payloads], axis=2)
            return (DEFAULT_FAMILY.dequantize_kv(kq, ks, dtype),
                    DEFAULT_FAMILY.dequantize_kv(vq, vs, dtype))
        if len(payloads) == 1:
            return payloads[0]
        return (jnp.concatenate([b[0] for b in payloads], axis=2),
                jnp.concatenate([b[1] for b in payloads], axis=2))

    def _materialize_prefix(self, payloads: list):
        """Matched block chain → the continuation program's (k, v)
        prefix arrays (see _materialize_payloads)."""
        return self._materialize_payloads(payloads, self.kv_quantize,
                                          self.cfg.dtype)

    def _stack_prefix(self, entries: list):
        """Stack per-request materialized prefixes into the continuation
        wave's (k_prefix, v_prefix) program inputs along the batch axis.
        entries: list of `_materialize_prefix` results, one per wave row.
        The stage-sharded engine overrides this to stack per layer slab."""
        return tuple(jax.tree.map(
            lambda *rows: jnp.concatenate(rows, axis=1),
            *[e[n] for e in entries]) for n in (0, 1))

    @staticmethod
    def _payload_slice(parts, s: int, e: int):
        """One radix block's payload from the raw-extract arrays: the
        [s, e) token-axis slice of every part. The stage-sharded engine
        overrides this to slice each stage's parts (the block payload is
        then the per-stage tuple — the stage-keyed store's currency)."""
        return tuple(a[:, :, s:e] for a in parts)

    def _decode_fn(self, steps: int, span: int | None = None):
        """One compiled program per (chunk length, attention span) pair —
        chunk lengths are powers of two up to decode_chunk, spans powers of
        two from 128 to max_len (chosen by _do_decode from the live
        lengths). Cold pairs compile lazily on first use."""
        span = self.max_len if span is None else span
        if (steps, span) not in self._decode_fns:
            self._decode_fns[steps, span] = jax.jit(
                named_program("decode", self._decode, steps=steps, span=span),
                donate_argnums=(1, 2, 3, 4, 5))
        return self._decode_fns[steps, span]

    def _span_menu(self) -> list[int]:
        """Attention-span buckets: powers of two from 128 up to (and always
        including) max_len."""
        spans = []
        s = 128
        while s < self.max_len:
            spans.append(s)
            s *= 2
        spans.append(self.max_len)
        return spans

    def _kv_block_tokens(self) -> int:
        """Tokens to a KV block of the decode-attention kernel's grid."""
        from kubeflow_tpu.ops.flash_decode import DEFAULT_BLOCK_KV

        return min(DEFAULT_BLOCK_KV, self.max_len)

    def _pick_span(self, needed: int) -> int:
        for s in self._span_menu():
            if s >= needed:
                return s
        return self.max_len

    # -- public API ----------------------------------------------------------

    def _chunk_plan(self, n: int) -> list[tuple[int, int]]:
        """Chunked-prefill schedule for an n-token prompt longer than the
        largest bucket: [(chunk_len, program_len), ...] — full largest-
        bucket chunks, then a tail rounded up to a bucket. Raises
        PromptTooLong when no tail bucket fits inside max_len."""
        big = self.buckets[-1]
        if n >= self.max_len:
            raise PromptTooLong(
                f"prompt_len {n} leaves no room to decode in max_len "
                f"{self.max_len}")
        plan = []
        done = 0
        while n - done > big:
            plan.append((big, big))
            done += big
        tail = n - done
        t = self._tail_bucket(tail)
        if t is None or done + t > self.max_len:
            raise PromptTooLong(
                f"prompt_len {n}: tail {tail} after {done} chunked tokens "
                f"fits no bucket within max_len {self.max_len}")
        plan.append((tail, t))
        return plan

    def _validate_submit(self, prompt, temperature, adapter, top_k, top_p,
                         presence_penalty, frequency_penalty, seed, stop,
                         deadline_s, tenant):
        """Every submit()-time argument check, factored out so the
        disaggregated coordinator (serving/disagg.py) can reject a bad
        request EAGERLY — on the caller's thread, before the job enters
        the prefill queue — instead of poisoning the engine-loop thread
        at dispatch time. Raises exactly what submit() would; returns the
        normalized (temperature, top_k, top_p, presence, frequency,
        folded_seed, stop_seqs, adapter_id) tuple submit() enqueues."""
        import math

        # a NaN/inf/huge value would blow up later INSIDE the engine loop
        # thread (wave packing), killing serving for every request
        if not (math.isfinite(temperature) and 0 <= temperature <= 100):
            raise ValueError("temperature must be finite and in [0, 100]")
        top_k = int(top_k)
        if not 0 <= top_k <= self.sample_k_max:
            raise ValueError(
                f"top_k must be 0..{self.sample_k_max} (the engine's "
                "static sample_k_max candidate window)")
        top_p = float(top_p)
        if not (math.isfinite(top_p) and 0 < top_p <= 1):
            raise ValueError("top_p must be in (0, 1]")
        presence_penalty = float(presence_penalty)
        frequency_penalty = float(frequency_penalty)
        for name, v in (("presence_penalty", presence_penalty),
                        ("frequency_penalty", frequency_penalty)):
            if not (math.isfinite(v) and -2 <= v <= 2):
                raise ValueError(f"{name} must be finite and in [-2, 2]")
        if seed is not None:
            if not isinstance(seed, int) or isinstance(seed, bool) \
                    or seed < 0:
                raise ValueError("seed must be a non-negative int")
            seed = _fold_seed24(seed)   # f32-exact; deterministic mixing
        stop_seqs: list[list[int]] = []
        for ss in (stop or ()):
            seq = [int(t) for t in ss]
            if not seq or len(seq) > 64:
                raise ValueError("each stop sequence must be 1..64 tokens")
            stop_seqs.append(seq)
        if len(stop_seqs) > 8:
            raise ValueError("at most 8 stop sequences per request")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        aid = 0
        if adapter is not None:
            if adapter not in self._adapter_idx:
                raise ValueError(
                    f"unknown adapter {adapter!r}; "
                    f"loaded: {sorted(self._adapter_idx)}")
            aid = self._adapter_idx[adapter]
        if tenant is not None and (not isinstance(tenant, str)
                                   or not 1 <= len(tenant) <= 256):
            # the length cap pairs with MAX_TENANTS: names persist in
            # _tenant_idx for the engine's lifetime, so both the count
            # AND the bytes must be bounded against adversarial clients
            raise ValueError("tenant must be a string of 1..256 chars")
        if len(prompt) > self.buckets[-1]:
            # chunked prefill: validate the chain now (fail at submit, not
            # mid-serve); the scheduler sees the largest bucket — it only
            # uses the length for bucket choice, the engine keeps the truth
            self._chunk_plan(len(prompt))
        return (temperature, top_k, top_p, presence_penalty,
                frequency_penalty, seed, stop_seqs, aid)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0,
               adapter: str | None = None,
               top_k: int = 0, top_p: float = 1.0,
               presence_penalty: float = 0.0,
               frequency_penalty: float = 0.0,
               seed: int | None = None,
               stop: Sequence[Sequence[int]] | None = None,
               deadline_s: float | None = None,
               tenant: str | None = None,
               trace: str | None = None) -> int:
        """Queue one request. top_k (0 = off) / top_p (1.0 = off) filter
        the sampled distribution inside the compiled programs (only when
        temperature > 0 — greedy rows stay bit-exact argmax).
        presence/frequency penalties (OpenAI [-2, 2]; 0 = off) are logit
        edits over the request's GENERATED tokens (the vLLM convention),
        applied inside the compiled programs before temperature/filters —
        they affect greedy requests too (penalized argmax). Nonzero
        penalties are quantized to milli units with a floor of ±1 milli
        (like the top_p micro guard): |v| < 0.0005 stays a minimal
        penalty instead of silently turning off. `seed` makes
        temp>0 sampling reproducible: the row's PRNG keys derive from
        (seed, position) alone, independent of slot, batch composition,
        decode chunking, or engine restarts. Seeds ride the f32 sampling
        row, so they are folded onto 24 bits via a splitmix64 mixing
        hash (_fold_seed24): distinct seeds can collide (~2^-24 per
        pair — unavoidable at this width), but unlike a plain modulus
        the colliding pairs are not predictable from the seed values,
        and the fold is deterministic so a given seed replays the same
        stream forever. `stop`: token-id sequences;
        generation ends (finish_reason "stop") when the output ends with
        one, and the matched sequence is excluded from the result (OpenAI
        semantics; matching is host-side at chunk boundaries, so at most
        one decode chunk of surplus is computed). `deadline_s`:
        wall-clock budget; past it the request is cancelled at the next
        chunk boundary (finish_reason "cancelled"). `tenant`: optional
        tenant name — requests of the same tenant share a scheduler queue
        and the max-min fair pop / admission caps (set_tenant_limits)
        apply per tenant; None rides the anonymous tenant-0 queue."""
        try:
            (temperature, top_k, top_p, presence_penalty,
             frequency_penalty, seed, stop_seqs, aid) = \
                self._validate_submit(prompt, temperature, adapter, top_k,
                                      top_p, presence_penalty,
                                      frequency_penalty, seed, stop,
                                      deadline_s, tenant)
        except PromptTooLong:
            if len(prompt) > self.buckets[-1]:
                # bump the scheduler's rejected counter (the operator
                # metric) but surface the chunk-aware message, not the
                # scheduler's generic "exceeds buckets"
                with self._submit_lock:
                    try:
                        self.scheduler.submit(len(prompt), max_new_tokens,
                                              time.monotonic(),
                                              tenant=self._tenant_id(tenant))
                    except PromptTooLong:
                        pass
            raise
        sched_len = min(len(prompt), self.buckets[-1])
        with self._submit_lock:
            req_id = self.scheduler.submit(sched_len, max_new_tokens,
                                           time.monotonic(),
                                           tenant=self._tenant_id(tenant))
            self._prompts[req_id] = list(prompt)
            if tenant is not None:
                self._req_tenant[req_id] = tenant
            self._results[req_id] = []
            self._logprobs[req_id] = []
            if self.logprobs_topk:
                self._toplogprobs[req_id] = []
            self._max_new[req_id] = max_new_tokens
            self._req_samp[req_id] = (
                float(temperature), top_k, top_p, presence_penalty,
                frequency_penalty, -1 if seed is None else seed)
            if stop_seqs:
                self._req_stop[req_id] = stop_seqs
            if deadline_s is not None:
                self._deadlines[req_id] = time.monotonic() + deadline_s
            if aid:
                self._req_aids[req_id] = aid
            self._req_plen[req_id] = len(prompt)
            self._submit_t[req_id] = time.monotonic()
            if trace is not None:
                self._req_trace[req_id] = trace
        obs_metrics.REQUESTS.inc(component=self.role, event="submitted")
        return req_id

    #: bound on distinct tenant names one engine tracks: the OpenAI
    #: `user` field is client-controlled, so an unbounded name->id map
    #: would be a memory leak an adversarial client can drive. Past the
    #: cap, new names share the anonymous tenant-0 queue — degraded
    #: fairness for the overflow tail, never unbounded growth.
    MAX_TENANTS = 65536

    def _tenant_id(self, tenant: str | None) -> int:
        """Tenant name -> stable scheduler id. MUST be called under
        _submit_lock: the len()-based id assignment has to be atomic
        with the insert, or two first-requests from distinct tenants
        could mint the same id and permanently merge their fairness
        queues and admission quotas."""
        if tenant is None:
            return 0
        tid = self._tenant_idx.get(tenant)
        if tid is not None:
            return tid
        if len(self._tenant_idx) >= self.MAX_TENANTS:
            return 0
        tid = len(self._tenant_idx) + 1
        self._tenant_idx[tenant] = tid
        return tid

    def cancel(self, req_id: int) -> bool:
        """Ask the engine to drop a request; takes effect at the NEXT
        chunk boundary (the engine thread applies pending cancellations at
        the top of step(), so the freed slot is refillable by the very
        next prefill wave). Thread-safe; callable from server/SSE threads.
        Returns True if the request was still in flight."""
        with self._submit_lock:
            if req_id in self._done or req_id not in self._results:
                return False
            self._cancel_pending.append(req_id)
            return True

    def _apply_cancellations(self) -> None:
        """Engine-thread only (top of step()): drain queued cancellations
        and expired deadlines, free their scheduler state, and mark them
        finished with reason "cancelled"."""
        now = time.monotonic()
        with self._submit_lock:
            pending = self._cancel_pending
            self._cancel_pending = []
            pending += [r for r, dl in self._deadlines.items()
                        if now >= dl and r not in self._done]
            for rid in dict.fromkeys(pending):   # dedup, keep order
                if rid in self._done or rid not in self._results:
                    continue
                self.scheduler.cancel(rid)
                self._finish_reasons[rid] = "cancelled"
                self._finish_t[rid] = now
                self._close_phases(rid)
                self._done.add(rid)
                self._cancelled_count += 1
                self._prompts.pop(rid, None)
                self._max_new.pop(rid, None)
                self._req_samp.pop(rid, None)
                self._req_stop.pop(rid, None)
                self._req_aids.pop(rid, None)
                self._deadlines.pop(rid, None)
                self._obs_finish(rid)

    def step(self) -> bool:
        """One engine iteration: a prefill wave or a batched decode.
        False = idle.

        All queued prefills drain into per-bucket BATCHED programs (one
        dispatch per bucket group) and every wave dispatches before any
        token fetch, so a burst of n arrivals pays ~one program dispatch +
        one RTT instead of n of each. Exception: prompts longer than the
        largest bucket run as per-request chained dispatches (2 per chunk
        boundary) — long-prompt TTFT scales with the chain length.

        Chunk boundary = here: pending cancellations and expired deadlines
        are applied first, so a freed slot is refillable by this very
        step's prefill wave.

        The phase clock runs from here to the return: every helper below
        enters the phase it is (obs.trace.PHASES)."""
        clock = self.phase_clock
        clock.enter("sched")
        try:
            return self._step()
        finally:
            clock.leave()

    def _step(self) -> bool:
        self._apply_cancellations()
        with self._submit_lock:
            action = self.scheduler.next()
        if action is None:
            if self._pending is not None:
                self._drain_pending()   # the final chunk's tokens
                return True
            return False
        if isinstance(action, DecodeAction):
            self._do_decode()
            return True
        # prefill path: the in-flight chunk must land FIRST — its replay
        # frees slots/completes requests, and the device-side prefill that
        # follows overwrites any junk the chunk wrote into reused slots
        self._drain_pending()
        self.phase_clock.enter("sched")
        actions = [action]
        while len(actions) < self.prefill_wave_max:
            with self._submit_lock:
                nxt = self.scheduler.next()
            if not isinstance(nxt, PrefillAction):
                break   # Decode/None: dropping is safe — the decode pass
                        # re-derives from slot state on the next step()
            actions.append(nxt)
        t_prefill = time.monotonic()
        for a in actions:
            # phase epoch: the request's prefill left the queue now (a
            # chunked chain keeps its FIRST pop — the whole chain is one
            # prefill phase)
            self._prefill_start_t.setdefault(a.req_id, t_prefill)
        actions = self._admit_prefills(actions)
        if actions:
            self._run_prefill_actions(actions)
        return True

    def _admit_prefills(self, actions: list) -> list:
        """Admission hook between the scheduler pop and the wave
        dispatch. The slab engine admits everything — its KV rows are
        preallocated per slot, so a popped action is always fundable.
        The paged engine (serving/paged.py) overrides this to reserve
        KV blocks against the free-block watermark, run the radix
        eviction valve under pressure, and HOLD BACK actions it cannot
        fund yet (their slots stay assigned; the held prefill
        dispatches on a later step once blocks free up)."""
        return actions

    def _run_prefill_actions(self, actions: list) -> None:
        """Dispatch one admitted prefill burst and replay its tokens.
        Factored out of step() so the paged engine's held-action retry
        can dispatch without re-entering the scheduler pop."""
        # prompts longer than the largest bucket peel off into chained
        # chunked prefills; prefix-cache hits into continuation programs
        # (tail-only compute); everything else groups by bucket, one
        # batched program per group. All dispatches go out before any
        # token fetch.
        clock = self.phase_clock
        clock.enter("prefill_pack")
        chunked: list[PrefillAction] = []
        short: list[PrefillAction] = []
        for a in actions:  # one-pass, identity-safe partition
            (chunked if len(self._prompts.get(a.req_id, ())) > a.bucket_len
             else short).append(a)
        cont: list[tuple] = []   # (action, match, p, t)
        normal: list[PrefillAction] = []
        if self.prefix_cache_enabled:
            for a in short:
                hit = self._prefix_lookup(a)
                (cont.append((a,) + hit) if hit is not None
                 else normal.append(a))
        else:
            normal = short
        groups: dict[int, list[PrefillAction]] = {}
        for a in normal:
            groups.setdefault(a.bucket_len, []).append(a)
        bt = self.prefix_block_tokens
        cont_groups: dict[tuple[int, int], list] = {}
        for a, m, p, t in cont:
            # materialize the pinned chain into the program's prefix
            # arrays (truncated to p when the legality clamp shortened
            # the match); the pin holds until after the dispatch below
            cont_groups.setdefault((p, t), []).append(
                (a, self._materialize_prefix(m.payloads[:p // bt])))
        dispatched = [(wave, self._dispatch_prefill_wave(bucket, wave))
                      for bucket, wave in groups.items()]
        dispatched += [([a for a, _ in pairs],
                        self._dispatch_prefill_cont_wave(p, t, pairs))
                       for (p, t), pairs in cont_groups.items()]
        dispatched += [([a], self._dispatch_chunked_prefill(a))
                       for a in chunked]
        # hit bookkeeping + unpin AFTER every dispatch went out: the
        # committed accounting records only reuse that actually rode a
        # continuation program
        if self.prefix_cache_enabled:
            clock.enter("prefix_bank")
        for a, m, p, t in cont:
            self._prefix_hits += 1
            self._cached_prefix[a.req_id] = p
            self.kvcache.record_hit(self._req_tenant.get(a.req_id), p)
            self._prefill_reused_tokens += p
            self._prefill_computed_tokens += \
                len(self._prompts[a.req_id]) - p
            self.kvcache.release(m)
        if self.prefix_cache_enabled:
            # bank fresh prefix blocks BEFORE the fetch loop: recording a
            # request's final token pops its prompt, and extraction only
            # needs the (device-ordered) prefill to have been dispatched.
            # Continuation hits bank too — a multi-turn session's new
            # suffix blocks extend the cached chain (dedup skips the
            # already-cached prefix).
            for wave, _ in dispatched:
                for a in wave:
                    self._bank_prefix_blocks(a)
        for n, (wave, out) in enumerate(dispatched, 1):
            clock.enter("prefill_fetch")
            out_np = np.asarray(out)   # one fetch per wave [W, out_cols]
            clock.fetched(outstanding=n < len(dispatched))
            clock.enter("replay")
            for i, a in enumerate(wave):
                # true length, not action.prompt_len: a chunked request's
                # scheduler-visible length was clamped to the largest bucket
                self._host_lengths[a.slot] = len(self._prompts[a.req_id])
                if self._spec_adapt is not None:
                    # new occupant: optimistic draft length until its own
                    # rounds re-anchor the slot's acceptance EMA
                    self._spec_adapt.reset_slot(a.slot)
                tok, lp, top = self._unpack_out(out_np[i])
                self._record_token(a.req_id, a.slot, tok, lp, top,
                                   first_token=True)

    def _chunk_plan_from(self, n: int, start: int
                         ) -> list[tuple[int, int]] | None:
        """Chunk schedule for the UNCOVERED tokens [start, n) of a long
        prompt: [(chunk_len, program_len), ...] — full largest-bucket
        chunks, then a tail rounded up to a bucket. None when some
        boundary's continuation (p = tokens done so far) cannot fit
        inside max_len."""
        big = self.buckets[-1]
        plan = []
        done = start
        while n - done > big:
            if done + big > self.max_len:
                return None
            plan.append((big, big))
            done += big
        t = self._tail_bucket(n - done)
        if t is None or done + t > self.max_len:
            return None
        plan.append((n - done, t))
        return plan

    def _dispatch_chunked_prefill(self, action) -> Any:
        """Chained prefill for a prompt longer than the largest bucket:
        the first chunk runs the ordinary bucket prefill, then each
        further chunk extracts the accumulated slot KV and runs a
        continuation program against it (the prefix-reuse machinery,
        aimed at the slot's own rows). Radix composition: when the
        prompt's leading blocks are cached (the shared-system-prompt
        case) the chain STARTS at the longest reusable prefix instead of
        token 0 — possibly replacing the full first prefill and several
        chain links at once. One request = len(plan)+1 dispatches; the
        chain's programs compile lazily on the first long prompt — a
        cold start the docstring of warmup() points at. Returns the
        next-token device array [1]."""
        self.phase_clock.enter("prefill_pack")
        prompt = self._prompts[action.req_id]
        n = len(prompt)
        slot = action.slot
        tail = self._row_tail(action.req_id)
        big = self.buckets[-1]
        bt = self.prefix_block_tokens
        tenant = self._req_tenant.get(action.req_id)
        done = 0
        pending = None
        if self.prefix_cache_enabled and n - 1 >= bt:
            m = self.kvcache.match(
                prompt, max_tokens=n - 1,
                namespace=self._req_aids.get(action.req_id, 0))
            done = m.tokens
            # shrink the reused prefix until the remaining chain is
            # schedulable (every boundary fits inside max_len)
            while done > 0 and self._chunk_plan_from(n, done) is None:
                done -= bt
            if done > 0:
                pending = self._materialize_prefix(
                    m.payloads[:done // bt])
                self._prefix_hits += 1
                self._cached_prefix[action.req_id] = done
                self.kvcache.record_hit(tenant, done)
                self._prefill_reused_tokens += done
            else:
                self.kvcache.record_miss(tenant)
                self._prefix_misses += 1
            self.kvcache.release(m)
        self._prefill_computed_tokens += n - done
        clock = self.phase_clock
        if done == 0:
            packed = self._put(self._pack_rows(
                1, big, [(prompt[:big], slot, big) + tail]))
            fn = self._prefill_fn(big, 1)
            clock.enter("prefill_dispatch")
            (self.cache, self.lengths, self.last_tokens, self.samp,
             self.rng_key, out) = fn(
                self.params, self.cache, self.lengths, self.last_tokens,
                self.samp, self.rng_key, packed, *self._extra())
            done = big
        plan = self._chunk_plan_from(n, done) or []
        for chunk_len, t in plan:
            clock.enter("prefill_pack")
            # the chain boundary is a continuation with the request's OWN
            # prefix (p == done), so the row layout comes from the same
            # helper the cont waves use
            row_toks = self._cont_row_tokens(
                list(prompt[:done + chunk_len]), done, t)
            packed = self._put(self._pack_rows(
                1, t + (done if self.spec else 0),
                [(row_toks, slot, done + chunk_len) + tail]))
            fn = self._cont_fn(done, t, 1)
            clock.enter("prefill_dispatch")
            ek, ev = (pending if pending is not None
                      else self._extract_fn(done)(self.cache, slot))
            pending = None
            (self.cache, self.lengths, self.last_tokens, self.samp,
             self.rng_key, out) = fn(
                self.params, self.cache, self.lengths, self.last_tokens,
                self.samp, self.rng_key, packed, ek, ev, *self._extra())
            done += chunk_len
        return out

    def run_until_idle(self) -> None:
        while self.step():
            pass

    def warmup(self) -> None:
        """_warm_menu under the census of the quantized matmul sites it
        traces (ops/quant.py count_sites): metrics() reports, as
        "quant_matmul_sites", how many sites of the warmed programs run
        the stacked int8 kernel, the 2-D one, or the XLA expression."""
        with quant.count_sites() as sites:
            self._warm_menu()
        self._quant_matmul_sites = dict(sites)

    def _warm_menu(self) -> None:
        """Execute every program in the menu once (each bucket × each
        power-of-two wave width, plus decode) so no request ever pays XLA
        compile time. Must run before serving traffic: a cold width means
        a whole burst waits ~seconds on the compiler. Slot state is junk
        during warmup and reset after; call only while idle.

        NOT pre-warmed unless `warm_chain`: the chunked-prefill chain
        programs (extract + continuation per chunk boundary) — the first
        prompt longer than the largest bucket pays their compile, later
        ones are warm."""
        ex = self._row_extra
        for bucket in self.buckets:
            width = 1
            while True:   # every power of two through next-pow2(n_slots):
                # a wave of n_slots actions pads UP to that width, so for
                # e.g. n_slots=6 width 8 must be warm too
                packed = np.zeros((width, bucket + ex), np.int32)
                packed[:, :2] = 1   # token + prompt_len floor
                packed[:, -ex] = np.arange(width) % self.n_slots
                packed[:, -ex + 1] = 1
                packed[:, -ex + 7] = -1   # unseeded sentinel
                (self.cache, self.lengths, self.last_tokens, self.samp,
                 self.rng_key, _) = self._prefill_fn(bucket, width)(
                    self.params, self.cache, self.lengths,
                    self.last_tokens, self.samp, self.rng_key,
                    self._put(packed), *self._extra())
                if width >= self.prefill_wave_max:
                    break
                width *= 2
        if self.prefix_cache_enabled:
            # continuation menu: (block-multiple prefix, tail bucket,
            # width) combos, plus the per-prefix extract programs. Radix
            # hits reuse ANY block multiple up to the largest bucket
            # (longer reused prefixes belong to the chunked chain and
            # compile lazily like the rest of it), the first
            # WARM_CONT_PAIRS pairs of it.
            bt = self.prefix_block_tokens
            pairs = [(p, t) for p in range(bt, self.buckets[-1] + 1, bt)
                     for t in self.buckets
                     if p + t <= self.max_len][:WARM_CONT_PAIRS]
            # the banking path's raw-extract programs are cheap slice
            # jits, but a cold one still stalls the engine thread
            # mid-replay — warm every block multiple the banker can ask
            # for (aligned prompt prefixes up to max_len). The paged
            # engine banks block ids (no extraction) and skips this.
            if self._bank_uses_raw_extract:
                for p in range(bt, self.max_len, bt):
                    self._extract_raw_fn(p)(self.cache, 0)
            extracts = {}
            for p, t in pairs:
                if p not in extracts:
                    extracts[p] = self._extract_fn(p)(self.cache, 0)
                ek, ev = extracts[p]
                width = 1
                while True:
                    cols = t + (p if self.spec else 0) + ex
                    packed = np.zeros((width, cols), np.int32)
                    packed[:, 0] = 1
                    packed[:, -ex] = np.arange(width) % self.n_slots
                    packed[:, -ex + 1] = p + 1  # last-row index stays valid
                    packed[:, -ex + 7] = -1   # unseeded sentinel
                    kw, vw = self._stack_prefix([(ek, ev)] * width)
                    (self.cache, self.lengths, self.last_tokens,
                     self.samp, self.rng_key, _) = \
                        self._cont_fn(p, t, width)(
                            self.params, self.cache, self.lengths,
                            self.last_tokens, self.samp, self.rng_key,
                            self._put(packed), kw, vw, *self._extra())
                    if width >= self.prefill_wave_max:
                        break
                    width *= 2
        if self.warm_chain:
            # the chain of a long prompt: full largest-bucket chunks, then
            # a tail in any bucket; one request at a time (width 1)
            big = self.buckets[-1]
            for p in range(big, self.max_len, big):
                ek, ev = self._extract_fn(p)(self.cache, 0)
                for t in self.buckets:
                    if p + t > self.max_len:
                        continue
                    packed = np.zeros((1, t + (p if self.spec else 0) + ex),
                                      np.int32)
                    packed[:, 0] = 1
                    packed[:, -ex + 1] = p + 1
                    packed[:, -ex + 7] = -1   # unseeded sentinel
                    (self.cache, self.lengths, self.last_tokens,
                     self.samp, self.rng_key, _) = self._cont_fn(p, t, 1)(
                        self.params, self.cache, self.lengths,
                        self.last_tokens, self.samp, self.rng_key,
                        self._put(packed), ek, ev, *self._extra())
        chunks, k = [], 1
        while k <= self.decode_chunk:
            chunks.append(k)
            k *= 2
        spans = self._span_menu()
        combos = [(c, s) for c in chunks for s in spans]
        if len(combos) > 16:
            # long-cache engines: the full (chunk x span) grid is too many
            # compiles — warm every chunk at full span plus the workhorse
            # chunk at every span; cold combos compile lazily on first use
            combos = ([(c, self.max_len) for c in chunks]
                      + [(chunks[-1], s) for s in spans[:-1]])
        out = None
        # spec mode dispatches _spec_fn instead of _decode_fn — warm THAT
        # menu (the plain decode menu would be dead weight)
        fn = self._spec_fn if self.spec else self._decode_fn
        for c, span in combos:
            (self.cache, self.lengths, self.last_tokens, self.samp,
             self.rng_key, out) = fn(c, span)(
                self.params, self.cache, self.lengths, self.last_tokens,
                self.samp, self.rng_key,
                self._put(np.zeros((self.n_slots,), bool)),
                *self._extra())
        if self._spec_adapt is not None:
            # adaptive draft length: warm each sub-k_max menu k at the
            # workhorse chunk and the drain-tail chunk (full span only —
            # the rest of the (chunk, span, k) cube would explode compile
            # time; cold members fall back to the static-k program at
            # dispatch, exactly like cold spans fall back to full span)
            for kd in self._spec_adapt.menu[:-1]:
                for c in {chunks[-1], 1}:
                    (self.cache, self.lengths, self.last_tokens, self.samp,
                     self.rng_key, out) = self._spec_fn(
                        c, self.max_len, kd)(
                        self.params, self.cache, self.lengths,
                        self.last_tokens, self.samp, self.rng_key,
                        self._put(np.zeros((self.n_slots,), bool)),
                        *self._extra())
        float(np.asarray(out).flat[0])  # sync: compile + execute finished
        # reset via _put, not zeros_like: under a mesh the reset arrays must
        # carry the same committed replicated sharding the programs were
        # traced with, or the first live request retraces (= recompiles)
        self.lengths = self._put(np.zeros((self.n_slots,), np.int32))
        self.last_tokens = self._put(np.zeros((self.n_slots,), np.int32))
        self.samp = self._put(self._samp_reset())
        self._host_lengths[:] = 0
        self._pending = None
        self._inflight[:] = 0
        self._active_host = None
        self._active_dev = None
        self._decode_chunk_warm = self.decode_chunk
        self._warmed = True

    def close(self) -> None:
        """Release device state NOW. The engine is cyclic (compiled-
        program dicts hold jit(partial(self._...)) objects that reference
        the engine), so `del engine` alone leaves the KV cache + params
        refs alive until a full gc pass — on a 16 GiB chip that is the
        difference between the next engine fitting or not. close()
        breaks the cycles and drops the big buffers eagerly."""
        import gc

        for d in (self._prefill_fns, self._decode_fns, self._spec_fns,
                  self._cont_fns, self._extract_fns,
                  self._extract_raw_fns):
            d.clear()
        self.kvcache = None   # block payloads hold the only device refs
        self._pending = None
        self._active_dev = None
        self._active_host = None
        self.cache = None
        self.params = None
        gc.collect()

    def _step_counters(self) -> dict[str, float]:
        """The family's STEP_COUNTERS as the replayed decode chunks have
        folded them, and what its `prompt_counters` (where it has one)
        make of the prompt tokens computed so far."""
        out = {name: float(v) for (name, _), v in zip(
            self.family.STEP_COUNTERS, self._step_counts)}
        per_prompt = getattr(self.family, "prompt_counters", None)
        if per_prompt is not None:
            out.update(per_prompt(self.cfg, self._prefill_computed_tokens))
        return out

    def _obs_publish(self) -> None:
        """Scrape hook body: refresh this engine's queue-depth gauges
        just before a /metrics render (see obs.metrics.add_scrape_hook;
        exceptions are swallowed by the hook runner, so a closed engine
        can't poison a scrape)."""
        self.phase_clock.publish()
        s = self.scheduler.stats()
        obs_metrics.SCHED_QUEUED.set(s.queued, engine=self.role)
        obs_metrics.SCHED_ACTIVE.set(s.active, engine=self.role)
        obs_metrics.INFLIGHT.set(s.queued + s.active,
                                 component=self.role)
        # resolved attention impls as info-style gauges (ISSUE 20): one
        # series per (engine, phase, impl), value 1 — a scrape can alert
        # on a fleet member silently falling back to the einsum path
        obs_metrics.ATTENTION_IMPL.set(
            1, engine=self.role, phase="decode",
            impl=self.cfg.decode_attention_impl)
        obs_metrics.ATTENTION_IMPL.set(
            1, engine=self.role, phase="prefill",
            impl=self.cfg.prefill_attention_impl)
        for name, v in self._step_counters().items():
            obs_metrics.ENGINE_STEP_COUNT.set(v, engine=self.role, name=name)
        # ... and what the family says of its cache's layout (the latent
        # slab's bytes): llama's says nothing
        if self.cache is not None:
            for name, v in self.family.cache_stats(self.cache).items():
                obs_metrics.ENGINE_STEP_COUNT.set(v, engine=self.role,
                                                  name=name)
        if self.kvcache is not None:
            st = self.kvcache.stats()
            obs_metrics.KV_FREE_BLOCKS.set(st["free_blocks"],
                                           engine=self.role)
            obs_metrics.KV_WATERMARK_FRAC.set(st["watermark_frac"],
                                              engine=self.role)

    def is_done(self, req_id: int) -> bool:
        return req_id in self._done

    def result(self, req_id: int) -> list[int]:
        if req_id not in self._done:
            raise KeyError(f"request {req_id} not finished")
        return self._results[req_id]

    def result_logprobs(self, req_id: int) -> list[float]:
        """Per-token raw-model logprobs of result(req_id) (same length;
        the OpenAI `logprobs` surface)."""
        if req_id not in self._done:
            raise KeyError(f"request {req_id} not finished")
        return self._logprobs[req_id]

    def result_top_logprobs(self, req_id: int) -> list[dict[int, float]]:
        """Per-position top-N alternative logprobs ({token_id: logprob});
        requires the engine to be built with logprobs_topk > 0."""
        if not self.logprobs_topk:
            raise ValueError("engine built with logprobs_topk=0")
        if req_id not in self._done:
            raise KeyError(f"request {req_id} not finished")
        return self._toplogprobs[req_id]

    def partial_result(self, req_id: int) -> list[int]:
        """Tokens generated so far (streaming consumers poll this while
        the request runs). Snapshot copy: the engine thread appends."""
        return list(self._results.get(req_id, ()))

    def partial_logprobs(self, req_id: int) -> list[float]:
        """Logprobs of the tokens generated so far (streaming twin of
        result_logprobs)."""
        return list(self._logprobs.get(req_id, ()))

    def last_append(self, req_id: int) -> tuple[int, float] | None:
        """(tokens the request had before its newest append, when that
        append began on time.monotonic): what a stream thread sets its
        own pick-up instant against."""
        return self._append_t.get(req_id)

    def finish_reason(self, req_id: int) -> str:
        """Why a finished request stopped: "stop" (EOS) or "length"
        (max-new-tokens / cache room). Read before release()."""
        return self._finish_reasons.get(req_id, "length")

    def release(self, req_id: int) -> None:
        """Drop all per-request state. Long-lived servers MUST call this
        after reading result(), or per-request dicts grow without bound."""
        self._done.discard(req_id)
        self._results.pop(req_id, None)
        self._logprobs.pop(req_id, None)
        self._toplogprobs.pop(req_id, None)
        self._submit_t.pop(req_id, None)
        self._first_token_t.pop(req_id, None)
        self._finish_t.pop(req_id, None)
        self._finish_reasons.pop(req_id, None)
        self._req_tenant.pop(req_id, None)
        self._cached_prefix.pop(req_id, None)
        self._req_plen.pop(req_id, None)
        self._prefill_start_t.pop(req_id, None)
        self._req_trace.pop(req_id, None)
        self._phase_mark.pop(req_id, None)
        self._phase_fin.pop(req_id, None)
        self._append_t.pop(req_id, None)

    def generate(self, prompt: Sequence[int],
                 max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 adapter: str | None = None, **kw) -> list[int]:
        rid = self.submit(prompt, max_new_tokens, temperature,
                          adapter=adapter, **kw)
        while not self.is_done(rid):
            if not self.step():
                raise RuntimeError("engine idle with request outstanding")
        return self.result(rid)

    def ttft_seconds(self, req_id: int) -> float | None:
        """Submit→first-token latency for one request (None until then)."""
        if req_id not in self._first_token_t:
            return None
        return self._first_token_t[req_id] - self._submit_t[req_id]

    def request_timing(self, req_id: int) -> dict[str, Any]:
        """Wall-clock record for one request (the loadgen runner's SLO
        input): submit / first-token / finish instants (time.monotonic;
        None until they happen), tenant, tokens delivered so far, and
        the prefix-reuse fields — prompt_len, cached_prefix_len (KV
        tokens reused from the radix cache; 0 until the prefill lands or
        with the cache off) and prefill_tokens (what was actually
        computed) — plus the explicit PHASE split (the disagg bench's
        interference attribution): queue_wait_ms (submit → the prefill
        leaving the queue), prefill_ms (queue exit → first token) and
        decode_ms (first token → finish), each None until its phase
        boundary lands; and `engine`, what the ENGINE THREAD did over the
        decode_ms window (obs.trace.PhaseClock.usage: per phase [ms,
        count] and CPU ms, device_empty_ms and its split by phase, the
        collector's pauses, the longest single occurrence since
        submit), None until finish. Read BEFORE release() — release
        drops all of it."""
        plen = self._req_plen.get(req_id)
        cached = self._cached_prefix.get(req_id, 0)
        sub = self._submit_t.get(req_id)
        pstart = self._prefill_start_t.get(req_id)
        first = self._first_token_t.get(req_id)
        fin = self._finish_t.get(req_id)
        fin_phases = self._phase_fin.get(req_id)

        def ms(a, b):
            return (round((b - a) * 1e3, 3)
                    if a is not None and b is not None else None)

        return {
            "submit_s": sub,
            "first_token_s": first,
            "finish_s": fin,
            "tenant": self._req_tenant.get(req_id),
            "n_tokens": len(self._results.get(req_id, ())),
            "prompt_len": plen,
            "cached_prefix_len": cached,
            "prefill_tokens": (plen - cached if plen is not None
                               else None),
            "queue_wait_ms": ms(sub, pstart),
            "prefill_ms": ms(pstart, first),
            "decode_ms": ms(first, fin),
            "engine": fin_phases[0] if fin_phases else None,
            # the family's decode-step counts so far, engine-wide (absent
            # where the family keeps none: the usage shape stays as it is)
            **({"counters": self._step_counters()}
               if self.family.STEP_COUNTERS else {}),
        }

    def cached_tokens(self, req_id: int) -> int:
        """Prompt tokens whose KV was reused from the prefix cache for
        this request (the OpenAI usage `cached_tokens` surface). 0 until
        the prefill lands, with the cache off, or on a miss."""
        return self._cached_prefix.get(req_id, 0)

    def set_tenant_limits(self, max_active_per_tenant: int = 0,
                          max_queued_per_tenant: int = 0) -> None:
        """Per-tenant fairness/admission knobs, forwarded to the scheduler
        (both twins): a soft work-conserving share cap on decode slots and
        a hard admission cap on queued requests (over it, submit raises
        TenantOverQuota). 0 disables either."""
        self.scheduler.set_fairness(max_active_per_tenant,
                                    max_queued_per_tenant)

    @property
    def decode_chunk_max(self) -> int:
        """Largest decode chunk the warmed program menu supports (the
        set_decode_chunk clamp; the SLO controller's upper bound)."""
        return self._decode_chunk_warm

    def set_decode_chunk(self, chunk: int) -> int:
        """Re-pick the decode chunk length at runtime (the SLO-aware
        `ttft_target_ms` control surface — loadgen/control.py): a prefill
        wave must drain the in-flight chunk first, so TTFT carries ~one
        chunk of decode wall time, while throughput mildly prefers longer
        chunks (measured at 8B/32 slots: chunk 8 = 1055 tok/s / p50
        ~465 ms; chunk 4 = 990 tok/s / p50 ~217 ms). Applied at the next
        chunk boundary — _do_decode reads self.decode_chunk per dispatch.
        After warmup the value is clamped to the warmed menu (powers of
        two up to the construction-time decode_chunk) so live traffic
        never waits on the XLA compiler. Returns the applied value."""
        chunk = max(1, int(chunk))
        if self._warmed:
            chunk = min(chunk, self._decode_chunk_warm)
        self.decode_chunk = chunk
        return chunk

    def mesh_info(self) -> dict[str, Any]:
        """The /healthz `mesh` section (ISSUE 14 satellite): layout name,
        axis names/sizes, device count, and params bytes — so a fleet
        operator can tell a single-chip replica from a tp slice from a
        tp×pp stage-sharded one without a device round-trip. The
        stage-sharded engine overrides this with its per-stage view."""
        params_bytes = (int(sum(l.nbytes
                                for l in jax.tree.leaves(self.params)))
                        if self.params is not None else 0)
        if self.mesh is None:
            return {"layout": "single", "axes": {}, "device_count": 1,
                    "params_bytes": params_bytes}
        from kubeflow_tpu.parallel.mesh import mesh_shape

        shape = mesh_shape(self.mesh)
        axes = {k: v for k, v in shape.items() if v > 1}
        return {"layout": "tensor" if axes.get("tensor", 1) > 1
                else "mesh",
                "axes": axes,
                "device_count": int(math.prod(shape.values())),
                "params_bytes": params_bytes}

    def metrics(self) -> dict[str, Any]:
        ttfts = list(self._ttft_window)  # survives release() of old requests
        s = self.scheduler.stats()
        out = {"queued": s.queued, "active": s.active,
               "completed": s.completed, "rejected": s.rejected,
               "cancelled": self._cancelled_count,
               "decode_chunk": self.decode_chunk,
               # the RESOLVED decode-attention impl (the benchmark and
               # /healthz read this, so a record can never misreport
               # which kernel path produced its numbers)
               "decode_attention_impl": self.cfg.decode_attention_impl,
               # ...and its prefill twin (ISSUE 20): the impl the
               # prefill/continuation chunk programs run
               "prefill_attention_impl": self.cfg.prefill_attention_impl,
               # which KV residency this engine runs (serving/paged.py
               # overrides to "paged" and adds the pool gauges)
               "kv_layout": self.kv_layout,
               "mesh": self.mesh_info()}
        if self._quant_matmul_sites:
            out["quant_matmul_sites"] = self._quant_matmul_sites
        out["prefill_tokens_computed"] = self._prefill_computed_tokens
        if self.cache is not None:
            out.update(self.family.cache_stats(self.cache))
        out.update(self._step_counters())
        if self.prefix_cache_enabled and self.kvcache is not None:
            st = self.kvcache.stats()
            out["prefix_hits"] = self._prefix_hits
            out["prefix_misses"] = self._prefix_misses
            out["prefix_entries"] = st["blocks"]
            looked = self._prefix_hits + self._prefix_misses
            out["prefix_cache"] = {
                **st,
                "request_hits": self._prefix_hits,
                "request_misses": self._prefix_misses,
                "request_hit_rate": (round(self._prefix_hits / looked, 4)
                                     if looked else None),
                "prefill_tokens_computed": self._prefill_computed_tokens,
                "prefill_tokens_saved": self._prefill_reused_tokens,
            }
        if self.adapters is not None:
            out["adapters_loaded"] = sorted(self._adapter_idx)
        if self._tenant_idx:
            out["tenants_seen"] = len(self._tenant_idx)
        if self.spec:
            out["spec_verify_rounds"] = self._spec_verifies
            out["spec_tokens_emitted"] = self._spec_tokens
            # 1.0 = no draft ever accepted (plain-decode cost); spec+1 =
            # every draft accepted — the effective per-round multiplier
            out["spec_tokens_per_round"] = round(
                self._spec_tokens / max(1, self._spec_verifies), 3)
            out["spec_draft_k_max"] = self.spec
            out["spec_est_round_tokens"] = round(
                self._est_round_tokens(), 3)
            if self._spec_adapt is not None:
                out["spec_draft_k_last"] = self._spec_last_k
                out["spec_accept_ema"] = round(
                    float(np.mean(self._spec_adapt.ema)), 3)
        if ttfts:
            out["ttft_p50_s"] = float(np.percentile(ttfts, 50))
            out["ttft_p99_s"] = float(np.percentile(ttfts, 99))
        return out

    # -- internals -----------------------------------------------------------

    def _extra(self) -> tuple:
        """Trailing program args: the adapter stack rides as an explicit
        argument (a closure would inline it into the HLO as constants)."""
        return () if self.adapters is None else (self.adapters,)

    @staticmethod
    def _pack_temp(temp: float) -> int:
        """Nearest-milli quantization; sub-milli temps still sample (floor
        of 1) rather than silently flipping to greedy. ONE rule for the
        full-prefill and continuation row layouts."""
        return max(1, round(temp * 1000)) if temp > 0 else 0

    @staticmethod
    def _pack_milli(v: float) -> int:
        """Signed nearest-milli quantization for the penalty columns with
        a floor of ±1 milli on nonzero values (the penalties' twin of the
        _pack_temp/top_p guards): a requested |v| < 0.0005 must stay a
        minimal penalty, not silently round to OFF (ADVICE r5)."""
        if v == 0:
            return 0
        q = round(v * 1000)
        return q if q else (1 if v > 0 else -1)

    def _row_tail(self, req_id: int) -> tuple:
        """The non-token row columns for one request: (temp, top_k, top_p,
        presence, frequency, seed[, adapter_idx]) — ONE source for every
        wave-packing call site."""
        tail = self._req_samp.get(req_id, (0.0, 0, 1.0, 0.0, 0.0, -1))
        if self.adapters is not None:
            tail = tail + (self._req_aids.get(req_id, 0),)
        return tail

    def _pack_rows(self, width: int, bucket: int, rows) -> np.ndarray:
        """[tokens ++ slot ++ prompt_len ++ temp_milli ++ top_k ++
        top_p_micro ++ presence_milli ++ freq_milli ++ seed(, aid)] per
        row, padded up to `width` by repeating the last row (idempotent
        duplicate writes). rows: list of (tokens, slot, prompt_len, temp,
        top_k, top_p, presence, frequency, seed[, adapter_idx])."""
        ex = self._row_extra
        padded = list(rows) + [rows[-1]] * (width - len(rows))
        packed = np.zeros((width, bucket + ex), np.int32)
        for i, row in enumerate(padded):
            toks, slot, plen, temp, topk, topp = row[:6]
            pres, freq, seed = row[6:9]
            packed[i, :len(toks)] = toks
            packed[i, -ex] = slot
            packed[i, -ex + 1] = plen
            packed[i, -ex + 2] = self._pack_temp(temp)
            packed[i, -ex + 3] = int(topk)
            # micro quantization with a floor of 1 (like _pack_temp): a
            # sub-micro top_p must stay a maximal filter, not flip to OFF
            packed[i, -ex + 4] = (1_000_000 if topp >= 1
                                  else max(1, round(topp * 1e6)))
            packed[i, -ex + 5] = self._pack_milli(pres)
            packed[i, -ex + 6] = self._pack_milli(freq)
            packed[i, -ex + 7] = int(seed)
            if ex == 9:
                packed[i, -1] = row[9] if len(row) > 9 else 0
        return packed

    def _cont_row_tokens(self, prompt: list[int], p: int, t: int):
        """A continuation row's token columns: the tail (prompt[p:p+...],
        padded to the tail bucket by _pack_rows) — plus, in speculative
        mode, the p prefix tokens appended after a pad-to-t, so the
        compiled program can mirror them into the history buffer."""
        tail = prompt[p:]
        if not self.spec:
            return tail
        return tail + [0] * (t - len(tail)) + prompt[:p]

    def _dispatch_prefill_cont_wave(self, p: int, t: int, pairs):
        """Dispatch ONE batched continuation prefill for all hits sharing
        (prefix length, tail bucket) — a shared-prefix burst costs one
        packed transfer + one dispatch, mirroring _dispatch_prefill_wave.
        pairs: list of (action, materialized (k, v) prefix); returns [W]
        device tokens."""
        self.phase_clock.enter("prefill_pack")
        width = 1
        while width < len(pairs):
            width *= 2
        padded = list(pairs) + [pairs[-1]] * (width - len(pairs))
        rows = [(self._cont_row_tokens(self._prompts[a.req_id], p, t),
                 a.slot, a.prompt_len) + self._row_tail(a.req_id)
                for a, _ in padded]
        packed = self._put(self._pack_rows(
            width, t + (p if self.spec else 0), rows))
        k_prefix, v_prefix = self._stack_prefix([e for _, e in padded])
        fn = self._cont_fn(p, t, width)
        self.phase_clock.enter("prefill_dispatch")
        (self.cache, self.lengths, self.last_tokens, self.samp,
         self.rng_key, out) = fn(
            self.params, self.cache, self.lengths, self.last_tokens,
            self.samp, self.rng_key, packed,
            k_prefix, v_prefix, *self._extra())
        return out

    def _bank_prefix_blocks(self, action) -> None:
        """After a prefill (full, continuation, or chunked chain), cache
        the slot's block-aligned prompt-prefix KV. Probe first — a chain
        already cached end-to-end costs zero extraction — then extract
        the aligned prefix ONCE (device-to-device slice; nothing crosses
        the host) and hand the radix insert lazy per-block slices: only
        NEW blocks are sliced and stored."""
        prompt = self._prompts.get(action.req_id)
        if prompt is None:
            return
        bt = self.prefix_block_tokens
        aligned = (len(prompt) // bt) * bt
        ns = self._req_aids.get(action.req_id, 0)
        if aligned <= 0:
            return
        if self.kvcache.cached_prefix_len(
                prompt, max_tokens=aligned, namespace=ns) >= aligned:
            return
        parts = self._extract_raw_fn(aligned)(self.cache, action.slot)

        def payload(_i, s, e):
            return self._payload_slice(parts, s, e)

        self.kvcache.insert(prompt, payload, max_tokens=aligned,
                            tenant=self._req_tenant.get(action.req_id),
                            namespace=ns)

    def _dispatch_prefill_wave(self, bucket: int,
                               wave: list[PrefillAction]):
        """Dispatch one batched prefill over `wave`; returns the (device)
        next-token array [W] WITHOUT fetching, so several waves can
        pipeline. The wave is padded up to a power-of-two width by
        repeating its last action (idempotent duplicate writes), keeping
        the compiled-program menu small."""
        self.phase_clock.enter("prefill_pack")
        width = 1
        while width < len(wave):
            width *= 2
        # one packed transfer: [tokens ++ slot ++ prompt_len ++ sampling
        # columns] per row (each transfer is a round trip of its own)
        rows = [(self._prompts[a.req_id], a.slot, a.prompt_len)
                + self._row_tail(a.req_id) for a in wave]
        self._prefill_computed_tokens += sum(
            len(self._prompts[a.req_id]) for a in wave)
        packed = self._put(self._pack_rows(width, bucket, rows))
        fn = self._prefill_fn(bucket, width)
        self.phase_clock.enter("prefill_dispatch")
        (self.cache, self.lengths, self.last_tokens, self.samp,
         self.rng_key, out) = fn(
            self.params, self.cache, self.lengths, self.last_tokens,
            self.samp, self.rng_key, packed, *self._extra())
        return out

    def _do_decode(self) -> None:
        """Scan-fused decode: K steps execute inside ONE compiled program
        (one dispatch + one token fetch for the whole chunk): the host
        pays one round trip per chunk instead of one per token.

        PIPELINED (pipeline_decode=True): the next chunk is DISPATCHED
        before the previous chunk's tokens are fetched, so the host-side
        fetch RTT + replay overlaps the device's execution of the new
        chunk — per-chunk wall time becomes max(device, host) instead of
        their sum (~106ms RTT measured against an 8B chunk). The cost: a
        slot that finishes mid-chunk burns at most ONE extra chunk of
        junk compute before the host notices, and planning uses lengths
        that lag the device by the in-flight chunk (tracked via
        _inflight).

        K = largest power of two <= decode_chunk that fits cache headroom
        (chunk writes KV rows L..L+K-1 for the fullest slot, which must
        stay < max_len). Slots may finish (EOS / max_new) mid-chunk: their
        surplus tokens are dropped host-side, and new arrivals wait at
        most one chunk for their prefill — decode_chunk bounds scheduling
        latency."""
        clock = self.phase_clock
        clock.enter("decode_plan")
        if self._pending is not None:
            # if the in-flight chunk's deliveries already satisfy every
            # active budget, OR the cache has no room for even one more
            # row past the in-flight writes (the out_of_room finish will
            # land at replay), another dispatch would be pure junk
            # compute — drain instead (this is what makes the final chunk
            # of a drain free under pipelining). Plain decode delivers
            # EXACTLY psteps per continuing slot; spec rounds deliver
            # 1..per_tok each, so the guard also drains when the LIKELY
            # spec delivery (observed live acceptance, optimism margin)
            # covers every budget — at high acceptance the follow-on
            # chunk is near-certain junk and one dispatch RTT is the
            # whole r3->r4 spec-throughput regression (VERDICT r4 weak
            # #3); at low acceptance the estimate stays small and the
            # pipeline keeps running.
            psr, psteps, _, _, _ = self._pending
            full = max((int(self._host_lengths[s] + self._inflight[s])
                        for s in range(self.n_slots) if psr[s] >= 0),
                       default=0) >= self.max_len
            need = [self._max_new[r] - len(self._results[r])
                    for r in psr if r >= 0 and r in self._max_new]
            likely = psteps * self._est_round_tokens() * 1.25
            if full or all(n <= psteps for n in need) or (
                    self.spec and all(n <= likely for n in need)):
                self._drain_pending()
                return
        slot_req = self._mask_unfunded(
            [self.scheduler.slot_request(s) for s in range(self.n_slots)])
        active = np.array([r >= 0 for r in slot_req], bool)
        if not active.any():
            # every live slot is admission-held (paged engine under
            # block pressure): nothing has KV to decode against yet
            return
        # adaptive draft length: the per-slot acceptance EMAs of the
        # DRAFTING slots (greedy, penalty-free — sampled/penalized rows
        # draft nothing by contract) set this round's k; a batch with no
        # drafting slot verifies at the smallest warmed k, near
        # plain-decode cost
        kd = self.spec or 0
        if self.spec and self._spec_adapt is not None:
            kd = self._spec_adapt.pick(
                [s for s, r in enumerate(slot_req)
                 if r >= 0 and self._draftable(r)])
        per_tok = (kd + 1) if self.spec else 1
        # in-flight credit: the pending chunk GUARANTEES psteps deliveries
        # to each slot it still owns, so the next chunk is sized for what
        # will remain after those land — without it a second chunk can be
        # sized past a request's true budget (junk compute at the tail)
        credit = [0] * self.n_slots
        if self._pending is not None:
            psr, psteps, _, _, _ = self._pending
            for s, r in enumerate(psr):
                if r >= 0 and r == slot_req[s]:
                    credit[s] = psteps
        remaining = max(max(1, self._max_new[r] - len(self._results[r])
                            - credit[s])
                        for s, r in enumerate(slot_req) if r >= 0)
        # planned-position accounting: rows already written by the
        # in-flight (unfetched) chunk count toward headroom and span
        planned = self._host_lengths + self._inflight
        headroom = self.max_len - int(
            max(planned[s] for s in range(self.n_slots) if active[s]))
        est = self._est_round_tokens()
        k = 1
        # doubling guard: the NEXT candidate (k*2 steps) must fit — a
        # spec round writes up to per_tok rows, plain decode exactly one;
        # spec sizing counts LIKELY tokens per round (est), not rounds,
        # so a high-acceptance engine stops growing once k rounds should
        # cover the largest remaining budget
        while (k * 2 <= self.decode_chunk
               and k * 2 * per_tok <= headroom
               and k * est < remaining):
            k *= 2
        # length-aware span: the chunk's last write lands at max_len-1 at
        # most; attend over the smallest power-of-two window covering every
        # active length through the chunk's end
        longest = int(max((planned[s] for s in range(self.n_slots)
                           if active[s]), default=0))
        span = self._pick_span(min(longest + k * per_tok, self.max_len))
        # after warmup, never hand live traffic to the XLA compiler: a
        # (chunk, span[, k]) combo outside the warmed menu (small tail
        # chunks at mid spans; adaptive ks at mid chunks — warmup covers
        # every chunk at FULL span with k_max, the workhorse chunk at
        # every span, and the sub-k_max menu at the workhorse and tail
        # chunks) falls back first to the full-span variant, then to the
        # static-k program. At 8B dims a cold compile is seconds; the
        # fallbacks cost ~nothing extra (full-span reads measured 20.1 vs
        # 19.8 ms/step; a too-long k only verifies dead draft positions).
        if self.spec:
            if self._warmed and (k, span, kd) not in self._spec_fns:
                if (k, self.max_len, kd) in self._spec_fns:
                    span = self.max_len
                else:
                    # static-k program at FULL span (every chunk is warm
                    # there). span must be full, not merely warm: the
                    # picked span only covers k*(kd+1) writes, and the
                    # static program advances up to k*(spec+1) rows —
                    # attending a too-short window would silently drop
                    # the newest context from late rounds' logits.
                    kd = self.spec
                    span = self.max_len
                    # the fallback k also writes more rows per round than
                    # the sizing assumed — shrink the chunk to stay
                    # inside the cache headroom (power-of-two chunks all
                    # warm at full span)
                    while k > 1 and k * (kd + 1) > headroom:
                        k //= 2
            fn = self._spec_fn(k, span, kd)
            per_tok = kd + 1
        else:
            if self._warmed and (k, span) not in self._decode_fns:
                span = self.max_len
            fn = self._decode_fn(k, span)
        self._spec_last_k = kd
        active_dev = self._active_for(active)
        clock.enter("decode_dispatch")
        (self.cache, self.lengths, self.last_tokens, self.samp,
         self.rng_key, out) = fn(
            self.params, self.cache, self.lengths, self.last_tokens,
            self.samp, self.rng_key, active_dev, *self._extra())
        # obs: phases and aggregate counters only on this path (no span
        # objects — scripts/check_observability.py lints that invariant)
        clock.note_step(int(active.sum()) * k * per_tok, steps=k)
        # decode attention copies a slot's KV blocks up to its length and
        # none of a dead slot's: of the blocks the span covers, how many
        # the chunk's last step moves
        blk = self._kv_block_tokens()
        n_k = -(-span // blk)
        clock.note_kv_blocks(
            np.minimum(n_k, (planned[active] + k * per_tok - 1) // blk
                       + 1).sum(), self.n_slots * n_k)
        rows_added = np.where(active, k * per_tok, 0)
        self._inflight += rows_added
        prev = self._pending
        self._pending = (slot_req, k, out, rows_added, kd)
        if not self.pipeline_decode:
            self._drain_pending()
        elif prev is not None:
            self._replay(prev)

    def _mask_unfunded(self, slot_req: list[int]) -> list[int]:
        """Decode-planning hook: the paged engine masks slots whose
        prefill is admission-HELD (slot assigned by the scheduler, no KV
        funded yet) to -1, so chunk sizing, the active mask, and replay
        treat them as empty until their prefill lands. Slab engines have
        no held state — identity."""
        return slot_req

    def _constrain_cnt(self, cnt):
        """Pin the penalty-count layout under a mesh (see _shard_over)."""
        if self.mesh is None:
            return cnt
        return jax.lax.with_sharding_constraint(cnt, self._cnt_sh)

    def _draftable(self, req_id: int) -> bool:
        """True when the request's rows draft under speculation: greedy
        (temp == 0) and penalty-free — the same predicate the compiled
        program applies per row."""
        t = self._req_samp.get(req_id)
        return t is None or (t[0] <= 0 and t[3] == 0 and t[4] == 0)

    def _active_for(self, active: np.ndarray):
        """Device-resident decode active mask, re-uploaded only when the
        mask actually changes (slot assignments move at prefill/finish
        boundaries, not per chunk) — the redundant per-chunk
        host->device transfer was pure overhead."""
        if (self._active_host is None
                or not np.array_equal(active, self._active_host)):
            self._active_host = active.copy()
            self._active_dev = self._put(active)
        return self._active_dev

    def _observe_round_tokens(self, n: int) -> None:
        """Fold one verify round's delivered-token count into the EMA the
        chunk sizing and drain heuristic consume."""
        if self._spec_round_ema is None:
            self._spec_round_ema = float(n)
        else:
            self._spec_round_ema += SPEC_EMA_ALPHA * (
                n - self._spec_round_ema)

    def _est_round_tokens(self) -> float:
        """Expected delivered tokens per decode round: exactly 1 in plain
        mode; in spec mode an EMA of tokens-per-verify-round (optimistic
        per_tok before any observation — worst case that costs is one
        lost overlap boundary, never junk). An EMA, not the engine-
        lifetime average (ADVICE r5 #2): after a workload shift from
        high- to low-acceptance text the stale lifetime average
        undersized chunks and triggered premature drains."""
        if not self.spec:
            return 1.0
        if self._spec_round_ema is None:
            return float(self.spec + 1)
        return min(float(self.spec + 1), max(1.0, self._spec_round_ema))

    def _drain_pending(self) -> None:
        """Fetch + replay the in-flight decode chunk, if any. Must run
        before any prefill dispatch or idle return: replay frees slots and
        completes requests, and the host bookkeeping must be current
        before slot assignments change."""
        p = self._pending
        if p is not None:
            self._pending = None
            self._replay(p)

    def _replay(self, pending) -> None:
        """Fetch one dispatched chunk's packed rows and replay them into
        per-request results. `slot_req` is the slot->request map AT
        DISPATCH time; a slot freed since (cancellation applied at a chunk
        boundary while this chunk was in flight) no longer maps to its
        captured request and is skipped — its rows are junk by contract,
        exactly like post-EOS surplus."""
        slot_req, steps, out, rows_added, kd = pending
        clock = self.phase_clock
        clock.enter("decode_fetch")
        out_np = np.asarray(out)   # one fetch per chunk
        clock.fetched(outstanding=self._pending is not None)
        clock.enter("replay")
        # in-flight rows for THIS chunk are now accounted by the replay's
        # own host_lengths advancement (junk/surplus rows stay counted in
        # neither — the next prefill into the slot resets both)
        alive = [self.scheduler.slot_request(s) == slot_req[s]
                 for s in range(self.n_slots)]
        now = time.monotonic()
        for slot, req in enumerate(slot_req):
            if req >= 0 and alive[slot]:
                self._append_t[req] = (len(self._results.get(req, ())), now)
        done_slots: set[int] = set()
        if self.spec:
            kp1 = kd + 1
            oc = self._out_cols
            for s in range(steps):
                for slot, req in enumerate(slot_req):
                    if req < 0 or slot in done_slots or not alive[slot]:
                        continue
                    cnt = int(out_np[s, slot, 0])
                    emits = out_np[s, slot, 1:].reshape(kp1, oc)
                    self._spec_verifies += 1
                    # live acceptance estimators: the round delivered cnt
                    # tokens = (cnt - 1) accepted drafts + the bonus
                    self._observe_round_tokens(cnt)
                    if self._spec_adapt is not None:
                        self._spec_adapt.observe(slot, cnt - 1, kd)
                    for j in range(cnt):
                        self._host_lengths[slot] += 1
                        # count DELIVERED tokens, not the round's emit
                        # count: a mid-round finish drops the surplus, and
                        # the tokens-per-round metric must not claim them
                        self._spec_tokens += 1
                        tok, lp, top = self._unpack_out(emits[j])
                        if self._record_token(req, slot, tok, lp, top):
                            done_slots.add(slot)
                            break
        else:
            if len(self._step_counts):
                steps_counts = out_np[:, 0, -len(self._step_counts):]
                for n, (_, how) in enumerate(self.family.STEP_COUNTERS):
                    self._step_counts[n] = (
                        self._step_counts[n] + steps_counts[:, n].sum()
                        if how == "sum" else steps_counts[-1, n])
            for row in out_np:   # [steps, n_slots, out_cols]
                for slot, req in enumerate(slot_req):
                    if req < 0 or slot in done_slots or not alive[slot]:
                        continue
                    self._host_lengths[slot] += 1
                    tok, lp, top = self._unpack_out(row[slot])
                    if self._record_token(req, slot, tok, lp, top):
                        # finished mid-chunk: later tokens are garbage for
                        # this slot; drop them (its cache is reset by the
                        # next prefill into the slot). The local return
                        # value — not the shared _done set — decides, so a
                        # concurrent release() from a server thread can't
                        # unfinish it.
                        done_slots.add(slot)
        # remove THIS chunk's planned rows: delivered ones re-entered via
        # host_lengths above; junk rows belong to freed slots whose state
        # the next prefill resets anyway
        self._inflight = np.maximum(self._inflight - rows_added, 0)

    def _record_token(self, req_id: int, slot: int, token: int,
                      lp: float = 0.0, top: dict[int, float] | None = None,
                      first_token: bool = False) -> bool:
        """Returns True when this token finished the request."""
        if first_token:
            now = time.monotonic()
            self._first_token_t[req_id] = now
            self._ttft_window.append(now - self._submit_t[req_id])
            self._phase_mark[req_id] = self.phase_clock.mark()
            self._append_t[req_id] = (0, now)
        res = self._results[req_id]
        res.append(token)
        self._logprobs[req_id].append(lp)
        if top is not None and req_id in self._toplogprobs:
            self._toplogprobs[req_id].append(top)
        hit_eos = self.eos_id is not None and token == self.eos_id
        # stop-sequence suffix match (host-side, at chunk-boundary replay):
        # the matched sequence is EXCLUDED from the result (OpenAI
        # semantics) — matching over the accumulated output makes
        # sequences spanning chunk boundaries work for free
        hit_stop = 0
        if not hit_eos:
            for ss in self._req_stop.get(req_id, ()):
                if len(res) >= len(ss) and res[-len(ss):] == ss:
                    hit_stop = len(ss)
                    break
        if hit_stop:
            del res[-hit_stop:]
            del self._logprobs[req_id][-hit_stop:]
            if req_id in self._toplogprobs:
                del self._toplogprobs[req_id][-hit_stop:]
        # cache exhaustion: _host_lengths == KV rows written; the NEXT decode
        # writes at that index, which must stay < max_len (the host mirror
        # avoids a device fetch here)
        out_of_room = self._host_lengths[slot] >= self.max_len
        freed = self.scheduler.token_done(
            slot, finished=hit_eos or bool(hit_stop) or out_of_room)
        if freed:
            # OpenAI finish_reason semantics: "stop" = the model chose to
            # end (EOS) or a stop sequence matched; "length" = budget/cache
            # truncation
            self._finish_reasons[req_id] = (
                "stop" if (hit_eos or hit_stop) else "length")
            self._finish_t[req_id] = time.monotonic()
            self._close_phases(req_id)
            self._done.add(req_id)
            self._prompts.pop(req_id, None)
            self._max_new.pop(req_id, None)
            self._req_samp.pop(req_id, None)
            self._req_stop.pop(req_id, None)
            self._req_aids.pop(req_id, None)
            self._deadlines.pop(req_id, None)
            self._obs_finish(req_id)
        return freed

    def _close_phases(self, req_id: int) -> None:
        """At a request's finish instant: what the engine thread did
        since its first token (request_timing()'s `engine`) and the
        decode-step window of its decode span. Nothing for a request
        that never got a token."""
        first = self._phase_mark.pop(req_id, None)
        if first is None:
            return
        clock = self.phase_clock
        self._phase_fin[req_id] = (
            clock.usage(first, self._submit_t.get(req_id)),
            StepAggregator.window((first.steps, first.tokens),
                                  clock.snapshot()))

    def _stall_context(self) -> dict[str, Any]:
        """What a stall line says beside the phase (PhaseClock calls
        this on the engine thread, once per stall)."""
        s = self.scheduler.stats()
        return {"in_flight": ("decode_chunk" if self._pending is not None
                              else "nothing"),
                "queued": s.queued, "active": s.active}

    def _obs_finish(self, req_id: int) -> None:
        """Per-request telemetry, emitted ONCE at finish (never inside
        the decode loop): lifecycle counter, TTFT/TPOT/queue-wait
        histogram observations, and — when the request carried a SAMPLED
        trace id — the retrospective queue/prefill/decode spans
        reconstructed from the timestamps the engine already keeps for
        request_timing()."""
        reason = self._finish_reasons.get(req_id, "length")
        obs_metrics.REQUESTS.inc(component=self.role, event=reason)
        sub = self._submit_t.get(req_id)
        pstart = self._prefill_start_t.get(req_id)
        first = self._first_token_t.get(req_id)
        fin = self._finish_t.get(req_id)
        n_tok = len(self._results.get(req_id, ()))
        if sub is not None and first is not None:
            obs_metrics.TTFT_SECONDS.observe(first - sub,
                                             component=self.role)
        if sub is not None and pstart is not None:
            obs_metrics.QUEUE_WAIT_SECONDS.observe(pstart - sub,
                                                   component=self.role)
        if first is not None and fin is not None and n_tok >= 2:
            obs_metrics.TPOT_SECONDS.observe((fin - first) / (n_tok - 1),
                                             component=self.role)
        trace = self._req_trace.pop(req_id, None)
        if trace is None or not TRACER.sampled(trace):
            return
        tenant = self._req_tenant.get(req_id)
        TRACER.record_span(f"{self.role}.queue", "queue", trace, sub,
                           pstart, tenant=tenant)
        TRACER.record_span(f"{self.role}.prefill", "prefill", trace,
                           pstart, first,
                           prompt_len=self._req_plen.get(req_id),
                           cached_prefix_len=self._cached_prefix.get(
                               req_id, 0))
        attrs: dict[str, Any] = {"n_tokens": n_tok,
                                 "finish_reason": reason,
                                 "tenant": tenant}
        if req_id in self._phase_fin:
            attrs.update(self._phase_fin[req_id][1])
        TRACER.record_span(f"{self.role}.decode", "decode", trace,
                           first, fin, **attrs)


# -- disaggregated serving roles (ISSUE 13, ROADMAP #3) -----------------------
#
# Prefill and decode want opposite things from one engine: prefill is a
# bursty, compute-bound batch job whose chained dispatches block the step
# loop for a whole chunk plan, while decode wants short, uniform steps —
# interleaving them is exactly the interference the loadgen per-bucket
# TTFT table measures (a 4k-token prompt arriving mid-window spikes every
# active request's TPOT). The disaggregated configuration
# (serving/disagg.py) splits the two onto dedicated engine ROLES and moves
# the finished KV between them as radix-cache block payloads — the r10
# handoff currency. Both roles are ordinary LLMEngines (one program menu,
# one scheduler, one parity story); the role classes below only pin the
# contract each side of the split relies on. Like LLMEngine itself, role
# engines may only be constructed inside supervisor factory functions
# (scripts/check_dataplane.py lints all three names).


class PrefillEngine(LLMEngine):
    """Dedicated prefill worker: runs (chunked) prefill — starting from
    the longest chain its own radix prefix cache already holds — and
    STOPS at KV materialization. Every submission is clamped to ONE
    greedy token, which the scheduler counts as the request's completion
    AT the prefill, so the step loop never dispatches a decode program
    and a queued long prompt never steals a decode step from anyone.
    The single sampled token is a byproduct the coordinator discards
    (greedy, so a crash-replay of an un-handed-off prefill is
    byte-deterministic); the PRODUCT is the banked block-aligned prefix
    KV in self.kvcache, which the coordinator matches and hands to the
    decode worker through a KVHandoff (serving/disagg.py)."""

    role = "prefill"

    def __init__(self, params, cfg, **kw):
        # the radix cache IS the handoff staging area — a prefill worker
        # without it would materialize KV with no way to export it
        kw["prefix_cache"] = True
        super().__init__(params, cfg, **kw)

    def submit(self, prompt, max_new_tokens: int = 1,
               temperature: float = 0.0, **kw) -> int:
        # max_new/temperature are clamped, not honored: KV
        # materialization is the entire job, and greedy keeps the
        # supervisor's journal-replay byte-exact
        return super().submit(prompt, 1, 0.0, **kw)


class DecodeEngine(LLMEngine):
    """Dedicated decode worker: admissions are EXPECTED to find their
    block-aligned prompt prefix already in the radix cache (a KVHandoff
    inserted it), so per-request prefill compute is at most one tail
    bucket of continuation — decode steps stay short and uniform. A
    full/chunked prefill here means the handoff was missed (an eviction
    raced the insert, or a supervisor replay landed on a fresh post-crash
    cache): counted in `full_prefills`, never fatal — the decode worker
    degrades to colocated behavior rather than refusing the request,
    which is what keeps the crash-recovery story identical to r11's."""

    role = "decode"

    def __init__(self, params, cfg, **kw):
        kw["prefix_cache"] = True
        super().__init__(params, cfg, **kw)
        # admissions (>= 1 block of prompt) that found NO cached prefix
        # and paid a full prefill — the disagg miss counter
        self.full_prefills = 0

    def _prefix_lookup(self, action):
        hit = super()._prefix_lookup(action)
        if hit is None and self.prefix_block_tokens \
                and len(self._prompts.get(action.req_id, ())) - 1 \
                >= self.prefix_block_tokens:
            self.full_prefills += 1
        return hit

    def metrics(self):
        out = super().metrics()
        out["disagg_full_prefills"] = self.full_prefills
        return out
