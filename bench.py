"""Headline benchmark: Llama training step MFU + tokens/sec/chip on the local
accelerator. The LAST stdout line is ONE compact JSON headline:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
and the full extras (longctx/serving/spec/8B sections) are written to
BENCH_EXTRAS.json in the repo root — the driver records only the last
~2000 bytes of stdout, so the headline must stay well under that
(VERDICT r4 weak #1: two rounds of extras-inlined output left
`parsed: null` in the driver record).

`python bench.py --check` re-validates the committed BENCH_EXTRAS.json
against the perf floors in PERF_FLOORS (VERDICT r4 ask #5) without
re-running the hardware benchmark; the slow-lane test
tests/test_perf_floors.py runs the same gate.

Baseline contract (BASELINE.json): >=40% MFU for Llama JAXJob. The reference
publishes no numbers ("published": {}), so vs_baseline = achieved_MFU / 0.40.

Model size is chosen to fit one chip's HBM with Adam state (fp32 second
moment, bf16 first moment — OptimizerConfig.mu_dtype); the same code path
scales to 8B on v5e-16 via MeshConfig (see __graft_entry__.dryrun_multichip
for the sharded-path proof and training/contract.py for the v5e-compiler
memory evidence).
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time

import jax

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel import MeshConfig
from kubeflow_tpu.training import Trainer, TrainerConfig, OptimizerConfig
from kubeflow_tpu.training import data as data_lib
from kubeflow_tpu.training.mfu import mfu

SEQ_LEN = 2048
BATCH = 6   # largest per-chip batch that fits HBM with unrolled layers +
            # minimal remat; b6 beats b4 by ~1 MFU pt (amortized fixed work)
WARMUP = 3
MEASURE = 10

# -- bench self-defense (ROADMAP r6 item #1) ---------------------------------
# BENCH_r05 and MULTICHIP_r05 both died rc=124: bench.py had no overall
# time budget and the 8B child subprocess could outlive a killed parent on
# the 1-core box, starving it. The budget is a hard wall-clock allowance
# for the WHOLE bench run: each best-effort section checks it first and
# records itself in extras["skipped_for_budget"] instead of running past
# it, and the serving_8b child gets (a) its own timeout computed from the
# REMAINING budget, (b) start_new_session so the parent can kill its whole
# process group, and (c) an in-child watchdog that exits when the deadline
# passes or the parent dies — an orphaned 8B child can never starve the
# box again. The compact headline is ALWAYS the last stdout line.
BUDGET_ENV = "KTPU_BENCH_BUDGET_S"
DEFAULT_BUDGET_S = 2400.0
#: wall-clock reserved for the headline train run + post-child extras when
#: sizing the serving_8b child's timeout
RESERVE_AFTER_CHILD_S = 900.0


class Budget:
    """Monotonic wall-clock budget; total from KTPU_BENCH_BUDGET_S unless
    given explicitly."""

    def __init__(self, total_s: float | None = None):
        if total_s is None:
            total_s = float(os.environ.get(BUDGET_ENV, DEFAULT_BUDGET_S))
        self.total_s = total_s
        self.t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        return self.total_s - self.elapsed()

    def expired(self) -> bool:
        return self.remaining() <= 0


def _budget_gate(extras: dict, budget: Budget, name: str) -> bool:
    """True when `name` may still run; False records the skip so the
    committed record says WHY a section is absent (a silently missing
    section reads as a floor failure, which is the honest default — this
    marker distinguishes 'out of time' from 'crashed')."""
    if not budget.expired():
        return True
    extras.setdefault("skipped_for_budget", []).append(name)
    return False


def main() -> None:
    budget = Budget()
    # serving_8b runs FIRST, in a fresh subprocess, BEFORE this process
    # initializes its own JAX backend: the 32-slot engine peaks at
    # ~13-14 GiB of the 16 GiB HBM, the chip is shared, and even a
    # merely-ATTACHED second client costs enough reserved HBM to tip the
    # child into RESOURCE_EXHAUSTED (measured: the child fits alone,
    # fails with an idle parent attached). The child probes the platform
    # itself and reports not_tpu when this is a CPU box. Its timeout
    # comes from the REMAINING budget, leaving room for the headline run.
    serving_8b: dict | None = None
    serving_8b_err: str | None = None
    child_timeout = min(1200.0, budget.remaining() - RESERVE_AFTER_CHILD_S)
    if child_timeout < 60.0:
        serving_8b_err = (f"skipped_for_budget: {budget.remaining():.0f}s "
                          "remaining leaves no room for the 8B child")
    else:
        try:
            serving_8b = _serving_8b_subprocess(child_timeout)
            if serving_8b.get("not_tpu"):
                # on a TPU box this means the child could not see the chip
                # (held by another process at child start) — say so rather
                # than recording a bare null
                serving_8b = None
                serving_8b_err = ("child saw no TPU (chip busy/unavailable "
                                  "at subprocess start, or a CPU box)")
        except Exception as e:
            serving_8b_err = f"{type(e).__name__}: {e}"
    n_dev = jax.local_device_count()
    on_tpu = "tpu" in str(jax.devices()[0].device_kind).lower()
    # Shape picked by scripts/mfu_sweep.py on TPU v5 lite: larger d_model
    # (bigger MXU tiles) beats deeper/narrower; minimal remat (checkpoint
    # dots) beats full recompute once activations fit HBM.
    model_overrides = dict(
        vocab_size=32000, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=7168, max_seq_len=SEQ_LEN, remat=False,  # b6 fits HBM without
        # remat at this shape, and skipping the bwd recompute is worth
        # ~6 MFU pts (0.558 -> 0.615 measured; the r2 sweep also tried
        # vocab-blockwise fused CE and larger flash blocks — both lost)
        scan_layers=False,  # L8 is shallow: unrolled layers skip the scan's
                            # residual-stacking copies (+3 MFU pts measured)
    ) if on_tpu else dict(
        vocab_size=512, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=256,
    )
    seq = SEQ_LEN if on_tpu else 128
    # per-device batch: keeps the data-parallel sharding divisible on any host
    batch = (BATCH if on_tpu else 2) * n_dev

    trainer = Trainer(TrainerConfig(
        model="llama",
        model_overrides=model_overrides,
        batch_size=batch,
        optimizer=OptimizerConfig(warmup_steps=10, total_steps=1000,
                                  mu_dtype="bfloat16" if on_tpu else None),
        mesh=MeshConfig(data=-1),
        log_every=1000,
    ))
    trainer.metrics.echo = False
    # Train from an on-disk token corpus through the prefetching loader
    # (VERDICT r2 missing #1: the bench exercises the real data path, not a
    # synthetic generator). KTPU_BENCH_CORPUS points at a user corpus; the
    # default is a generated one with the same learnable n-gram structure.
    from kubeflow_tpu.training.loader import token_file_dataset, write_corpus

    corpus = os.environ.get("KTPU_BENCH_CORPUS")
    vocab = model_overrides["vocab_size"]
    if not corpus:
        n_tok = 2_000_000
        corpus = os.path.join(tempfile.gettempdir(),
                              f"ktpu_bench_corpus_v{vocab}.bin")
        # regenerate unless a complete corpus is already cached (size check
        # guards against a truncated file from an interrupted earlier run);
        # tmp-name + rename keeps the write atomic
        if not (os.path.exists(corpus) and os.path.getsize(corpus) == 4 * n_tok):
            from scripts.gen_corpus import synthetic_corpus

            tmp = corpus + f".tmp.{os.getpid()}"
            write_corpus(tmp, synthetic_corpus(n_tok, vocab, seed=0))
            os.replace(tmp, corpus)
    data = token_file_dataset(corpus, batch, seq, seed=1)

    state = trainer.init_state()
    batch0 = trainer.shard_batch(next(data))
    step_fn = trainer.compiled_step(state, batch0)
    batches = [trainer.shard_batch(next(data)) for _ in range(MEASURE)]
    for _ in range(WARMUP):
        state, metrics = step_fn(state, batches[0])
    # end timing with a scalar fetch: the value on the host means the
    # steps that produced it have finished.
    float(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(MEASURE):
        state, metrics = step_fn(state, batches[i])
    final_loss = float(metrics["loss"])  # forces the whole step chain
    dt = (time.perf_counter() - t0) / MEASURE
    assert final_loss == final_loss  # NaN guard

    tokens_per_step = batch * seq
    # MFU counts *model* FLOPs (6N + attention), not remat recompute — XLA's
    # cost analysis on a full-remat step would inflate the number.
    flops = llama.flops_per_token(trainer.model_cfg, seq) * tokens_per_step

    achieved_mfu = mfu(flops, dt, n_dev)
    extras = {
        "tokens_per_sec_per_chip": round(tokens_per_step / dt / n_dev, 1),
        "step_time_s": round(dt, 4),
        "device": str(jax.devices()[0].device_kind),
        "n_devices": n_dev,
        "flops_per_step": flops,
        # honest labelling (VERDICT r1 weak #2): this measures a ~0.6B
        # single-chip PROXY of the contract model; the true Llama-3-8B
        # shape is proven separately by training/contract.py (v5e:4x4
        # topology AOT compile, peak HBM 15.2G < 16G) + tests/test_contract_8b.py
        "model": "llama-proxy-0.6b(d2048xL8,seq2048)" if on_tpu
                 else "llama-tiny(cpu)",
        "contract_model": "llama3-8b on v5e-16 (see training/contract.py)",
        "data_source": f"token_file[{type(data).__name__}]({corpus})",
    }
    # Loader feed-rate proof: the pipeline keeps the MXU fed iff the loader
    # produces tokens faster than the train step consumes them.
    t0 = time.perf_counter()
    n_feed = 40
    for _ in range(n_feed):
        next(data)
    feed_rate = n_feed * tokens_per_step / (time.perf_counter() - t0)
    extras["loader_tokens_per_sec"] = round(feed_rate, 1)
    extras["loader_feed_margin"] = round(feed_rate / (tokens_per_step / dt), 2)
    if hasattr(data, "close"):
        data.close()
    # free the headline run's HBM before the extras: state+batches for the
    # 0.65B proxy are ~10G of the 16G chip, and the longctx/serving/decode
    # sections each build their own models (observed: keeping these alive
    # RESOURCE_EXHAUSTs every extra)
    del state, batch0, batches, step_fn, trainer, metrics
    if _budget_gate(extras, budget, "longctx"):
        try:
            extras["longctx"] = longctx_bench(on_tpu)
        except Exception as e:  # long-context point is a best-effort extra
            extras["longctx_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving"):
        try:
            extras.update(serving_bench(on_tpu))
        except Exception as e:  # serving metrics are best-effort extras
            extras["serving_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "decode_2k"):
        try:
            extras["decode_2k"] = decode_span_bench(on_tpu)
        except Exception as e:
            extras["decode_2k_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "spec_decode"):
        try:
            extras["spec_decode"] = spec_decode_bench(on_tpu)
        except Exception as e:
            extras["spec_decode_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "mfu_8b_layer"):
        try:
            extras["mfu_8b_layer"] = mfu_8b_layer_bench(on_tpu)
        except Exception as e:
            extras["mfu_8b_layer_error"] = f"{type(e).__name__}: {e}"
    if on_tpu:
        if serving_8b is not None:
            extras["serving_8b"] = serving_8b
        else:
            extras["serving_8b_error"] = serving_8b_err
    elif _budget_gate(extras, budget, "serving_8b"):
        try:
            extras["serving_8b"] = serving_8b_bench(on_tpu)
        except Exception as e:
            extras["serving_8b_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_scenarios"):
        try:
            extras["serving_scenarios"] = serving_scenarios_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_scenarios_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "rl_anakin"):
        try:
            extras["rl_anakin"] = rl_anakin_bench(on_tpu)
        except Exception as e:
            extras["rl_anakin_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_chaos"):
        try:
            extras["serving_chaos"] = serving_chaos_bench(on_tpu, budget)
        except Exception as e:
            extras["serving_chaos_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_prefix_cache"):
        try:
            extras["serving_prefix_cache"] = serving_prefix_cache_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_prefix_cache_error"] = \
                f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_disagg"):
        try:
            extras["serving_disagg"] = serving_disagg_bench(on_tpu, budget)
        except Exception as e:
            extras["serving_disagg_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_multichip"):
        try:
            extras["serving_multichip"] = serving_multichip_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_multichip_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_kernels"):
        try:
            extras["serving_kernels"] = serving_kernels_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_kernels_error"] = f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_prefill_kernels"):
        try:
            extras["serving_prefill_kernels"] = \
                serving_prefill_kernels_bench(on_tpu, budget)
        except Exception as e:
            extras["serving_prefill_kernels_error"] = \
                f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_observability"):
        try:
            extras["serving_observability"] = serving_observability_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_observability_error"] = \
                f"{type(e).__name__}: {e}"
    if _budget_gate(extras, budget, "serving_paged_kv"):
        try:
            extras["serving_paged_kv"] = serving_paged_kv_bench(
                on_tpu, budget)
        except Exception as e:
            extras["serving_paged_kv_error"] = f"{type(e).__name__}: {e}"
    extras["budget"] = {"total_s": budget.total_s,
                        "used_s": round(budget.elapsed(), 1),
                        "env": BUDGET_ENV}
    # every dict-valued section carries the LIVE runtime it ran under
    # (CPU-vs-TPU records become self-describing: a reader never has to
    # guess whether a number is a CPU smoke or a hardware claim).
    # Sections computed in a subprocess (serving_8b, serving_multichip)
    # self-stamp with THEIR runtime — the loop only fills the gaps.
    stamp = _runtime_stamp()
    extras["runtime"] = stamp
    for key, section in extras.items():
        if (isinstance(section, dict) and key != "runtime"
                and "runtime" not in section):
            section["runtime"] = stamp
    headline = {
        "metric": "llama_train_mfu",
        "value": round(achieved_mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(achieved_mfu / 0.40, 4),
    }
    # the decode-step attribution rides the headline so the driver's
    # last-2000-bytes stdout capture carries the per-bucket breakdown
    bd = (extras.get("serving_8b") or {}).get("decode_breakdown") or {}
    if bd.get("buckets_ms"):
        headline["decode_breakdown_ms"] = {
            k: v for k, v in bd["buckets_ms"].items() if v is not None}
    # Full record -> committed file; stdout gets a compact headline ONLY,
    # as the LAST line (driver keeps the last ~2000 bytes of stdout).
    # Off-TPU smoke runs write a temp path instead: toy-CPU numbers must
    # never clobber the committed TPU record the floor gate validates.
    extras_path = (os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_EXTRAS.json") if on_tpu
                   else os.path.join(tempfile.gettempdir(),
                                     "BENCH_EXTRAS.cpu.json"))
    with open(extras_path, "w") as f:
        # schema 2 = the record carries serving_scenarios; schema 3 adds
        # rl_anakin; schema 4 adds serving_chaos; schema 5 adds
        # serving_prefix_cache; schema 6 adds the HTTP-path chaos
        # measurement (serving_chaos.http — real socket clients);
        # schema 7 adds serving_disagg (colocated-vs-disaggregated on
        # the pinned diurnal_burst trace); schema 8 adds
        # serving_multichip (tp×pp stage-sharded decode parity + bubble
        # accounting) and the per-section runtime stamps; schema 9 adds
        # serving_kernels (the xla-vs-flash decode-kernel A/B with its
        # exact parity contract); schema 10 adds serving_observability
        # (the tracing-on-vs-off A/B: byte parity under sampled traces
        # + bounded TPOT overhead + the SLO-burn summary `--check`
        # prints); schema 11 adds serving_paged_kv (the slab-vs-paged
        # equal-KV-bytes A/B on the long_tail_mix trace: byte parity
        # incl. forced eviction + oversubscription, peak in-flight
        # streams, goodput-per-GiB-of-KV); schema 12 adds
        # serving_prefill_kernels (the xla-vs-flash chunked-PREFILL A/B
        # with its exact parity contract across slab + paged engines)
        # and the serving_multichip `overlap` re-measure (the same
        # layouts under the overlapped wavefront schedule: parity +
        # bubble-not-worse). The floor gate only demands a
        # section's metrics from records new enough to know about it
        # (older committed records stay valid under --check; `--check`
        # lists which floors a record's schema gates out).
        json.dump({"schema": 12, "headline": headline, "extras": extras},
                  f, indent=1)
        f.write("\n")
    failures = check_floors(extras_path) if on_tpu else []
    _print_tail(headline, extras_path, on_tpu, failures)


def _print_tail(headline: dict, extras_path: str, on_tpu: bool,
                failures: list[str]) -> None:
    """The bench's stdout contract: optional floor-failure line, then the
    compact headline as the LAST line — in that order, always (the driver
    records only the tail of stdout)."""
    if failures:
        print(json.dumps({"floor_failures": failures}))
    print(json.dumps(dict(headline,
                          extras_file=os.path.basename(extras_path)
                          if on_tpu else extras_path,
                          floors="fail" if failures else "pass")))


# Perf floor gate (VERDICT r4 ask #5): committed floors that fail loudly at
# build time when a feature lands a regression. Floors are set a few percent
# under the round-5 measured numbers (headroom for run-to-run noise), not at
# the aspirational targets; raise them as the measured numbers climb.
PERF_FLOORS = {
    "headline_mfu": 0.60,                    # r4: 0.629 (proxy headline)
    "mfu_8b_layer": 0.68,                    # r5: 0.7395 no-remat b8
    # (r4: 0.5833 with full remat); sweep record in scripts/mfu8b_sweep.py
    "mfu_8b_2layer": 0.60,                   # r5: 0.6544 2-layer scan
    "decode_2k_speedup": 0.95,               # r5: ~1.09; span reads are
    # ~free after the grouped-attention rewrite (span 2048 ≈ span 256 at
    # 8B), so the span-vs-full ratio is structurally ~1 and the floor
    # (with run-to-run noise margin) guards against the span path ever
    # being materially SLOWER than full-cache
    "spec_full_tok_per_s": 2000.0,           # r5: 2131 in-bench, 2528 in a
    # standalone run (r3 2247, r4 regressed to 1571 — the junk-chunk bug
    # this floor exists to catch)
    "serving_saturation_tok_per_s": 275.0,   # r4: 285.8
    "serving_8b_decode_tok_per_s": 950.0,    # r5: 1029 plain at 32 slots
    # (r4: 392.8 at 16; the grouped-attention rewrite + 32-slot cache)
    "serving_8b_spec_tok_per_s": 1400.0,     # r5: 1570 at 32 slots,
    # 3 drafts, acceptance 1.95 (r4-era path: 254)
    # loadgen scenario suite (r7): enforced only on schema>=2 records
    # (older committed records predate the section). Conservative sanity
    # floor — the steady scenario offers ~3 req/s against an engine with
    # hundreds of tok/s of capacity and a 2 s TTFT SLO; raise toward the
    # measured number once the first green hardware run lands.
    "scenario_steady_slo_attainment": 0.5,
    # rl_anakin (r8): enforced only on schema>=3 records. Conservative —
    # the fused Anakin step sustains ~100k env-steps/s on the 1-core CPU
    # box at B=64×T=32; a TPU at B=2048×T=64 clears this by orders of
    # magnitude. Raise to just under the measured number once the first
    # hardware record lands.
    "rl_anakin_env_steps_per_s": 100_000.0,
    # serving_chaos (r9): enforced only on schema>=4 records.
    # terminal_frac is the zero-lost-request INVARIANT — every accepted
    # request reaches a terminal state even through a mid-stream backend
    # crash — so its floor is exactly 1.0 (a deterministic contract, not
    # a perf number with noise headroom).
    "chaos_crash_terminal_frac": 1.0,
    # conservative: a crash mid-window costs the restart — INCLUDING a
    # full program-menu warmup, which at d1024 is a large slice of the
    # 30 s steady window — plus replayed decode work. The floor only
    # guards against total collapse (zero goodput under fault); raise it
    # once the first hardware record lands.
    "chaos_crash_goodput_retained": 0.02,
    # serving_chaos.http (r11): enforced only on schema>=6 records.
    # stream_completion_frac is the streaming zero-duplicate/zero-lost
    # CONTRACT measured at a real socket — every SSE stream through a
    # mid-window engine crash delivers a complete response byte-identical
    # to the uncrashed run, with exactly one [DONE] and one usage object —
    # so its floor is exactly 1.0 (deterministic, no noise headroom).
    "chaos_http_stream_completion": 1.0,
    # conservative, same rationale as chaos_crash_goodput_retained: the
    # crash costs restart backoff (+ full rewarm on TPU) measured at the
    # socket; the floor only guards against total collapse.
    "chaos_http_goodput_retained": 0.02,
    # serving_prefix_cache (r10): enforced only on schema>=5 records.
    # The shared_prefix_chat scenario is built so that most admissions
    # extend a cached chain (turn >= 2 always should; turn-1 hits ride
    # template popularity), so a hit rate under 0.5 means the radix
    # path broke, not that traffic got unlucky.
    "prefix_cache_hit_rate": 0.5,
    # fraction of offered prefill tokens served from reused KV
    # (saved / (saved + computed)); conservative — the scenario's
    # template-to-turn ratio puts the expected value well above this.
    "prefix_prefill_saved_frac": 0.2,
    # EXACT contract, not a perf number: greedy tokens through the
    # cached path must be byte-identical to the cold engine's.
    "prefix_greedy_parity": 1.0,
    # serving_disagg (r12): enforced only on schema>=7 records.
    # THE acceptance product (ISSUE 13): disagg must beat colocated on
    # TTFT p99 at equal-or-better decode throughput on the identical
    # pinned diurnal_burst trace — (col_ttft_p99/dis_ttft_p99) ×
    # (dis_tok_per_s/col_tok_per_s) >= 1.0, the "done when" criterion
    # as a floor, not a claim.
    "disagg_ttft_x_decode_gain": 1.0,
    # EXACT contract: greedy/seeded tokens through the prefill→handoff→
    # decode pipeline must be byte-identical to the colocated engine's.
    "disagg_greedy_parity": 1.0,
    # EXACT contract: the zero-lost invariant under a prefill-worker
    # crash mid-trace (every accepted request reaches a terminal state).
    "disagg_crash_terminal_frac": 1.0,
    # serving_kernels (r14): enforced only on schema>=9 records.
    # EXACT contract, not a perf number: greedy AND seeded tokens
    # through the Pallas flash-decode kernel (int8 KV, chunked prefill,
    # prefix-cache hit, speculative verify) must be byte-identical to
    # the XLA einsum path's on the same warmed-engine construction.
    # The SPEEDUP stays a recorded number, not a floor — the CPU smoke
    # runs the kernel in interpret mode, so the gain claim awaits the
    # open-item-#1 TPU record (the established convention).
    "kernel_greedy_parity": 1.0,
    # serving_multichip (r13): enforced only on schema>=8 records.
    # EXACT contract, not a perf number: greedy tokens through the
    # tp×pp stage-sharded engine (per-stage params/KV slabs,
    # microbatched MPMD decode, int8 KV + chunked prefill +
    # prefix-cache ON) must be byte-identical to the single-program
    # engine's on the identical pinned trace. The multichip TTFT/TPOT
    # gain itself is recorded, not floored — meaningful only on the
    # first on-TPU record (ROADMAP open item #1).
    "multichip_greedy_parity": 1.0,
    # serving_observability (r16): enforced only on schema>=10 records.
    # EXACT contract: greedy tokens with every request carrying a
    # SAMPLED trace id must be byte-identical to the untraced engine's
    # — telemetry reads timestamps, it never touches the dataplane.
    "obs_greedy_parity": 1.0,
    # bounded-overhead contract: tpot_p50(tracing off)/tpot_p50(on) on
    # the identical byte-pinned replay. 0.95 = at most ~5% TPOT cost —
    # generous on CPU-smoke noise at toy dims, and the retrospective-
    # span design (aggregate counters only in the decode loop, spans
    # minted once per request at finish) should hold it trivially.
    "obs_tpot_overhead_ratio": 0.95,
    # serving_paged_kv (r17): enforced only on schema>=11 records.
    # EXACT contract, not a perf number: greedy AND seeded tokens
    # through the paged engine (block-table KV, radix-owned pool) must
    # be byte-identical to the slab engine's — including recompute-
    # from-prefix after a forced full eviction and an oversubscribed
    # burst where admission holds + retries through eviction. All-or-
    # nothing product, floor exactly 1.0.
    "paged_greedy_parity": 1.0,
    # THE acceptance product (ISSUE 19): at EQUAL KV bytes (paged pool
    # = the slab engine's token budget, +1 trash block) the paged
    # engine at 4S slots must hold 4x the slab engine's peak in-flight
    # streams on the heavy-tailed long_tail_mix trace. Both engines
    # saturate their slot tables under the pinned offered load, so the
    # ratio is structurally 4S/S — the floor guards the admission path
    # ever failing to fund what the freed tail bytes can hold.
    "paged_concurrency_gain": 4.0,
    # serving_prefill_kernels (r20): enforced only on schema>=12
    # records. EXACT contract, not a perf number: greedy AND seeded
    # tokens through the Pallas chunked-prefill kernel (int8 KV, cold +
    # prefix-cache hit + chunked prompts, slab AND paged block-table
    # engines) must be byte-identical to the XLA einsum prefill's on
    # the same warmed-engine construction. The TTFT gain stays a
    # recorded number, not a floor — the CPU smoke runs the kernel in
    # interpret mode (the serving_kernels convention).
    "prefill_kernel_greedy_parity": 1.0,
    # serving_multichip.overlap (r20): enforced only on schema>=12
    # records. EXACT contract: the overlapped wavefront schedule is a
    # dispatch reordering — greedy tokens through every overlapped
    # layout must be byte-identical to the single-program engine's.
    "multichip_overlap_parity": 1.0,
    # the bubble half of the ISSUE 20 acceptance: the overlapped
    # schedule's measured pipeline_bubble_frac must be no worse than
    # the same run's sync accounting (the r13 record sat at 0.72 sync)
    # — committed as a boolean product so the floor is exact.
    "overlap_bubble_not_worse": 1.0,
}

#: floor name → the record schema that introduced it (names absent here
#: are schema-1 originals). ONE table drives both check_floors' gating
#: and --check's "which floors does this old record not know about"
#: report, so the two can never drift.
SCHEMA_GATES = {
    "scenario_steady_slo_attainment": 2,
    "rl_anakin_env_steps_per_s": 3,
    "chaos_crash_terminal_frac": 4,
    "chaos_crash_goodput_retained": 4,
    "prefix_cache_hit_rate": 5,
    "prefix_prefill_saved_frac": 5,
    "prefix_greedy_parity": 5,
    "chaos_http_stream_completion": 6,
    "chaos_http_goodput_retained": 6,
    "disagg_ttft_x_decode_gain": 7,
    "disagg_greedy_parity": 7,
    "disagg_crash_terminal_frac": 7,
    "multichip_greedy_parity": 8,
    "kernel_greedy_parity": 9,
    "obs_greedy_parity": 10,
    "obs_tpot_overhead_ratio": 10,
    "paged_greedy_parity": 11,
    "paged_concurrency_gain": 11,
    "prefill_kernel_greedy_parity": 12,
    "multichip_overlap_parity": 12,
    "overlap_bubble_not_worse": 12,
}


def gated_out_floors(path: str) -> list[str]:
    """Floor names a record's schema gates OUT (the record predates the
    section, so --check does not demand it). Printed by `--check` so an
    old committed record says explicitly which contracts it is NOT
    attesting, instead of silently passing."""
    with open(path) as f:
        schema = json.load(f).get("schema", 1)
    return sorted(n for n, s in SCHEMA_GATES.items() if schema < s)


def slo_burn_summary(path: str) -> dict | None:
    """The SLO-burn view of a committed record (ISSUE 17 satellite):
    the serving_observability section's per-tenant attainment /
    error-budget burn, reduced to the two numbers an operator pages on
    — aggregate burn rate and the worst-burning tenant. None when the
    record predates schema 10 (gated_out_floors already says so)."""
    with open(path) as f:
        rec = json.load(f)
    burn = ((rec.get("extras") or {})
            .get("serving_observability") or {}).get("slo_burn")
    if not burn:
        return None
    tenants = burn.get("tenants") or {}
    worst = max(tenants, key=lambda t: tenants[t]["burn_rate"],
                default=None)
    return {
        "window_s": burn.get("window_s"),
        "slo": burn.get("slo"),
        "aggregate": burn.get("aggregate"),
        "worst_tenant": ({"tenant": worst, **tenants[worst]}
                         if worst is not None else None),
        "n_tenants": len(tenants),
    }


def check_floors(path: str) -> list[str]:
    """Assert the recorded bench extras against PERF_FLOORS. Returns a list
    of human-readable failures (empty = all floors hold). Reads the file
    written by main() so the gate can run without TPU hardware
    (tests/test_perf_floors.py runs it in the slow lane against the
    committed record)."""
    with open(path) as f:
        rec = json.load(f)
    ex = rec["extras"]

    def get(d, *ks):
        for k in ks:
            if not isinstance(d, dict) or k not in d:
                return None
            d = d[k]
        return d

    def as_frac(v):
        # exact-contract booleans (parity fields) compare as 1.0/0.0
        return None if v is None else float(v)

    # every floor's extraction, unconditional; SCHEMA_GATES alone
    # decides which apply to this record (a schema'd floor missing from
    # a new-enough record IS a failure — the honest default;
    # skipped_for_budget says why)
    checks = [
        ("headline_mfu", rec["headline"]["value"]),
        ("mfu_8b_layer", get(ex, "mfu_8b_layer", "mfu")),
        ("mfu_8b_2layer", get(ex, "mfu_8b_layer", "x2_scan", "mfu")),
        ("decode_2k_speedup", get(ex, "decode_2k", "speedup")),
        ("spec_full_tok_per_s",
         get(ex, "spec_decode", "full_acceptance", "tok_per_s_spec")),
        ("serving_saturation_tok_per_s",
         get(ex, "serving_saturation_tok_per_s")),
        ("serving_8b_decode_tok_per_s",
         get(ex, "serving_8b", "decode_tok_per_s")),
        ("serving_8b_spec_tok_per_s",
         get(ex, "serving_8b", "spec", "decode_tok_per_s")),
        ("scenario_steady_slo_attainment",
         get(ex, "serving_scenarios", "steady", "aggregate",
             "slo_attainment")),
        ("rl_anakin_env_steps_per_s",
         get(ex, "rl_anakin", "env_steps_per_s")),
        ("chaos_crash_terminal_frac",
         get(ex, "serving_chaos", "crash_midstream", "terminal_frac")),
        ("chaos_crash_goodput_retained",
         get(ex, "serving_chaos", "crash_midstream",
             "goodput_retained")),
        ("chaos_http_stream_completion",
         get(ex, "serving_chaos", "http", "stream_completion_frac")),
        ("chaos_http_goodput_retained",
         get(ex, "serving_chaos", "http", "goodput_retained")),
        ("disagg_ttft_x_decode_gain",
         get(ex, "serving_disagg", "ttft_x_decode_gain")),
        ("disagg_greedy_parity",
         as_frac(get(ex, "serving_disagg", "greedy_parity"))),
        ("disagg_crash_terminal_frac",
         get(ex, "serving_disagg", "crash", "terminal_frac")),
        ("prefix_cache_hit_rate",
         get(ex, "serving_prefix_cache", "hit_rate")),
        ("prefix_prefill_saved_frac",
         get(ex, "serving_prefix_cache", "prefill_saved_frac")),
        ("prefix_greedy_parity",
         as_frac(get(ex, "serving_prefix_cache", "greedy_parity"))),
        ("multichip_greedy_parity",
         as_frac(get(ex, "serving_multichip", "greedy_parity"))),
        ("kernel_greedy_parity",
         as_frac(get(ex, "serving_kernels", "kernel_greedy_parity"))),
        ("obs_greedy_parity",
         as_frac(get(ex, "serving_observability", "obs_greedy_parity"))),
        ("obs_tpot_overhead_ratio",
         get(ex, "serving_observability", "obs_tpot_overhead_ratio")),
        ("paged_greedy_parity",
         as_frac(get(ex, "serving_paged_kv", "paged_greedy_parity"))),
        ("paged_concurrency_gain",
         get(ex, "serving_paged_kv", "concurrency_gain")),
        ("prefill_kernel_greedy_parity",
         as_frac(get(ex, "serving_prefill_kernels",
                     "prefill_kernel_greedy_parity"))),
        ("multichip_overlap_parity",
         as_frac(get(ex, "serving_multichip", "overlap",
                     "greedy_parity"))),
        ("overlap_bubble_not_worse",
         as_frac(get(ex, "serving_multichip", "overlap",
                     "bubble_not_worse"))),
    ]
    schema = rec.get("schema", 1)
    failures = []
    for name, got in checks:
        if schema < SCHEMA_GATES.get(name, 1):
            continue   # record predates the floor — gated out (listed
            # by gated_out_floors / --check, never silently dropped)
        floor = PERF_FLOORS[name]
        if got is None:
            failures.append(f"{name}: missing from record (floor {floor})")
        elif got < floor:
            failures.append(f"{name}: {got} < floor {floor}")
    return failures


def longctx_bench(on_tpu: bool) -> dict:
    """Long-context points (SURVEY §5.7 design scale, VERDICT r2 missing
    #2, r4 ask #9): the proxy model at seq 8192 — plus 16384 and 32768
    (full remat, small batch: the configs that survive the activation
    wall) — with the Pallas flash kernel and its seq-adaptive blocks.
    Multi-chip long-context (ring over the sequence axis) is proven by
    the parity tests and dryrun_multichip; this records single-chip MFU
    per sequence length. The top-level keys stay the 8k point (r2-r4
    continuity); longer lengths nest under seq16384/seq32768."""
    out = _longctx_point(8192 if on_tpu else 512, on_tpu,
                         (("minimal", 2), ("minimal", 1), ("full", 4),
                          ("full", 2), ("full", 1)))
    if on_tpu:
        for seq, ce_chunk in ((16384, 0), (32768, 4096)):
            # at 32k the [1, S, 32000] f32 logits alone are ~4 GiB x
            # several live copies — the chunked-CE path (llama.ce_chunk)
            # is what fits it on one chip
            try:
                out[f"seq{seq}"] = _longctx_point(
                    seq, on_tpu, (("minimal", 1), ("full", 2), ("full", 1)),
                    ce_chunk=ce_chunk)
            except Exception as e:
                out[f"seq{seq}_error"] = f"{type(e).__name__}: {e}"
    return out


def _longctx_point(seq: int, on_tpu: bool, ladder, ce_chunk: int = 0) -> dict:
    base = dict(
        vocab_size=32000, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=7168, max_seq_len=seq, remat=True, remat_policy="minimal",
        attention_impl="flash", scan_layers=False, ce_chunk=ce_chunk,
    ) if on_tpu else dict(
        vocab_size=512, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=seq, attention_impl="flash",
    )
    def attempt(policy: str, batch: int) -> dict:
        # own frame per attempt: on OOM the frame dies with the except
        # block below, releasing this attempt's state (a stored traceback
        # would pin ~10G of HBM and starve every later attempt/extra)
        trainer = Trainer(TrainerConfig(
            model="llama", model_overrides=dict(base, remat_policy=policy),
            batch_size=batch,
            optimizer=OptimizerConfig(warmup_steps=10, total_steps=1000,
                                      mu_dtype="bfloat16" if on_tpu
                                      else None),
            mesh=MeshConfig(data=-1), log_every=1000))
        trainer.metrics.echo = False
        data = data_lib.for_model("llama", trainer.model_cfg, batch,
                                  seq_len=seq)
        state = trainer.init_state()
        b0 = trainer.shard_batch(next(data))
        step_fn = trainer.compiled_step(state, b0)
        for _ in range(2):
            state, metrics = step_fn(state, b0)
        float(metrics["loss"])  # sync: a fetched value is a finished step
        n_meas = 5
        t0 = time.perf_counter()
        for _ in range(n_meas):
            state, metrics = step_fn(state, b0)
        assert float(metrics["loss"]) == float(metrics["loss"])
        dt = (time.perf_counter() - t0) / n_meas
        tokens = batch * seq
        flops = llama.flops_per_token(trainer.model_cfg, seq) * tokens
        return {
            "seq_len": seq, "batch": batch,
            "mfu": round(mfu(flops, dt, 1), 4),
            "tokens_per_sec_per_chip": round(tokens / dt, 1),
            "step_time_s": round(dt, 4),
            "attention": "pallas-flash", "remat": policy,
            **({"ce_chunk": ce_chunk} if ce_chunk else {}),
        }

    last_msg = "no config attempted"
    # long-seq activations are the constraint: walk down from the fastest
    # config (minimal remat) to the one that fits (full recompute, batch 1)
    for policy, batch in (ladder if on_tpu else (("minimal", 2),)):
        try:
            return attempt(policy, batch)
        except Exception as e:  # OOM at this batch: try the smaller one
            last_msg = f"{type(e).__name__}: {e}"  # message only, no frames
    raise RuntimeError(last_msg)


def decode_span_bench(on_tpu: bool) -> dict:
    """Length-aware decode at a 2k-context cache (VERDICT r2 missing #4):
    short live lengths in a max_len=2048 cache decode against a 128-row
    attention span instead of all 2048 — the HBM-read lever. Same engine,
    same requests, span picking ON vs forced full-cache."""
    from kubeflow_tpu.serving.llm import LLMEngine

    cfg = llama.LlamaConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=3584, max_seq_len=2048, remat=False,
    ) if on_tpu else llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    max_len = 2048 if on_tpu else 64
    prompt = list(range(1, 100)) if on_tpu else [3, 7, 11]
    new_tokens = 64 if on_tpu else 8

    n_slots = 16 if on_tpu else 2
    decode_chunk = 64 if on_tpu else 8

    def run(engine) -> float:
        rids = [engine.submit(prompt, new_tokens) for _ in range(n_slots)]
        t0 = time.perf_counter()
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        assert all(engine.is_done(r) for r in rids)
        for r in rids:
            engine.release(r)
        return n_slots * new_tokens / dt

    def build(**kw):
        e = LLMEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                      buckets=(128,) if on_tpu else (16,),
                      decode_chunk=decode_chunk, **kw)
        e.warmup()
        return e

    # closure-free span override: a lambda capturing the engine (or a saved
    # bound method) would keep its whole KV cache alive past the `del`
    force_full = lambda needed, ml=max_len: ml  # noqa: E731

    engine = build()
    span_tps = run(engine)
    engine._pick_span = force_full  # r2 behavior
    full_tps = run(engine)
    del engine
    # int8 KV at FULL span: isolates the cache-read halving (span already
    # removed most KV reads, so the int8 win shows against the full scan)
    q_engine = build(kv_quantize="int8")
    q_engine._pick_span = force_full
    int8_full_tps = run(q_engine)
    del q_engine
    return {
        "max_len": max_len, "n_req": n_slots, "new_tokens": new_tokens,
        "decode_chunk": decode_chunk,
        "tok_per_s_span": round(span_tps, 1),
        "tok_per_s_full_cache": round(full_tps, 1),
        "tok_per_s_full_cache_int8kv": round(int8_full_tps, 1),
        "speedup": round(span_tps / full_tps, 2),
        "int8kv_speedup_at_full": round(int8_full_tps / full_tps, 2),
    }


def spec_decode_bench(on_tpu: bool) -> dict:
    """Speculative decoding, TWO operating points from one training run:

    - `full_acceptance`: the model trained to near-zero loss on a
      repeating 64-gram, serving that same text — the best case by
      construction (copy-heavy/low-entropy serving), kept for r2/r3
      continuity.
    - `realistic` (VERDICT r3 ask #4): the SAME model at a PARTIAL
      training snapshot (loss well above zero) serving the same prompt —
      its greedy continuations only locally match the prompt-lookup
      drafts, so acceptance sits materially below k+1 and the speedup
      shows what mixed-predictability text actually gets.

    Greedy outputs are byte-identical spec-vs-plain at BOTH points
    (exactness is the tested contract, tests/test_spec_decode.py)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.serving.llm import LLMEngine

    cfg = llama.LlamaConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=3584, max_seq_len=1024, remat=False,
    ) if on_tpu else llama.LlamaConfig.tiny()
    seq = 256 if on_tpu else 64
    rng = np.random.default_rng(0)
    base = rng.integers(0, cfg.vocab_size, size=(64,)).astype("int32")
    tokens = jnp.asarray(np.tile(base, ((4 * seq) // 64 + 1))[: 4 * seq]
                         .reshape(4, seq))
    params = llama.init(jax.random.key(0), cfg)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state):
        (loss, _), grads = jax.value_and_grad(
            llama.loss_fn, has_aux=True)(params, {"tokens": tokens}, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def greedy_acc(p):
        logits = llama.apply(p, tokens, cfg)[:, :-1]
        return jnp.mean(jnp.argmax(logits, -1) == tokens[:, 1:])

    total_steps = 150 if on_tpu else 120
    loss = None
    partial_at, partial_acc, partial_loss = 0, 0.0, 0.0
    params_partial = fallback = None
    for i in range(total_steps):
        params, opt_state, loss = train_step(params, opt_state)
        if params_partial is None:
            # adaptive snapshot keyed on ARGMAX accuracy, not loss: Adam
            # drives argmax-perfect prediction while the loss is still
            # ~0.7 (measured), so a loss/step-index rule lands at full
            # acceptance and the "realistic" point degenerates. The first
            # step predicting 55-92% of tokens is the mixed regime —
            # drafts accept in runs and reject at the mispredictions.
            a = float(greedy_acc(params))
            if a < 0.92:
                fallback = (jax.tree.map(lambda x: x + 0, params), a,
                            float(loss), i + 1)
            if 0.55 <= a <= 0.92:
                params_partial = jax.tree.map(lambda x: x + 0, params)
                partial_acc, partial_loss = a, float(loss)
                partial_at = i + 1
    if params_partial is None:   # curve jumped over the band: last <0.92
        params_partial, partial_acc, partial_loss, partial_at = fallback
    loss = float(loss)
    del opt_state, fallback

    n_slots = 8 if on_tpu else 2
    new_tokens = 96 if on_tpu else 16
    prompt = list(np.tile(base, 3))[: (160 if on_tpu else 24)]
    kw = dict(n_slots=n_slots, max_len=1024 if on_tpu else 64,
              buckets=(256,) if on_tpu else (32,), decode_chunk=8)

    def run(engine):
        rids = [engine.submit(prompt, new_tokens) for _ in range(n_slots)]
        t0 = time.perf_counter()
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        outs = [engine.result(r) for r in rids]
        for r in rids:
            engine.release(r)
        return n_slots * new_tokens / dt, outs

    def point(p):
        plain = LLMEngine(p, cfg, **kw)
        plain.warmup()
        plain_tps, plain_out = run(plain)
        del plain
        spec = LLMEngine(p, cfg, speculative=6, spec_ngram=3, **kw)
        spec.warmup()
        spec_tps, spec_out = run(spec)
        tokens_per_round = spec.metrics()["spec_tokens_per_round"]
        del spec
        assert spec_out == plain_out, \
            "speculative output diverged from greedy"
        return {
            "n_req": n_slots, "new_tokens": new_tokens,
            "tok_per_s_plain": round(plain_tps, 1),
            "tok_per_s_spec": round(spec_tps, 1),
            "speedup": round(spec_tps / plain_tps, 2),
            "spec_tokens_per_round": tokens_per_round,
            "drafts_per_round": 6,
        }

    full = dict(point(params), train_loss=round(loss, 4))
    realistic = dict(point(params_partial),
                     train_loss=round(partial_loss, 4),
                     greedy_train_acc=round(partial_acc, 3),
                     note=(f"partial snapshot at step {partial_at}/"
                           f"{total_steps} (first step with 55-92% argmax "
                           "accuracy): greedy continuations only locally "
                           "match the drafts"))
    del params, params_partial
    try:
        heldout = _spec_heldout_point(cfg, kw, n_slots, new_tokens, on_tpu)
    except Exception as e:   # best-effort extra, like the other sections
        heldout = {"error": f"{type(e).__name__}: {e}"}
    # top-level keys mirror the r3 full-acceptance point for continuity
    return dict(full, full_acceptance=full, realistic=realistic,
                heldout=heldout)


def _spec_heldout_point(cfg, kw, n_slots, new_tokens, on_tpu) -> dict:
    """Held-out spec-decode evidence (VERDICT r4 ask #7): the full and
    realistic points serve the TEXT THE MODEL WAS TRAINED ON; this one
    trains on walks of an order-2 Markov process (modal successor with
    p=0.85, uniform otherwise) and serves FRESH walks from a different
    seed — the exact token sequences were never in training, so
    acceptance can only come from the model having LEARNED the process's
    structure (greedy = modal branch) meeting prompt-lookup drafts where
    the held-out walk happened to take the modal branch. Expected
    acceptance sits between the extremes, completing the
    full / realistic / heldout story."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.serving.llm import LLMEngine

    alphabet, p_modal = 64, 0.85
    table_rng = np.random.default_rng(7)
    modal = table_rng.integers(1, alphabet + 1,
                               size=(alphabet + 1, alphabet + 1))

    def walk(r, n):
        out = [int(r.integers(1, alphabet + 1)),
               int(r.integers(1, alphabet + 1))]
        for _ in range(n - 2):
            a, b = out[-2], out[-1]
            out.append(int(modal[a, b]) if r.random() < p_modal
                       else int(r.integers(1, alphabet + 1)))
        return out

    seq = 256 if on_tpu else 64
    batch = 4
    steps = 240 if on_tpu else 30
    train_rng = np.random.default_rng(11)      # training walks: seed A
    batches = [jnp.asarray([walk(train_rng, seq) for _ in range(batch)],
                           jnp.int32) for _ in range(steps)]
    params = llama.init(jax.random.key(2), cfg)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, toks):
        (l, _), grads = jax.value_and_grad(
            llama.loss_fn, has_aux=True)(params, {"tokens": toks}, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, l

    for toks in batches:
        params, opt_state, train_l = train_step(params, opt_state, toks)
    train_l = float(train_l)
    del opt_state, batches

    heldout_rng = np.random.default_rng(1234)  # serving walks: seed B
    prompts = [walk(heldout_rng, 160 if on_tpu else 24)
               for _ in range(n_slots)]

    def run(engine):
        rids = [engine.submit(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        outs = [engine.result(r) for r in rids]
        for r in rids:
            engine.release(r)
        return n_slots * new_tokens / dt, outs

    plain = LLMEngine(params, cfg, **kw)
    plain.warmup()
    plain_tps, plain_out = run(plain)
    del plain
    spec = LLMEngine(params, cfg, speculative=6, spec_ngram=3, **kw)
    spec.warmup()
    spec_tps, spec_out = run(spec)
    acc = spec.metrics()["spec_tokens_per_round"]
    del spec, params
    assert spec_out == plain_out, "heldout spec diverged from greedy"
    return {
        "n_req": n_slots, "new_tokens": new_tokens,
        "tok_per_s_plain": round(plain_tps, 1),
        "tok_per_s_spec": round(spec_tps, 1),
        "speedup": round(spec_tps / plain_tps, 2),
        "spec_tokens_per_round": acc,
        "drafts_per_round": 6,
        "train_loss": round(train_l, 4),
        "process": (f"order-2 markov, alphabet {alphabet}, modal "
                    f"p={p_modal}; trained on seed-11 walks, served "
                    "seed-1234 walks (unseen continuations)"),
    }


def mfu_8b_layer_bench(on_tpu: bool) -> dict:
    """Measured train MFU at the CONTRACT geometry (VERDICT r3 ask #2, r4
    ask #3): true-dims Llama-3-8B layers (d4096/ff14336, GQA 32/8) at seq
    8192 with the Pallas flash kernel, fwd+bwd+SGD in a loop on the chip,
    at the config scripts/mfu8b_sweep.py found fastest — NO remat at the
    largest batch that fits (one bf16 layer + SGD leaves the 16G chip room
    for b8 activations; skipping the bwd recompute is worth ~15 MFU pts:
    sweep measured none/b8 0.7395, minimal/b8 0.6678, full/b8 0.5943).
    Reports the single-layer point plus a 2-LAYER lax.scan variant
    (sweep: none/b2 0.6544) so inter-layer residual-stacking and scan
    overheads are inside the number. Same FLOPs convention as the headline
    (llama.flops_per_token: 6N + 12·L·H·S); the vocab-256 head makes the
    embed/lm_head term negligible, so these are effectively LAYER MFU."""
    import jax.numpy as jnp

    from kubeflow_tpu.training.mfu import mfu as mfu_fn

    seq = 8192 if on_tpu else 512
    rng = jax.random.key(0)

    def make_cfg(n_layers: int, scan: bool, policy: str):
        if not on_tpu:
            return llama.LlamaConfig.tiny()
        kw = dict(vocab_size=256, d_model=4096, n_layers=n_layers,
                  n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=seq,
                  attention_impl="flash", scan_layers=scan)
        if policy == "none":
            kw["remat"] = False
        else:
            kw.update(remat=True, remat_policy=policy)
        return llama.LlamaConfig(**kw)

    def attempt(cfg, batch: int) -> dict:
        params = llama.init(rng, cfg)
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                    cfg.vocab_size, jnp.int32)

        @jax.jit
        def step(p, toks):
            def loss(pp):
                return llama.loss_fn(pp, {"tokens": toks}, cfg)[0]
            l, g = jax.value_and_grad(loss)(p)
            return jax.tree.map(lambda w, gw: w - 1e-4 * gw.astype(w.dtype),
                                p, g), l

        for _ in range(2):
            params, l = step(params, tokens)
        float(l)   # sync: a fetched value is a finished step
        n_meas = 6
        t0 = time.perf_counter()
        for _ in range(n_meas):
            params, l = step(params, tokens)
        assert float(l) == float(l)
        dt = (time.perf_counter() - t0) / n_meas
        tokens_per_step = batch * seq
        flops = llama.flops_per_token(cfg, seq) * tokens_per_step
        return {
            "mfu": round(mfu_fn(flops, dt, 1), 4),
            "tokens_per_sec_per_chip": round(tokens_per_step / dt, 1),
            "step_time_s": round(dt, 4),
            "batch": batch, "seq_len": seq,
            "geometry": (f"d{cfg.d_model}/ff{cfg.d_ff} "
                         f"GQA{cfg.n_heads}:{cfg.n_kv_heads} "
                         f"x{cfg.n_layers} layer"),
            "remat": cfg.remat_policy if cfg.remat else "none",
            "scan_layers": cfg.scan_layers,
            "attention": cfg.attention_impl,
        }

    def best(n_layers: int, scan: bool, ladder) -> dict:
        """Walk the (policy, batch) ladder from the sweep's winner down to
        configs that always fit."""
        last = "no config attempted"
        for policy, batch in (ladder if on_tpu else (("minimal", 2),)):
            try:
                return attempt(make_cfg(n_layers, scan, policy), batch)
            except Exception as e:   # OOM: walk down
                last = f"{type(e).__name__}: {e}"
        raise RuntimeError(last)

    out = best(1, False, (("none", 8), ("none", 4), ("minimal", 8),
                          ("full", 4), ("full", 2)))
    try:
        out["x2_scan"] = best(2, True, (("none", 2), ("minimal", 4),
                                        ("full", 4), ("full", 2)))
    except Exception as e:
        out["x2_scan_error"] = f"{type(e).__name__}: {e}"
    return out


def _init_llama_int8_serving(cfg, seed: int = 0):
    """Random-init llama params DIRECTLY in the serving int8 layout, leaf
    by leaf on device — the f32 8B tree (~32 GiB) never exists anywhere.
    Layer payloads are generated as raw random bytes ([L, in, out] uint8 →
    bitcast int8, ~1 byte/param of HBM and no int32 temps); scales are the
    1/(127·sqrt(fan_in)) constant that makes activations O(1); embed is
    bf16 (it is a gather, never quantized — models/llama.quantize_params).
    Random weights are the perf-honest stand-in BASELINE #5 allows: the
    programs, layouts, and byte traffic are exactly the production ones."""
    import functools

    import jax.numpy as jnp

    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    nh, nkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    @functools.partial(jax.jit, static_argnames=("shape",))
    def rand_i8(key, shape):
        bits = jax.random.bits(key, shape, dtype=jnp.uint8)
        return jax.lax.bitcast_convert_type(bits, jnp.int8)

    def qleaf(key, shape):
        return {"q": rand_i8(key, shape),
                "s": jnp.full(shape[:-2] + (shape[-1],),
                              1.0 / (127.0 * shape[-2] ** 0.5),
                              jnp.float32)}

    keys = jax.random.split(jax.random.key(seed), 16)
    layer_shapes = {
        "wq": (L, d, nh * hd), "wk": (L, d, nkv * hd),
        "wv": (L, d, nkv * hd), "wo": (L, nh * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    layers = {name: qleaf(keys[i], shape)
              for i, (name, shape) in enumerate(layer_shapes.items())}
    layers["attn_norm"] = jnp.ones((L, d), jnp.float32)
    layers["mlp_norm"] = jnp.ones((L, d), jnp.float32)
    embed = (jax.jit(lambda k: jax.random.normal(
        k, (cfg.vocab_size, d), jnp.bfloat16) / (d ** 0.5))(keys[8]))
    return {"embed": embed, "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": qleaf(keys[9], (d, cfg.vocab_size))}


#: peak HBM bandwidth of the bench chip (TPU v5e: 819 GB/s) for the
#: roofline accounting below
HBM_GBPS = 819.0


#: the serving_8b child's -c program. A watchdog thread inside the child
#: makes it self-terminating: it exits when its deadline passes OR when
#: its parent dies (reparent detected via getppid change) — so even a
#: SIGKILLed bench parent cannot leave an 8B child starving the box
#: (BENCH_r05/MULTICHIP_r05 both died rc=124 to exactly that).
_SERVING_8B_CHILD_SRC = """\
import json, os, sys, threading, time
deadline = time.monotonic() + float(sys.argv[1])
ppid0 = os.getppid()
def _watchdog():
    while True:
        if time.monotonic() > deadline or os.getppid() != ppid0:
            os._exit(3)
        time.sleep(2.0)
threading.Thread(target=_watchdog, daemon=True).start()
import jax, bench
on = 'tpu' in str(jax.devices()[0].device_kind).lower()
out = bench.serving_8b_bench(True) if on else {'not_tpu': True}
print('RESULT ' + json.dumps(out))
"""


def _kill_process_group(proc, grace_s: float = 10.0) -> None:
    """SIGTERM the child's whole session, escalate to SIGKILL after a
    grace period (the child was started with start_new_session, so the
    group id is its pid)."""
    import signal
    import subprocess

    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=grace_s)
            return
        except subprocess.TimeoutExpired:
            continue


def _run_watchdogged(cmd: list[str], timeout_s: float, *,
                     cwd: str | None = None, extra_argv=()) -> tuple:
    """Run `cmd` in its own session with a hard parent-side deadline;
    returns (rc, stdout, stderr). On timeout the child's entire process
    group is killed (TERM, then KILL) and RuntimeError raises — no
    orphan survives either parent path."""
    import subprocess

    proc = subprocess.Popen(list(cmd) + [str(x) for x in extra_argv],
                            cwd=cwd, start_new_session=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _kill_process_group(proc)
        raise RuntimeError(
            f"child exceeded its {timeout_s:.0f}s budget (process group "
            "killed)")
    return proc.returncode, out, err


def _serving_8b_subprocess(timeout_s: float = 1200.0) -> dict:
    """Run serving_8b_bench in a FRESH process: at 32 slots the engine
    needs ~13 GiB of the 16 GiB HBM, and the earlier bench sections'
    compiled executables + allocator fragmentation in this process are
    enough to tip it into RESOURCE_EXHAUSTED (observed). A clean process
    reproduces the production condition — a serving engine owns its
    chip. `timeout_s` (computed by main() from the remaining bench
    budget) bounds the child from BOTH sides: the parent kills the
    child's process group past it, and the child's own watchdog thread
    exits at the same deadline even if the parent is gone."""
    import sys

    rc, out, err = _run_watchdogged(
        [sys.executable, "-c", _SERVING_8B_CHILD_SRC],
        timeout_s, cwd=os.path.dirname(os.path.abspath(__file__)),
        extra_argv=[timeout_s])
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(
        f"serving_8b subprocess rc={rc}: {err[-500:]}")



def _is_oom(e: Exception) -> bool:
    """True for HBM exhaustion (walk-down-able); everything else — shape
    bugs, compile failures — must surface with its original traceback."""
    msg = f"{type(e).__name__}: {e}"
    return ("RESOURCE_EXHAUSTED" in msg or "ResourceExhausted" in msg
            or "Ran out of memory" in msg)


def _build_engine_walkdown(params, cfg, slots_start: int, min_slots: int,
                           **engine_kw):
    """Build + warm an LLMEngine, halving n_slots on HBM exhaustion (a
    fresh chip fits slots_start; a shared or fragmented one may not).
    Returns (engine, n_slots). Non-OOM failures re-raise immediately."""
    from kubeflow_tpu.serving.llm import LLMEngine

    n_slots = slots_start
    while True:
        engine = None
        try:
            engine = LLMEngine(params, cfg, n_slots=n_slots, **engine_kw)
            engine.warmup()
            return engine, n_slots
        except Exception as e:
            if engine is not None:
                engine.close()
            if not _is_oom(e) or n_slots <= min_slots:
                raise
            n_slots //= 2


def serving_8b_bench(on_tpu: bool) -> dict:
    """BASELINE config #5 at TRUE dims, LIVE on the chip (VERDICT r3 ask
    #1, r4 ask #1): Llama-3-8B geometry (d4096/L32/ff14336, GQA 32/8,
    vocab 128256) actually serving tokens through the continuous-batching
    engine — int8 weights (~8.6 GiB with the bf16 embed) + int8 KV cache
    (16 slots × 2048, ~2.1 GiB) resident in the 16 GiB HBM. Reports:

    - sustained plain decode tok/s + roofline_frac (achieved HBM read
      rate ÷ the chip's 819 GB/s — decode is weight-read-bound, so
      bytes/step ≈ the non-embed weight bytes each decode step re-reads);
    - a ≥3-point open-loop Poisson saturation sweep (the toy model had
      one; the flagship now does too);
    - a SPECULATIVE decode point: one verify forward reads the weights
      ONCE for spec+1 positions, so accepted drafts multiply tokens per
      weight read — the biggest lever a weight-read-bound decode owns.
      Acceptance here comes from the model's own greedy dynamics (an
      untrained model's greedy decode is deterministic and typically
      cyclic, which prompt-lookup drafting catches); the measured
      spec_tokens_per_round is reported so the operating point is
      honest. Draft-quality-vs-text-difficulty is characterized
      separately at toy scale with TRAINED weights (spec_decode's
      full/realistic/heldout triple)."""
    if not on_tpu:
        # exercise the code path with toy dims off-TPU
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
            d_ff=128, max_seq_len=256)
        n_slots, max_len, bucket = 2, 128, 16
        prompt_len, new_tokens = 8, 8
        gaps = ((0.1, 4), (0.05, 4), (0.02, 4))
    else:
        cfg = llama.LlamaConfig.llama3_8b()
        # 32 slots: decode's ~7 GiB weight read amortizes over 32
        # concurrent sequences. r4's ceiling was 16 (24+ failed to
        # compile); the r5 grouped-attention + cache-carry rewrite freed
        # the head-expanded/dequantized temps AND the whole-cache rewrite,
        # so 32 x 2048 int8 KV (~4.1 GiB) now fits beside the weights
        # (40+ still OOMs). Measured (live sustain): 775 tok/s at 16
        # slots -> 1029 at 32; spec decode 1186 (16 slots, 6 drafts) ->
        # 1570 (32 slots, 3 drafts) -> 1630 (2 drafts).
        n_slots, max_len, bucket = 32, 2048, 128  # walk-down on OOM below
        prompt_len, new_tokens = 100, 64
        # offered 2 / 8 / 32 req/s (128 / 512 / 2048 tok/s of demand)
        # vs ~1060 tok/s sustained decode capacity: the light point
        # measures unloaded TTFT, the heavy point drives the engine past
        # saturation so the sweep's top throughput IS the serving
        # capacity under mixed prefill+decode (more requests at the
        # heavier points so the measurement reaches steady state)
        gaps = ((0.5, 24), (0.125, 32), (0.03125, 64))
    from kubeflow_tpu.serving.llm import LLMEngine

    import numpy as np

    slots_start = n_slots
    params = _init_llama_int8_serving(cfg)
    weight_bytes = sum(l.nbytes for l in jax.tree.leaves(params))
    # decode re-reads every weight byte per step EXCEPT the embed table
    # (a 16-row gather of the [V, d] bf16 table)
    read_bytes = weight_bytes - params["embed"].nbytes
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size,
                          size=(prompt_len,)).astype(int).tolist()

    def sustain(engine, slots: int) -> tuple[float, float]:
        """All slots busy with long generations; returns (tok/s, s)."""
        rids = [engine.submit(prompt, new_tokens * 2)
                for _ in range(slots)]
        t0 = time.perf_counter()
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        assert all(engine.is_done(r) for r in rids)
        for r in rids:
            engine.release(r)
        return slots * new_tokens * 2 / dt, dt

    t0 = time.perf_counter()
    # Pipelined decode (the engine default): the next chunk dispatches
    # before the previous chunk's fetch, so the host round trip (~106ms
    # in the r5 builders' account) hides behind device execution — 8B
    # decode went 118.6 (chunk-8 serial, the r3 design) -> ~202 tok/s
    # in that account, ~95% of the
    # 4-slot weight-read roofline at the observed step time. Chunk stays
    # 8: throughput is flat in chunk size once pipelined (8/16/32 all
    # ~200-204), and the shorter chunk halves the prefill's
    # drain-the-inflight-chunk wait, keeping TTFT low.
    # decode_chunk is the latency/throughput knob: a prefill wave must
    # drain the in-flight decode chunk first, so TTFT carries ~one chunk
    # of decode wall time. Measured at 32 slots: chunk 8 = 1055 tok/s
    # sustained, TTFT p50 ~465 ms under load; chunk 4 = 990 tok/s
    # (-6%), TTFT p50 ~217 ms. The bench records the throughput point;
    # latency-sensitive deployments should run chunk 4.
    engine, n_slots = _build_engine_walkdown(
        params, cfg, n_slots, 8, max_len=max_len, buckets=(bucket,),
        decode_chunk=8, kv_quantize="int8")
    cache_bytes = sum(l.nbytes for l in jax.tree.leaves(engine.cache))
    warmup_s = time.perf_counter() - t0
    engine.perf_counters(reset=True)   # clean host-side attribution
    decode_tps, _ = sustain(engine, n_slots)
    # plain decode: one weight read per step, n_slots tokens per step
    steps_per_s = decode_tps / n_slots
    plain_roofline = steps_per_s * read_bytes / (HBM_GBPS * 1e9)
    # decode-step attribution (tentpole r6, ROADMAP #2): split the step
    # into weight read / attention+KV update / sampling+penalties /
    # dispatch RTT / host fetch+replay — the five buckets that decide
    # whether the remaining roofline gap is addressable. The live-sustain
    # host counters (populated above) fill the host buckets.
    from kubeflow_tpu.training.profiling import serving_decode_breakdown

    try:
        breakdown = serving_decode_breakdown(
            engine, iters=5, hbm_gbps=HBM_GBPS if on_tpu else None)
    except Exception as e:
        breakdown = {"error": f"{type(e).__name__}: {e}"}
    # open-loop Poisson saturation sweep (r4 weak #4: the flagship had a
    # single light-load point)
    sweep = [_poisson_run(engine, prompt, new_tokens, nr, g)
             for g, nr in gaps]
    load = sweep[0]
    engine.close()   # eager HBM release (the engine is cyclic; see close)
    del engine

    # speculative decode at 8B: same weights, same slots, verify-mode
    # programs (spec+1 positions per weight read). Draft count 3: the
    # random-init model's measured acceptance is ~1.95/round at EVERY
    # k in 2..6 (all acceptance is the bonus + ~1 draft), so small k
    # wins — the verify forward carries k+1 query positions whose
    # FLOPs/scatter costs grow with k (measured at 32 slots: k=2 1630,
    # k=3 1570, k=4 1483, k=6 1259 tok/s). k=3 is the bench point: within
    # 4% of k=2 here, with headroom if the served text is more
    # predictable than random-weight cyclic decode. k is a per-engine
    # knob (`speculative=`); acceptance is reported so the operating
    # point stays honest.
    t0 = time.perf_counter()
    # verify-program temps sit above plain decode's: the spec engine gets
    # its own HBM walk-down
    spec_engine, spec_slots = _build_engine_walkdown(
        params, cfg, n_slots, 8, max_len=max_len, buckets=(bucket,),
        decode_chunk=8, kv_quantize="int8", speculative=3, spec_ngram=3)
    spec_warmup_s = time.perf_counter() - t0
    # static-k baseline FIRST on the same warmed engine (detaching the
    # policy dispatches k_max every round — the pre-r6 behavior; both
    # program menus are warm, so this is one extra sustain, not a second
    # engine build), then the adaptive-k point the floors track.
    adapt_policy = spec_engine._spec_adapt
    spec_engine._spec_adapt = None
    static_tps, _ = sustain(spec_engine, spec_slots)
    m_static = spec_engine.metrics()
    spec_engine._spec_adapt = adapt_policy
    spec_tps, _ = sustain(spec_engine, spec_slots)
    m = spec_engine.metrics()
    # the engine counters are cumulative across both sustains: the
    # adaptive point's acceptance must come from ITS rounds only (the
    # static run's rounds would otherwise skew both acc and the roofline)
    d_tok = (m.get("spec_tokens_emitted", 0)
             - m_static.get("spec_tokens_emitted", 0))
    d_rounds = (m.get("spec_verify_rounds", 0)
                - m_static.get("spec_verify_rounds", 0))
    acc = round(d_tok / max(1, d_rounds), 3)
    static_acc = m_static.get("spec_tokens_per_round", 0.0)
    # spec roofline: one weight read per verify round, `acc` tokens/round
    spec_rounds_per_s = spec_tps / (spec_slots * max(acc, 1e-9))
    spec_roofline = spec_rounds_per_s * read_bytes / (HBM_GBPS * 1e9)
    spec_engine.close()
    del spec_engine

    out = {
        "model": "llama3-8b(true-dims)" if on_tpu else "llama-tiny(cpu)",
        "weights": "int8(+bf16 embed)", "kv_cache": "int8",
        "n_params": 8030261248 if on_tpu else None,
        "weight_gib": round(weight_bytes / 1024**3, 3),
        "weight_read_gib_per_step": round(read_bytes / 1024**3, 3),
        "kv_cache_gib": round(cache_bytes / 1024**3, 3),
        "n_slots": n_slots, "max_len": max_len,
        # True when the engines could not fit the configured operating
        # point the floors assume (shared/fragmented chip): the record is
        # still the authoritative latest hardware run, and the floor gate
        # failing on it is the honest outcome — this flag says WHY
        "walked_down": bool(n_slots < slots_start
                            or spec_slots < slots_start),
        "prefill_bucket": bucket,
        "warmup_s": round(warmup_s, 1),
        "decode_tok_per_s": round(decode_tps, 1),
        "roofline_frac": round(plain_roofline, 3),
        "decode_breakdown": breakdown,
        "ttft_p50_ms": load["ttft_p50_ms"],
        "ttft_p99_ms": load["ttft_p99_ms"],
        "poisson_sweep": sweep,
        "saturation_tok_per_s": max(p["throughput_tok_per_s"]
                                    for p in sweep),
        "spec": {
            "decode_tok_per_s": round(spec_tps, 1),
            "speedup_vs_plain": round(spec_tps / decode_tps, 2),
            "spec_tokens_per_round": acc,
            "n_slots": spec_slots,
            "drafts_per_round": 3,
            "adaptive_k": True,
            "draft_k_last": m.get("spec_draft_k_last"),
            "accept_ema": m.get("spec_accept_ema"),
            # same warmed engine, policy detached → static k=3 each round
            "static_k3_tok_per_s": round(static_tps, 1),
            "static_k3_tokens_per_round": static_acc,
            "speedup_vs_static_k3": round(spec_tps / static_tps, 2),
            "roofline_frac": round(spec_roofline, 3),
            "warmup_s": round(spec_warmup_s, 1),
        },
    }
    del params
    return out


def _poisson_run(engine, prompt, new_tokens: int, n_req: int,
                 mean_gap_s: float, rng_seed: int = 0) -> dict:
    """One open-loop Poisson run. Returns TTFT percentiles plus the
    queueing-vs-service split (VERDICT r2 weak #2): `service` is the median
    busy engine.step() wall time (what one wave of work costs), `queue_wait`
    is scheduled-arrival -> prefill-start delay; their sum explains TTFT.
    """
    import numpy as np

    arrivals = np.cumsum(np.random.default_rng(rng_seed).exponential(
        mean_gap_s, n_req))
    rids: list[int] = []
    # TTFT epoch is the SCHEDULED Poisson arrival, not the submit instant:
    # arrivals coming due while a blocking engine.step() runs are submitted
    # late, and dropping that wait would bias the percentiles low
    sched_lag: list[float] = []
    first_tok_t: float | None = None
    step_times: list[float] = []
    t0 = time.perf_counter()
    while len(rids) < n_req or not all(engine.is_done(r) for r in rids):
        now = time.perf_counter() - t0
        while len(rids) < n_req and arrivals[len(rids)] <= now:
            sched_lag.append(now - arrivals[len(rids)])
            rids.append(engine.submit(prompt, new_tokens))
        ts = time.perf_counter()
        worked = engine.step()
        if worked:
            step_times.append(time.perf_counter() - ts)
        if first_tok_t is None and any(
                engine.ttft_seconds(r) is not None for r in rids):
            first_tok_t = time.perf_counter()
        if not worked:
            if len(rids) < n_req:  # idle until the next scheduled arrival
                time.sleep(max(0.0, arrivals[len(rids)]
                               - (time.perf_counter() - t0)))
            else:  # all submitted but not drained: don't busy-spin the host
                time.sleep(0.001)
    t_end = time.perf_counter()

    base_ttfts = [engine.ttft_seconds(r) for r in rids]
    assert all(t is not None for t in base_ttfts)
    ttfts = [t + lag for t, lag in zip(base_ttfts, sched_lag)]
    # queue wait = TTFT minus the prefill wave that actually served the
    # request; approximated by median busy-step service time
    service_ms = float(np.median(step_times)) * 1e3
    decode_tokens = n_req * (new_tokens - 1)
    return {
        "mean_gap_ms": round(mean_gap_s * 1e3, 1),
        "offered_req_per_s": round(1.0 / mean_gap_s, 1),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
        "service_per_wave_ms": round(service_ms, 2),
        "queue_wait_p50_ms": round(
            max(0.0, float(np.percentile(ttfts, 50)) * 1e3 - service_ms), 2),
        "decode_tok_per_s": round(
            decode_tokens / (t_end - (first_tok_t or t0)), 1),
        # end-to-end: first scheduled arrival -> drain of the whole stream
        "throughput_tok_per_s": round(
            n_req * new_tokens / (t_end - t0), 1),
    }


def serving_bench(on_tpu: bool) -> dict:
    """KServe-analog serving metric (BASELINE config #5): TTFT through the
    continuous-batching engine under open-loop Poisson load, swept over three
    offered rates so queueing delay and service time separate (VERDICT r2
    weak #2). The headline p50/p99 keys quote the HEAVIEST load point (30ms
    mean gap, continuity with r1/r2); the sweep shows where the engine
    saturates: once offered token rate exceeds saturation_tok_per_s, TTFT
    measures queue buildup, not engine latency.
    """
    from kubeflow_tpu.serving.llm import LLMEngine

    cfg = llama.LlamaConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=3584, max_seq_len=1024, remat=False,
    ) if on_tpu else llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    engine = LLMEngine(params, cfg, n_slots=8, max_len=256, buckets=(128,))
    engine.warmup()   # compile the full program menu (all wave widths)
    prompt = list(range(1, 100))
    new_tokens = 16
    engine.generate(prompt, new_tokens)  # exercise the live path once

    n_req = 32
    gaps = (0.100, 0.060, 0.030) if on_tpu else (0.030, 0.020, 0.010)
    sweep = [_poisson_run(engine, prompt, new_tokens, n_req, g) for g in gaps]
    heaviest = sweep[-1]
    saturation = max(p["throughput_tok_per_s"] for p in sweep)
    return {
        "serving_ttft_p50_ms": heaviest["ttft_p50_ms"],
        "serving_ttft_p99_ms": heaviest["ttft_p99_ms"],
        "serving_n_requests": n_req,
        "serving_arrivals":
            f"poisson mean_gap={heaviest['mean_gap_ms']:.0f}ms",
        "serving_decode_tok_per_s": heaviest["decode_tok_per_s"],
        "serving_throughput_tok_per_s": heaviest["throughput_tok_per_s"],
        "serving_load_sweep": sweep,
        "serving_saturation_tok_per_s": saturation,
    }


def _scenario_lora_adapters(cfg, names, rank: int = 4) -> dict:
    """Small random LoRA fleet for the multi-tenant scenario: the adapter
    GATHER path is what the scenario exercises — random weights are the
    perf-honest stand-in, exactly like _init_llama_int8_serving."""
    import numpy as np

    d, hd, nh, L = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_layers
    out = {}
    for i, name in enumerate(names):
        rng = np.random.default_rng(1000 + i)
        lora = {}
        for t, (d_in, d_out) in (("wq", (d, nh * hd)),
                                 ("wo", (nh * hd, d))):
            lora[t] = {
                "a": rng.standard_normal((L, d_in, rank)).astype("f4")
                * 0.02,
                "b": rng.standard_normal((L, rank, d_out)).astype("f4")
                * 0.02}
        out[name] = {"lora": lora, "alpha": float(2 * rank)}
    return out


def serving_scenarios_bench(on_tpu: bool, budget: Budget | None = None
                            ) -> dict:
    """Trace-driven production-traffic scenario suite (ROADMAP #4 — the
    loadgen subsystem): replay the committed named scenarios against one
    live engine through the ordinary submit path and record per-tenant
    SLO attainment, fairness, saturation, and goodput — the committed
    multi-scenario serving record the floor gate understands.

    One engine serves every scenario (multi-bucket prefill menu + a
    4-adapter S-LoRA fleet, warmed once); scenarios run in a fixed order
    and each checks the remaining bench budget first (skip-and-record,
    like the top-level sections). Identical seeds reproduce identical
    traces — the per-scenario trace_sha256 plus the recorded determinism
    re-check are the evidence."""
    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, run_scenario,
                                      trace_sha256)
    from kubeflow_tpu.loadgen.scenarios import SCENARIOS

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 128, 256),
                      decode_chunk=8)
        mini = None
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256)
        eng_kw = dict(n_slots=4, max_len=128, buckets=(16, 32),
                      decode_chunk=8)
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=30,
                    duration_s=3.0, rate_rps=4.0)
    from kubeflow_tpu.serving.llm import LLMEngine

    params = llama.init(jax.random.key(0), cfg)
    adapters = _scenario_lora_adapters(cfg, ("a0", "a1", "a2", "a3"))
    engine = LLMEngine(params, cfg, adapters=adapters, **eng_kw)
    t0 = time.perf_counter()
    engine.warmup()
    base_chunk = engine.decode_chunk
    out: dict = {
        "engine": {
            "model": (f"d{cfg.d_model}xL{cfg.n_layers}" if on_tpu
                      else "llama-tiny(cpu)"),
            "n_slots": eng_kw["n_slots"], "buckets": eng_kw["buckets"],
            "max_len": eng_kw["max_len"], "adapters": sorted(adapters),
            "warmup_s": round(time.perf_counter() - t0, 1),
        },
        "scenarios_run": [],
    }
    try:
        # floor-gated scenarios run FIRST: SCENARIOS is alphabetical, and
        # letting budget exhaustion skip `steady` would turn a healthy
        # run into a spurious scenario_steady floor failure
        gated = [n for n in SCENARIOS if n == "steady"]
        for name in gated + [n for n in SCENARIOS if n not in gated]:
            if budget is not None and budget.expired():
                out.setdefault("skipped_for_budget", []).append(name)
                continue
            # full-scale configs assume vocab 32000 (= the TPU cfg); the
            # CPU path shrinks every scenario onto the tiny engine
            scenario = load_scenario(name)
            if mini is not None:
                scenario = miniature(scenario, **mini)
            try:
                # clamp each replay to the REMAINING bench budget: the
                # default replay wall (duration*4+60) could otherwise
                # overrun the hard KTPU_BENCH_BUDGET_S wall by minutes —
                # the exact overrun the r6 harness exists to prevent
                wall = scenario.trace.duration_s * 4.0 + 60.0
                if budget is not None:
                    wall = max(5.0, min(wall, budget.remaining()))
                out[name] = run_scenario(engine, scenario,
                                         max_wall_s=wall)
                out["scenarios_run"].append(name)
            except Exception as e:   # one scenario must not kill the rest
                out[f"{name}_error"] = f"{type(e).__name__}: {e}"
            engine.set_decode_chunk(base_chunk)   # slo_chase may move it
        # determinism evidence: regenerating any run scenario's trace
        # yields the identical bytes (the committed sha re-derives)
        if out["scenarios_run"]:
            name = out["scenarios_run"][0]
            scenario = load_scenario(name)
            if mini is not None:
                scenario = miniature(scenario, **mini)
            out["deterministic"] = (
                trace_sha256(generate_trace(scenario.trace))
                == out[name]["trace_sha256"])
    finally:
        engine.close()
        del engine
    return out


def serving_chaos_bench(on_tpu: bool, budget: Budget | None = None) -> dict:
    """Chaos-hardened serving record (ISSUE 10, the robustness tentpole):
    replay the steady scenario through an EngineSupervisor three times —
    once clean (the goodput baseline), then once under each committed
    fault script (`crash_midstream`, `stall_and_partition`) — and commit:

    - MTTR: detected-death → recovered (restart + journal replay done),
      averaged over the run's outages;
    - goodput_retained: goodput under fault / clean-run goodput — how
      much of the SLO-met token stream survives a mid-window failure;
    - terminal_frac: accepted requests that reached a terminal state
      (completed/cancelled/rejected) / accepted — the zero-lost-request
      invariant; this is an exact contract (floor 1.0), not a perf
      number;
    - the fault script sha + fired-event log, so the committed record
      shows both the schedule and what actually landed.

    Each run builds a FRESH supervisor+engine (accounting is per-run) and
    checks the remaining bench budget first (skip-and-record)."""
    from kubeflow_tpu.loadgen import load_scenario, miniature, run_scenario
    from kubeflow_tpu.serving.agent import EngineSupervisor
    from kubeflow_tpu.serving.llm import LLMEngine

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 128, 256),
                      decode_chunk=8)
        sup_kw = dict(stall_timeout_s=1.0, backoff_base_s=0.1,
                      backoff_cap_s=2.0)
        mini = None
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256)
        eng_kw = dict(n_slots=4, max_len=128, buckets=(16, 32),
                      decode_chunk=8)
        sup_kw = dict(stall_timeout_s=0.2, backoff_base_s=0.02,
                      backoff_cap_s=0.2)
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=30,
                    duration_s=4.0, rate_rps=4.0)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("steady")
    if mini is not None:
        scenario = miniature(scenario, **mini)

    def factory():
        return LLMEngine(params, cfg, **eng_kw)

    out: dict = {
        "engine": {"model": (f"d{cfg.d_model}xL{cfg.n_layers}" if on_tpu
                             else "llama-tiny(cpu)"),
                   "n_slots": eng_kw["n_slots"],
                   "scenario": scenario.name,
                   "duration_s": scenario.trace.duration_s},
        "runs": [],
    }

    def one_run(label: str, script: str | None) -> dict | None:
        if budget is not None and budget.expired():
            out.setdefault("skipped_for_budget", []).append(label)
            return None
        sup = EngineSupervisor(factory, warm=True, **sup_kw)
        try:
            wall = scenario.trace.duration_s * 4.0 + 60.0
            if budget is not None:
                wall = max(5.0, min(wall, budget.remaining()))
            res = run_scenario(sup, scenario, fault_script=script,
                               max_wall_s=wall)
            acc = (res.get("chaos") or {}).get("accounting") \
                or sup.accounting()
            rec = {
                "goodput_tok_per_s":
                    res["aggregate"]["goodput_tok_per_s"],
                "throughput_tok_per_s":
                    res["aggregate"]["throughput_tok_per_s"],
                "slo_attainment": res["aggregate"]["slo_attainment"],
                "timed_out": res["timed_out"],
                "accepted": acc["accepted"],
                "terminal": acc["terminal"],
                "lost": acc["lost"],
                "in_flight": acc["in_flight"],
                # terminal/accepted, NOT (accepted-lost)/accepted: a
                # timed-out run's still-in-flight requests must count
                # AGAINST the exact 1.0 floor, not slip past it
                "terminal_frac": (round(
                    acc["terminal"] / acc["accepted"], 4)
                    if acc["accepted"] else None),
                "restarts": acc["restarts"],
                "replayed": acc["replayed"],
                "retried": acc["retried"],
                "replay_verified": acc["replay_verified"],
                "replay_mismatch": acc["replay_mismatch"],
                "mttr_s": acc["mttr_s"],
            }
            if res.get("chaos"):
                rec["script_sha256"] = res["chaos"]["script_sha256"]
                rec["events_fired"] = res["chaos"]["events_fired"]
            out["runs"].append(label)
            return rec
        finally:
            sup.close()

    clean = one_run("clean", None)
    if clean is not None:
        out["clean"] = clean
    base_goodput = (clean or {}).get("goodput_tok_per_s") or None
    for script in ("crash_midstream", "stall_and_partition"):
        try:
            rec = one_run(script, script)
        except Exception as e:   # one chaos run must not kill the rest
            out[f"{script}_error"] = f"{type(e).__name__}: {e}"
            continue
        if rec is None:
            continue
        if base_goodput:
            rec["goodput_retained"] = round(
                rec["goodput_tok_per_s"] / base_goodput, 4)
        out[script] = rec
    # partition events target the router↔backend path; this section
    # replays at the supervisor layer, so they are scheduled (and shown
    # in the committed script) but consumed by the router tests instead
    out["note"] = ("partition events are router-level — exercised by "
                   "tests/test_router_health.py, not this replay")
    # -- HTTP-path chaos (ISSUE 12, schema 6): the same crash measured
    # through a REAL socket client instead of the in-process engine
    if budget is not None and budget.expired():
        out.setdefault("skipped_for_budget", []).append("http")
    else:
        try:
            out["http"] = _serving_chaos_http(on_tpu, cfg, budget)
        except Exception as e:
            out["http_error"] = f"{type(e).__name__}: {e}"
    return out


def _serving_chaos_http(on_tpu: bool, cfg,
                        budget: Budget | None = None) -> dict:
    """HTTP-path chaos measurement (ISSUE 12): a supervised LLMModel
    behind ModelServer + Router, driven by REAL socket SSE clients while
    the committed `crash_midstream` script kills the engine mid-window.
    Committed metrics:

    - stream_completion_frac: streams that delivered a complete,
      BYTE-IDENTICAL response (vs the same request on the uncrashed
      server) with exactly one [DONE] and one usage object — the
      zero-duplicate/zero-lost streaming contract, floor exactly 1.0;
    - goodput_retained: delivered tok/s under fault / clean tok/s
      (includes restart backoff + replay, measured at the socket);
    - mttr_s / restarts / keepalives: the recovery the client actually
      rode through (keepalive comments are what held the connections).
    """
    import concurrent.futures

    import numpy as np

    from kubeflow_tpu.chaos import load_fault_script, script_sha256
    from kubeflow_tpu.loadgen import stream_completion
    from kubeflow_tpu.serving.llm_runtime import LLMModel
    from kubeflow_tpu.serving.model import ModelRepository
    from kubeflow_tpu.serving.router import Router
    from kubeflow_tpu.serving.server import ModelServer

    model_cfg = {k: getattr(cfg, k) for k in
                 ("vocab_size", "d_model", "n_layers", "n_heads",
                  "n_kv_heads", "d_ff", "max_seq_len")}
    if on_tpu:
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 128, 256),
                      decode_chunk=8)
        sup_cfg = dict(stall_timeout_s=5.0, backoff_base_s=0.1,
                       backoff_cap_s=2.0)   # rewarm default True: MTTR
        # includes the full program-menu warmup, the honest number
        n_req, max_tokens, lens = 16, 64, (48, 96, 200)
    else:
        eng_kw = dict(n_slots=4, max_len=128, buckets=(16, 32),
                      decode_chunk=8)
        sup_cfg = dict(stall_timeout_s=5.0, backoff_base_s=0.02,
                       backoff_cap_s=0.2, rewarm=False)
        n_req, max_tokens, lens = 8, 24, (6, 12, 24)
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in
                rng.integers(1, cfg.vocab_size,
                             int(lens[i % len(lens)]))]
               for i in range(n_req)]
    m = LLMModel("llm", model=model_cfg, seed=0,
                 supervisor=sup_cfg, sse_keepalive_s=0.25, **eng_kw)
    repo = ModelRepository()
    repo.register(m)
    server = ModelServer(repo).start()
    router = Router("bench/chaos-http")
    router.set_backends(server.port)

    def drive(min_wall: float) -> tuple[float, list[tuple[int, dict]]]:
        """Waves of concurrent SSE streams (prompt index attached) until
        `min_wall` elapses — so a fault scheduled inside the window
        provably fires while streams are live, on CPU dims too."""
        t0 = time.monotonic()
        res: list[tuple[int, dict]] = []
        while True:
            with concurrent.futures.ThreadPoolExecutor(4) as ex:
                res.extend(ex.map(
                    lambda ip: (ip[0], stream_completion(
                        router.port,
                        {"model": "llm", "prompt": ip[1],
                         "max_tokens": max_tokens, "temperature": 0.0},
                        timeout_s=300.0)),
                    enumerate(prompts)))
            if time.monotonic() - t0 >= min_wall:
                return time.monotonic() - t0, res

    # the committed script places the crash at ~0.4 of its window; the
    # drive runs past 0.6×window so the crash provably lands while
    # streams are in flight, and the run drains every stream it opened
    window = 30.0 if on_tpu else 2.0
    try:
        clean_wall, clean = drive(0.0)   # one wave: the byte oracle
        ref = {i: r["token_ids"] for i, r in clean}
        clean_toks = sum(len(r["token_ids"]) for _, r in clean)
        script = load_fault_script("crash_midstream", duration_s=window)
        m.supervisor.arm_faults(script)
        crash_wall, crash = drive(0.6 * window)
        crash_toks = sum(len(r["token_ids"]) for _, r in crash)
        acc = m.supervisor.accounting()
        ok = [r["token_ids"] == ref[i] and r["done_count"] == 1
              and r["usage_count"] == 1 and not r["errors"]
              and r["finish_reason"] in ("stop", "length")
              for i, r in crash]
        return {
            "n_streams": len(crash),
            "max_tokens": max_tokens,
            "script_sha256": script_sha256(script),
            "events_fired": m.supervisor.injector.log(),
            "crash_fired": bool(m.supervisor.injector.log()),
            "clean": {"wall_s": round(clean_wall, 3),
                      "tok_per_s": round(clean_toks / clean_wall, 2)},
            "crash": {"wall_s": round(crash_wall, 3),
                      "tok_per_s": round(crash_toks / crash_wall, 2),
                      "keepalives": sum(r["keepalives"] for _, r in crash),
                      "restarts": acc["restarts"],
                      "mttr_s": acc["mttr_s"],
                      "lost": acc["lost"]},
            "stream_completion_frac": round(sum(ok) / len(ok), 4),
            "goodput_retained": (round(
                (crash_toks / crash_wall) / (clean_toks / clean_wall), 4)
                if clean_toks else None),
        }
    finally:
        router.stop()
        server.stop()
        m.unload()


def serving_prefix_cache_bench(on_tpu: bool,
                               budget: Budget | None = None) -> dict:
    """Prefix-KV reuse record (ISSUE 11, the kvcache tentpole): replay
    the committed `shared_prefix_chat` scenario twice against the same
    model — once through an engine running the radix prefix cache, once
    through a cache-disabled engine — and commit:

    - hit_rate: admissions served from a cached chain / eligible
      admissions (floor 0.5: the scenario is BUILT to hit — every
      turn >= 2 extends a cached prompt);
    - prefill_saved_frac + prefill tokens per request cached vs cold —
      the compute the cache actually removed from the prefill path;
    - ttft_p50_ms cached vs cold (the step-change claim; recorded, not
      floored — at CPU toy dims the prefill delta sits inside timer
      noise, on TPU it is the headline);
    - greedy_parity: a shared-prefix probe set generated on BOTH
      engines must be byte-identical (the cached path replays the same
      math over reused KV — an exact contract, floor 1.0).

    Both runs replay the identical byte-pinned trace (sha recorded), so
    the comparison is between engines, never between workloads."""
    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, run_scenario,
                                      trace_sha256)
    from kubeflow_tpu.serving.llm import LLMEngine

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 128, 256),
                      decode_chunk=8)
        # warm_cont_pairs=None: pre-compile the WHOLE continuation menu
        # so the replayed TTFTs measure the cache, not mid-run XLA
        # compiles (warmup_s absorbs the cost, as everywhere else)
        cache_kw = dict(prefix_cache=True, prefix_cache_blocks=256,
                        warm_cont_pairs=None)
        mini = None
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 16, 32),
                      decode_chunk=8)
        cache_kw = dict(prefix_cache=True, prefix_cache_blocks=128,
                        warm_cont_pairs=None)
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=4.0, rate_rps=4.0)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("shared_prefix_chat")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": (f"d{cfg.d_model}xL{cfg.n_layers}" if on_tpu
                             else "llama-tiny(cpu)"),
                   "n_slots": eng_kw["n_slots"],
                   "buckets": eng_kw["buckets"],
                   "max_len": eng_kw["max_len"],
                   "block_tokens": math.gcd(*eng_kw["buckets"]),
                   "capacity_blocks": cache_kw["prefix_cache_blocks"]},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
    }

    def one_run(label: str, **extra_kw) -> dict | None:
        if budget is not None and budget.expired():
            out.setdefault("skipped_for_budget", []).append(label)
            return None
        engine = LLMEngine(params, cfg, **eng_kw, **extra_kw)
        try:
            t0 = time.perf_counter()
            engine.warmup()
            warmup_s = round(time.perf_counter() - t0, 1)
            wall = scenario.trace.duration_s * 4.0 + 60.0
            if budget is not None:
                wall = max(5.0, min(wall, budget.remaining()))
            res = run_scenario(engine, scenario, max_wall_s=wall)
            m = engine.metrics()
            done = max(1, m["completed"])
            return {
                "warmup_s": warmup_s,
                "ttft_p50_ms": res["aggregate"]["ttft_p50_ms"],
                "ttft_p95_ms": res["aggregate"].get("ttft_p95_ms"),
                "slo_attainment": res["aggregate"]["slo_attainment"],
                "timed_out": res["timed_out"],
                "completed": m["completed"],
                "prefill_tokens_computed": m["prefill_tokens_computed"],
                "prefill_tokens_per_request": round(
                    m["prefill_tokens_computed"] / done, 2),
                "prefix_cache": m.get("prefix_cache"),
            }
        finally:
            engine.close()
            del engine

    cached = one_run("cached", **cache_kw)
    cold = one_run("cold")
    if cached is not None:
        out["cached"] = cached
        pc = cached["prefix_cache"] or {}
        out["hit_rate"] = pc.get("request_hit_rate")
        saved = pc.get("prefill_tokens_saved", 0)
        computed = pc.get("prefill_tokens_computed", 0)
        out["prefill_saved_frac"] = (round(saved / (saved + computed), 4)
                                     if saved + computed else None)
    if cold is not None:
        out["cold"] = cold
    if cached is not None and cold is not None:
        out["prefill_tokens_per_request_cached"] = \
            cached["prefill_tokens_per_request"]
        out["prefill_tokens_per_request_cold"] = \
            cold["prefill_tokens_per_request"]
        if cached["ttft_p50_ms"] and cold["ttft_p50_ms"]:
            out["ttft_p50_speedup"] = round(
                cold["ttft_p50_ms"] / cached["ttft_p50_ms"], 3)
    # greedy parity: a fresh pair of engines (the scenario runs above
    # decode different mixes of arrival timing, so parity needs its own
    # controlled probe): shared template + distinct tails, generated on
    # the cached engine twice (miss then hit) and on a cold engine
    if budget is None or not budget.expired():
        parity_eng = LLMEngine(params, cfg, **eng_kw, **cache_kw)
        plain_eng = LLMEngine(params, cfg, **eng_kw)
        try:
            parity_eng.warmup()
            plain_eng.warmup()
            # the shared prefix must span >= 2 BLOCKS at this engine's
            # geometry or the probe can never hit (block = bucket gcd:
            # 8 on the CPU engine, 64 on the TPU engine)
            bt = parity_eng.prefix_block_tokens
            shared = [(i * 7) % (cfg.vocab_size - 1) + 1
                      for i in range(2 * bt + bt // 2)]
            parity = True
            for tail in ([17, 23, 5], [101, 9], [55, 56, 57, 58]):
                want = plain_eng.generate(shared + tail, 12)
                got = parity_eng.generate(shared + tail, 12)
                parity = parity and (got == want)
            hits = parity_eng.metrics()["prefix_hits"]
            out["greedy_parity"] = bool(parity and hits >= 2)
            out["parity_probe_hits"] = hits
        finally:
            parity_eng.close()
            plain_eng.close()
    return out


def serving_disagg_bench(on_tpu: bool, budget: Budget | None = None) -> dict:
    """Disaggregated prefill/decode record (ISSUE 13, ROADMAP #3): the
    SAME byte-pinned `diurnal_burst` trace replayed against (a) a
    colocated prefix-cache engine and (b) the disaggregated
    configuration — dedicated PrefillEngine feeding a DecodeEngine via
    radix-block KV handoff, each behind its own EngineSupervisor, with
    the SRPT prefill queue and decode-KV backpressure in between.
    Committed:

    - ttft_p50/p99 + decode tpot_p50/p99 per configuration (from the
      per-request phase-split records), goodput/throughput;
    - ttft_x_decode_gain = (colocated ttft_p99 / disagg ttft_p99) ×
      (disagg decode tok/s / colocated decode tok/s) — the acceptance
      product, floor 1.0 on schema>=7 records: disagg must beat
      colocated on TTFT p99 at equal-or-better decode throughput;
    - greedy/seeded byte-parity between the two configurations (exact
      contract, floor 1.0; the serialized-transport parity twin lives in
      tests/test_disagg.py) and handoff accounting (blocks/tokens moved,
      queue wait, bypasses);
    - a prefill-worker crash replay of the same trace (committed
      `crash_midstream` script armed on the PREFILL supervisor):
      terminal_frac floor exactly 1.0 — the zero-lost invariant holds
      when the prefill role dies mid-chunk.

    Engine economy matters off-TPU: the colocated engine doubles as the
    parity oracle, the replay coordinator doubles as the parity subject,
    and the crash pair warms lazily — the CPU smoke stays inside the
    bench budget."""
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.loadgen.runner import run_trace
    from kubeflow_tpu.serving.agent import EngineSupervisor
    from kubeflow_tpu.serving.disagg import DisaggregatedEngine
    from kubeflow_tpu.serving.llm import (DecodeEngine, LLMEngine,
                                          PrefillEngine)

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 128, 256),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=256, warm_cont_pairs=None)
        sup_kw = dict(stall_timeout_s=5.0, backoff_base_s=0.1,
                      backoff_cap_s=2.0)
        mini = None
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256)
        # default warm_cont_pairs (4): the full continuation menu is the
        # dominant CPU-smoke cost; cold pairs compile lazily mid-replay,
        # which the smoke tolerates (the committed comparison is TPU's)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 16, 32),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128)
        sup_kw = dict(stall_timeout_s=5.0, backoff_base_s=0.02,
                      backoff_cap_s=0.2)
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=4.0, rate_rps=4.0)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("diurnal_burst")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": (f"d{cfg.d_model}xL{cfg.n_layers}" if on_tpu
                             else "llama-tiny(cpu)"),
                   "n_slots": eng_kw["n_slots"],
                   "buckets": eng_kw["buckets"],
                   "max_len": eng_kw["max_len"]},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
    }
    if not on_tpu:
        # honest labelling: the prefill worker is a real thread, but on
        # a single-core CPU box the roles time-share the core, so the
        # TTFT/throughput comparison here is a smoke of the MACHINERY
        # only — the committed gain (and its schema>=7 floor) is the
        # TPU record's, where role dispatches overlap on the device
        out["note"] = ("cpu smoke: single-core roles time-share — "
                       "comparison numbers are not the committed claim")

    def pct(vals, q):
        vals = [v for v in vals if v is not None]
        return (round(float(np.percentile(vals, q)), 3)
                if vals else None)

    def replay(engine) -> dict:
        wall = scenario.trace.duration_s * 4.0 + 60.0
        if budget is not None:
            wall = max(5.0, min(wall, budget.remaining()))
        res = run_trace(engine, trace, max_wall_s=wall)
        ttfts = [r.ttft_ms() for r in res["records"]]
        tpots = [r.tpot_ms() for r in res["records"]]
        agg = res["summary"]["aggregate"]
        return {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "throughput_tok_per_s": agg["throughput_tok_per_s"],
            "goodput_tok_per_s": agg["goodput_tok_per_s"],
            "slo_attainment": agg["slo_attainment"],
            "completed": agg["completed"],
            "timed_out": res["timed_out"],
        }

    def disagg_coordinator(warm: bool) -> DisaggregatedEngine:
        def prefill_engine_factory():
            eng = PrefillEngine(params, cfg, **eng_kw)
            if warm:
                eng.warmup()
            return eng

        def decode_engine_factory():
            eng = DecodeEngine(params, cfg, **eng_kw)
            if warm:
                eng.warmup()
            return eng

        return DisaggregatedEngine(
            EngineSupervisor(prefill_engine_factory, **sup_kw),
            EngineSupervisor(decode_engine_factory, **sup_kw),
            handoff="zero_copy")

    # -- colocated baseline + disaggregated configuration on the
    # IDENTICAL trace; the same two serving stacks then answer the
    # byte-parity probes (bare colocated engine: the raw-engine perf
    # point the lint sanctions for bench.py)
    ref = LLMEngine(params, cfg, **eng_kw)
    co = None
    try:
        if budget is None or not budget.expired():
            t0 = time.perf_counter()
            ref.warmup()
            rec = replay(ref)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            out["colocated"] = rec
        if budget is None or not budget.expired():
            co = disagg_coordinator(warm=True)
            rec = replay(co)
            m = co.metrics()
            rec["handoff"] = m["disagg"]["handoff"]
            rec["queue_wait_ms_mean"] = m["disagg"]["queue_wait_ms_mean"]
            rec["bypass"] = m["disagg"]["bypass"]
            rec["decode_full_prefills"] = \
                m["disagg"]["decode_full_prefills"]
            rec["lost"] = co.accounting()["lost"]
            out["disagg"] = rec
        col, dis = out.get("colocated"), out.get("disagg")
        if col and dis and col["ttft_p99_ms"] and dis["ttft_p99_ms"] \
                and col["throughput_tok_per_s"]:
            out["ttft_p99_speedup"] = round(
                col["ttft_p99_ms"] / dis["ttft_p99_ms"], 4)
            out["decode_throughput_ratio"] = round(
                dis["throughput_tok_per_s"]
                / col["throughput_tok_per_s"], 4)
            out["ttft_x_decode_gain"] = round(
                out["ttft_p99_speedup"] * out["decode_throughput_ratio"],
                4)
            if col["tpot_p99_ms"] and dis["tpot_p99_ms"]:
                out["tpot_p99_ratio"] = round(
                    col["tpot_p99_ms"] / dis["tpot_p99_ms"], 4)
        # byte parity: greedy AND seeded sampling through the
        # prefill→handoff→decode pipeline must match the colocated
        # engine exactly (the r10 cached-path contract across the split)
        if co is not None and (budget is None or not budget.expired()):
            probes = [list(range(1, 2 * eng_kw["buckets"][0] + 3)),
                      [7, 9, 11],
                      list(range(3, eng_kw["buckets"][-1] + 10))]
            out["greedy_parity"] = bool(all(
                co.generate(p, 12) == ref.generate(p, 12)
                for p in probes))
            out["seeded_parity"] = bool(all(
                co.generate(p, 12, temperature=0.8, seed=99)
                == ref.generate(p, 12, temperature=0.8, seed=99)
                for p in probes))
            out["parity_transport"] = "zero_copy"
    finally:
        ref.close()
        if co is not None:
            co.close()
        del ref, co
    # -- prefill-worker crash: same trace, committed crash script armed
    # on the PREFILL supervisor — zero lost requests is the contract
    if budget is None or not budget.expired():
        from kubeflow_tpu.chaos import load_fault_script, script_sha256

        co = disagg_coordinator(warm=on_tpu)   # CPU: lazy compiles keep
        try:                                   # the smoke in budget
            script = load_fault_script(
                "crash_midstream", duration_s=scenario.trace.duration_s)
            co.prefill.arm_faults(script)
            rec = replay(co)
            acc = co.accounting()
            rec.update({
                "script_sha256": script_sha256(script),
                "events_fired": co.prefill.injector.log(),
                "prefill_restarts": acc["prefill"]["restarts"],
                "accepted": acc["accepted"],
                "terminal": acc["terminal"],
                "lost": acc["lost"],
                "in_flight": acc["in_flight"],
                "terminal_frac": (round(
                    acc["terminal"] / acc["accepted"], 4)
                    if acc["accepted"] else None),
            })
            out["crash"] = rec
        finally:
            co.close()
    return out


def serving_kernels_bench(on_tpu: bool, budget: Budget | None = None) -> dict:
    """Kernel-path A/B record (ISSUE 15, ROADMAP #5): the SAME model,
    trace, and engine construction measured twice — once with
    `decode_attention_impl: xla` (the reference einsum) and once with
    `flash` (the fused Pallas flash-decode kernel over the int8 KV
    slab, ops/flash_decode.py) — so a kernel win (or regression) is a
    committed number on the current toolchain, never folklore.
    Committed:

    - per impl: replayed TTFT/TPOT percentiles + decode throughput on
      the identical byte-pinned shared_prefix_chat trace (int8 KV +
      chunked prefill + prefix cache ON — every correctness-critical
      decode path at once), and the full `serving_decode_breakdown`
      (whose `attn_kernel`/`attn_dequant` sub-buckets localize the
      delta: the impls differ there, every other bucket stays put);
    - `decode_step_ratio` (xla device step / flash device step) and
      `bucket_delta_ms` — the per-bucket attribution of the A/B;
    - `kernel_greedy_parity` — the exact contract, floor 1.0 on
      schema>=9 records: greedy AND seeded byte parity across the impls
      on probes covering the prefix-cache hit path and chunked prompts,
      plus speculative-verify parity (a flash spec engine, S_v>1
      through the kernel, against the xla pair) — all must hold;
    - `quant_matmul`: the weight-read path the record ran under
      (resolve_quant_matmul_impl — the other ISSUE 15 default flip).

    On CPU the flash engine runs the kernel in INTERPRET mode, so the
    timing comparison is a smoke of machinery + parity only; the
    speedup floor stays a placeholder until the open-item-#1 TPU record
    (the established convention)."""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.loadgen.runner import run_trace
    from kubeflow_tpu.ops import quant
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.training.profiling import serving_decode_breakdown

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 256),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128, kv_quantize="int8",
                      quantize="int8", warm_cont_pairs=None)
        spec_kw = dict(n_slots=8, max_len=512, buckets=(64,),
                       decode_chunk=8, kv_quantize="int8",
                       quantize="int8", speculative=3)
        mini = None
        max_new = 32
        bd_kw = dict(steps=4, iters=5)
    else:
        # f32 on CPU: the parity claim is the MACHINERY's exactness,
        # measured in a dtype where cross-impl accumulation-order drift
        # cannot make byte comparison a coin flip at toy dims (the
        # multichip smoke's choice); int8 KV stays ON — the dequant
        # fusion is half the kernel's contract
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256, dtype=jnp.float32)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 32),
                      decode_chunk=4, prefix_cache=True,
                      prefix_cache_blocks=96, kv_quantize="int8")
        spec_kw = dict(n_slots=2, max_len=96, buckets=(16,),
                       decode_chunk=4, kv_quantize="int8", speculative=3)
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=3.0, rate_rps=5.0)
        max_new = 12
        bd_kw = dict(steps=2, iters=3)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("shared_prefix_chat")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": f"d{cfg.d_model}xL{cfg.n_layers}",
                   "dtype": str(getattr(cfg.dtype, "__name__", cfg.dtype)),
                   **{k: v for k, v in eng_kw.items()
                      if k != "prefix_cache"}},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
        "quant_matmul": {"impl": quant.resolve_quant_matmul_impl(),
                         "env": os.environ.get(quant.QUANT_MATMUL_ENV)
                         or None},
    }
    if not on_tpu:
        out["note"] = ("cpu smoke: the flash impl runs the Pallas "
                       "INTERPRETER — parity + machinery are the "
                       "committed claims; the step-time comparison "
                       "awaits the on-TPU record")

    def expired() -> bool:
        return budget is not None and budget.expired()

    def replay(engine) -> dict:
        wall = scenario.trace.duration_s * 4.0 + 60.0
        if budget is not None:
            wall = max(5.0, min(wall, budget.remaining()))
        res = run_trace(engine, trace, max_wall_s=wall)
        ttfts = [r.ttft_ms() for r in res["records"]]
        tpots = [r.tpot_ms() for r in res["records"]]

        def pct(vals, q):
            vals = [v for v in vals if v is not None]
            return (round(float(np.percentile(vals, q)), 3)
                    if vals else None)

        agg = res["summary"]["aggregate"]
        return {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "throughput_tok_per_s": agg["throughput_tok_per_s"],
            "completed": agg["completed"],
            "timed_out": res["timed_out"],
        }

    engines: dict = {}
    try:
        for impl in ("xla", "flash"):
            if expired():
                out.setdefault("skipped_for_budget", []).append(impl)
                continue
            t0 = time.perf_counter()
            eng = LLMEngine(params, cfg, decode_attention_impl=impl,
                            **eng_kw)
            engines[impl] = eng   # registered BEFORE warmup: a compile
            # failure must not leak the engine's slabs into the next
            # section's HBM budget (the outer finally closes everything)
            eng.warmup()
            rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            rec["resolved_impl"] = eng.metrics()["decode_attention_impl"]
            # the per-bucket attribution: attn_kernel carries the impl
            # delta, weight_read/sampling/dispatch stay put — the
            # "explainable per bucket" half of the acceptance criteria
            rec["decode_breakdown"] = serving_decode_breakdown(
                eng, **bd_kw)
            out[impl] = rec
        if "xla" in out and "flash" in out:
            bx = out["xla"]["decode_breakdown"]
            bf = out["flash"]["decode_breakdown"]
            if bf["device_step_ms"]:
                out["decode_step_ms"] = {
                    "xla": bx["device_step_ms"],
                    "flash": bf["device_step_ms"]}
                out["decode_step_ratio"] = round(
                    bx["device_step_ms"] / bf["device_step_ms"], 4)
            if out["xla"]["tpot_p50_ms"] and out["flash"]["tpot_p50_ms"]:
                out["tpot_p50_ratio"] = round(
                    out["xla"]["tpot_p50_ms"]
                    / out["flash"]["tpot_p50_ms"], 4)
            out["bucket_delta_ms"] = {
                k: round(bx["buckets_ms"][k] - bf["buckets_ms"][k], 4)
                for k in bx["buckets_ms"]
                if bx["buckets_ms"].get(k) is not None
                and bf["buckets_ms"].get(k) is not None}
        # -- the exact parity contract (floor 1.0, schema>=9): greedy +
        # seeded probes across the impls, incl. a prefix-cache HIT and a
        # chunked (> largest bucket) prompt; then speculative verify
        # (S_v>1) through the flash kernel against the xla pair
        parity: dict[str, bool] = {}
        if "xla" in engines and "flash" in engines and not expired():
            ex, ef = engines["xla"], engines["flash"]
            bt = ex.prefix_block_tokens
            shared = [(i * 7) % (cfg.vocab_size - 1) + 1
                      for i in range(2 * bt + bt // 2)]
            probes = [shared + [17, 23, 5],
                      shared + [101, 9],          # second use: radix HIT
                      [7, 9, 11],
                      list(range(3, eng_kw["buckets"][-1] + 10))]  # chunked
            parity["greedy"] = bool(all(
                ex.generate(list(p), max_new) == ef.generate(list(p),
                                                             max_new)
                for p in probes))
            parity["seeded"] = bool(all(
                ex.generate(list(p), max_new, temperature=0.8, seed=99)
                == ef.generate(list(p), max_new, temperature=0.8,
                               seed=99)
                for p in probes))
            out["parity_probe_hits"] = ex.metrics()["prefix_hits"]
        if "xla" in engines and not expired():
            # speculative verify: draft acceptance runs S_v=4 windows
            # through the kernel; spec-greedy == plain-greedy is the
            # engine invariant, so the xla pair is the oracle for BOTH
            sx = sf = None
            try:
                sx = LLMEngine(params, cfg, decode_attention_impl="xla",
                               **spec_kw)
                sf = LLMEngine(params, cfg,
                               decode_attention_impl="flash", **spec_kw)
                sx.warmup()
                sf.warmup()
                sprobes = [list(range(1, 12)) * 2, [5, 6, 7, 5, 6, 7, 5]]
                parity["spec"] = bool(all(
                    sx.generate(list(p), max_new)
                    == sf.generate(list(p), max_new)
                    for p in sprobes))
            finally:
                if sx is not None:
                    sx.close()
                if sf is not None:
                    sf.close()
        if parity:
            out["parity"] = parity
            out["kernel_greedy_parity"] = (
                1.0 if all(parity.values()) else 0.0)
    finally:
        for eng in engines.values():
            eng.close()
    return out


def serving_prefill_kernels_bench(on_tpu: bool,
                                  budget: Budget | None = None) -> dict:
    """Prefill-kernel A/B record (ISSUE 20, schema>=12): the SAME model,
    trace, and engine construction measured twice — once with
    `prefill_attention_impl: xla` (the reference einsum prefill) and
    once with `flash` (the Pallas chunked-prefill kernel,
    ops/flash_prefill.py: online-softmax over KV blocks with fused int8
    dequant and q_offset causal masking) — the TTFT half of the ISSUE 15
    decode A/B. Committed:

    - per impl: replayed TTFT/TPOT percentiles + decode throughput on
      the identical byte-pinned shared_prefix_chat trace (int8 KV +
      chunked prefill + prefix cache ON — chunk continuations at
      nonzero q_offset are the kernel's hardest masking case), the
      `serving_decode_breakdown` whose `prefill_attn` bucket localizes
      the delta, and `prefill_ms_by_plen` — prefill wall per prompt
      length covering one-bucket, padded, and chunked admissions;
    - `prefill_kernel_greedy_parity` — the exact contract, floor 1.0 on
      schema>=12 records: greedy AND seeded byte parity across the
      impls on probes covering cold, prefix-cache HIT (continuation
      q_offset lands mid-sequence), and chunked (> largest bucket)
      prompts, on the slab engine AND the paged engine (block-table KV
      through the kernel's gather path) — all must hold.

    On CPU the flash engine runs the kernel in INTERPRET mode, so the
    timing comparison is a smoke of machinery + parity only; the TTFT
    gain floor stays a placeholder until the open-item-#1 TPU record
    (the serving_kernels convention)."""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.loadgen.runner import run_trace
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.paged import PagedLLMEngine
    from kubeflow_tpu.training.profiling import serving_decode_breakdown

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 256),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128, kv_quantize="int8")
        mini = None
        max_new = 32
        bd_kw = dict(steps=4, iters=5)
        plens = (48, 240, 400)
    else:
        # f32 on CPU: the parity claim is the MACHINERY's exactness,
        # measured in a dtype where cross-impl accumulation-order drift
        # cannot make byte comparison a coin flip at toy dims (the
        # serving_kernels choice); int8 KV stays ON — the fused dequant
        # of banked prefix blocks is half the prefill kernel's contract
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256, dtype=jnp.float32)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 32),
                      decode_chunk=4, prefix_cache=True,
                      prefix_cache_blocks=96, kv_quantize="int8")
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=3.0, rate_rps=5.0)
        max_new = 12
        bd_kw = dict(steps=2, iters=3)
        # one-bucket / padded-top-bucket / chunked (> largest bucket)
        plens = (6, 30, 56)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("shared_prefix_chat")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": f"d{cfg.d_model}xL{cfg.n_layers}",
                   "dtype": str(getattr(cfg.dtype, "__name__", cfg.dtype)),
                   **{k: v for k, v in eng_kw.items()
                      if k != "prefix_cache"}},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
    }
    if not on_tpu:
        out["note"] = ("cpu smoke: the flash impl runs the Pallas "
                       "INTERPRETER — parity + machinery are the "
                       "committed claims; the TTFT comparison awaits "
                       "the on-TPU record")

    def expired() -> bool:
        return budget is not None and budget.expired()

    def replay(engine) -> dict:
        wall = scenario.trace.duration_s * 4.0 + 60.0
        if budget is not None:
            wall = max(5.0, min(wall, budget.remaining()))
        res = run_trace(engine, trace, max_wall_s=wall)
        ttfts = [r.ttft_ms() for r in res["records"]]
        tpots = [r.tpot_ms() for r in res["records"]]

        def pct(vals, q):
            vals = [v for v in vals if v is not None]
            return (round(float(np.percentile(vals, q)), 3)
                    if vals else None)

        agg = res["summary"]["aggregate"]
        return {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "throughput_tok_per_s": agg["throughput_tok_per_s"],
            "completed": agg["completed"],
            "timed_out": res["timed_out"],
        }

    def prefill_by_plen(engine) -> dict:
        """Measured prefill wall (request_timing's prefill_ms) per
        prompt length — best of 2 so the number is the warm program,
        not a compile."""
        res = {}
        for plen in plens:
            prompt = [(i * 11) % (cfg.vocab_size - 1) + 1
                      for i in range(plen)]
            best = None
            for _ in range(2):
                rid = engine.submit(list(prompt), 2, 0.0)
                engine.run_until_idle()
                tm = engine.request_timing(rid)
                engine.release(rid)
                if tm["prefill_ms"] is not None:
                    best = (tm["prefill_ms"] if best is None
                            else min(best, tm["prefill_ms"]))
            res[str(plen)] = round(best, 3) if best is not None else None
        return res

    engines: dict = {}
    try:
        for impl in ("xla", "flash"):
            if expired():
                out.setdefault("skipped_for_budget", []).append(impl)
                continue
            t0 = time.perf_counter()
            eng = LLMEngine(params, cfg, prefill_attention_impl=impl,
                            **eng_kw)
            engines[impl] = eng   # registered BEFORE warmup (the
            # serving_kernels leak guard: a compile failure must not
            # pin the slabs past the section)
            eng.warmup()
            rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            rec["resolved_impl"] = eng.metrics()["prefill_attention_impl"]
            rec["prefill_ms_by_plen"] = prefill_by_plen(eng)
            # the per-bucket attribution: prefill_attn carries the impl
            # delta, the decode buckets stay put
            rec["decode_breakdown"] = serving_decode_breakdown(
                eng, **bd_kw)
            out[impl] = rec
        if "xla" in out and "flash" in out:
            bx = out["xla"]["decode_breakdown"]["buckets_ms"]
            bf = out["flash"]["decode_breakdown"]["buckets_ms"]
            if bx.get("prefill_attn") and bf.get("prefill_attn"):
                out["prefill_attn_ms"] = {"xla": bx["prefill_attn"],
                                          "flash": bf["prefill_attn"]}
                out["prefill_attn_ratio"] = round(
                    bx["prefill_attn"] / bf["prefill_attn"], 4)
        # -- the exact parity contract (floor 1.0, schema>=12): greedy +
        # seeded probes across the impls — cold, radix HIT (the
        # continuation prefill at nonzero q_offset), and chunked
        # (> largest bucket) prompts; then the SAME probes through a
        # paged pair (block-table KV read through the kernel's gather)
        parity: dict[str, bool] = {}
        bt = (next(iter(engines.values())).prefix_block_tokens
              if engines else 16)
        shared = [(i * 7) % (cfg.vocab_size - 1) + 1
                  for i in range(2 * bt + bt // 2)]
        probes = [shared + [17, 23, 5],
                  shared + [101, 9],          # second use: radix HIT
                  [7, 9, 11],
                  list(range(3, eng_kw["buckets"][-1] + 10))]  # chunked
        if "xla" in engines and "flash" in engines and not expired():
            ex, ef = engines["xla"], engines["flash"]
            parity["greedy"] = bool(all(
                ex.generate(list(p), max_new) == ef.generate(list(p),
                                                             max_new)
                for p in probes))
            parity["seeded"] = bool(all(
                ex.generate(list(p), max_new, temperature=0.8, seed=99)
                == ef.generate(list(p), max_new, temperature=0.8,
                               seed=99)
                for p in probes))
            out["parity_probe_hits"] = ex.metrics()["prefix_hits"]
        if not expired():
            px = pf = None
            try:
                px = PagedLLMEngine(params, cfg,
                                    prefill_attention_impl="xla",
                                    **eng_kw)
                pf = PagedLLMEngine(params, cfg,
                                    prefill_attention_impl="flash",
                                    **eng_kw)
                parity["paged_greedy"] = bool(all(
                    px.generate(list(p), max_new)
                    == pf.generate(list(p), max_new) for p in probes))
                parity["paged_seeded"] = bool(all(
                    px.generate(list(p), max_new, temperature=0.8,
                                seed=99)
                    == pf.generate(list(p), max_new, temperature=0.8,
                                   seed=99) for p in probes))
                out["paged_probe_hits"] = px.metrics()["prefix_hits"]
            finally:
                if px is not None:
                    px.close()
                if pf is not None:
                    pf.close()
        if parity:
            out["parity"] = parity
            out["prefill_kernel_greedy_parity"] = (
                1.0 if all(parity.values()) else 0.0)
    finally:
        for eng in engines.values():
            eng.close()
    return out


def serving_paged_kv_bench(on_tpu: bool, budget: Budget | None = None) -> dict:
    """Paged-KV A/B record (ISSUE 19, schema>=11): the SAME model and
    byte-pinned long_tail_mix trace served twice — once by the slab
    engine at S slots, once by the paged engine (serving/paged.py) at
    4S slots over a block pool holding the SLAB'S byte budget (pool
    blocks = S x max_len/bt, +1 trash block) — so the tentpole's claim
    ("the same HBM admits multiples of the streams") is a committed
    number, not an argument. Committed:

    - per layout: replayed TTFT/TPOT percentiles, decode throughput,
      peak in-flight streams (slots concurrently owned by admitted
      requests, sampled every runner loop), KV bytes resident, and
      goodput-per-GiB-of-KV (throughput / kv_gib — the metric the
      heavy-tailed trace exists to move);
    - `concurrency_gain` (floor 4.0 on schema>=11): paged peak
      in-flight / slab peak in-flight at equal KV bytes. The heavy
      tail strands slab slots sized for max_len; block-granular
      funding turns that stranding into admitted streams;
    - `paged_greedy_parity` (floor exactly 1.0): greedy AND seeded
      byte parity slab-vs-paged on probes covering the radix-hit and
      chunked (> largest bucket) prompts, PLUS the two eviction
      contracts — recompute-from-prefix after a forced full eviction
      reproduces the never-evicted stream, and an oversubscribed burst
      (more streams than the pool funds at once, admission holding and
      retrying through radix eviction) delivers every request's tokens
      exactly once, byte-identical to slab. All must hold.

    On CPU this is a smoke at toy dims (f32 activations so byte
    comparison is not an accumulation-order coin flip; int8 KV stays ON
    — the per-token scales ride the pool blocks); the committed TPU
    numbers await the open-item-#1 hardware run (the established
    convention)."""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.loadgen.runner import run_trace
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.paged import PagedLLMEngine

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        slab_slots, max_len, buckets = 8, 512, (64, 256)
        common = dict(decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128, kv_quantize="int8",
                      quantize="int8", warm_cont_pairs=None)
        mini = None
        max_new = 32
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256, dtype=jnp.float32)
        slab_slots, max_len, buckets = 2, 64, (8, 16)
        common = dict(decode_chunk=4, prefix_cache=True,
                      prefix_cache_blocks=64, kv_quantize="int8")
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=40,
                    duration_s=3.0, rate_rps=30.0, max_output=8)
        max_new = 8
    bt = math.gcd(*buckets)
    paged_slots = 4 * slab_slots
    # the equal-HBM construction: the paged pool holds exactly the slab
    # engine's KV token budget (S x max_len), +1 trash sentinel block
    pool_blocks = slab_slots * (max_len // bt)
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("long_tail_mix")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": f"d{cfg.d_model}xL{cfg.n_layers}",
                   "dtype": str(getattr(cfg.dtype, "__name__", cfg.dtype)),
                   "max_len": max_len, "buckets": buckets,
                   "block_tokens": bt,
                   "slab_slots": slab_slots, "paged_slots": paged_slots,
                   "pool_blocks": pool_blocks, **common},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
    }
    if not on_tpu:
        out["note"] = ("cpu smoke: parity + machinery + the equal-bytes "
                       "concurrency construction are the committed "
                       "claims; throughput numbers await the on-TPU "
                       "record")

    def expired() -> bool:
        return budget is not None and budget.expired()

    class _PeakProbe:
        """Runner controller hook abused as a sampler: every runner
        loop, count slots owned by an admitted request (held-but-
        unfunded prefills own their slot too — residency IS the
        admission claim)."""

        def __init__(self):
            self.peak = 0

        def observe(self, ttft_ms):
            pass

        def maybe_adjust(self, engine, now_s):
            n = sum(1 for s in range(engine.n_slots)
                    if engine.scheduler.slot_request(s) >= 0)
            self.peak = max(self.peak, n)

    def kv_bytes(engine) -> int:
        return sum(int(v.nbytes) for k, v in engine.cache.items()
                   if k in ("k", "v", "k_s", "v_s"))

    def replay(engine) -> dict:
        wall = scenario.trace.duration_s * 4.0 + 60.0
        if budget is not None:
            wall = max(5.0, min(wall, budget.remaining()))
        probe = _PeakProbe()
        res = run_trace(engine, trace, controller=probe, max_wall_s=wall)
        ttfts = [r.ttft_ms() for r in res["records"]]
        tpots = [r.tpot_ms() for r in res["records"]]

        def pct(vals, q):
            vals = [v for v in vals if v is not None]
            return (round(float(np.percentile(vals, q)), 3)
                    if vals else None)

        agg = res["summary"]["aggregate"]
        gib = kv_bytes(engine) / 2**30
        tput = agg["throughput_tok_per_s"]
        return {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "throughput_tok_per_s": tput,
            "completed": agg["completed"],
            "timed_out": res["timed_out"],
            "peak_inflight_streams": probe.peak,
            "kv_bytes": kv_bytes(engine),
            "goodput_per_gib_kv": (round(tput / gib, 1)
                                   if gib and tput is not None else None),
        }

    engines: dict = {}
    try:
        for layout in ("slab", "paged"):
            if expired():
                out.setdefault("skipped_for_budget", []).append(layout)
                continue
            t0 = time.perf_counter()
            if layout == "slab":
                eng = LLMEngine(params, cfg, n_slots=slab_slots,
                                max_len=max_len, buckets=buckets, **common)
            else:
                eng = PagedLLMEngine(params, cfg, n_slots=paged_slots,
                                     max_len=max_len, buckets=buckets,
                                     pool_blocks=pool_blocks, **common)
            engines[layout] = eng   # registered BEFORE warmup (leak guard)
            eng.warmup()
            rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            if layout == "paged":
                rec["kv_pool"] = eng.metrics()["kv_pool"]
            out[layout] = rec
        if "slab" in out and "paged" in out:
            out["kv_bytes_ratio"] = round(
                out["paged"]["kv_bytes"] / out["slab"]["kv_bytes"], 4)
            if out["slab"]["peak_inflight_streams"]:
                out["concurrency_gain"] = round(
                    out["paged"]["peak_inflight_streams"]
                    / out["slab"]["peak_inflight_streams"], 4)
            if (out["slab"]["goodput_per_gib_kv"]
                    and out["paged"]["goodput_per_gib_kv"]):
                out["goodput_per_gib_ratio"] = round(
                    out["paged"]["goodput_per_gib_kv"]
                    / out["slab"]["goodput_per_gib_kv"], 4)
        # -- the exact parity contract (floor 1.0, schema>=11) --------
        parity: dict[str, bool] = {}
        if "slab" in engines and "paged" in engines and not expired():
            es, ep = engines["slab"], engines["paged"]
            shared = [(i * 7) % (cfg.vocab_size - 1) + 1
                      for i in range(2 * bt + bt // 2)]
            probes = [shared + [17, 23, 5],
                      shared + [101, 9],          # second use: radix HIT
                      [7, 9, 11],
                      list(range(3, buckets[-1] + 10))]   # chunked
            parity["greedy"] = bool(all(
                es.generate(list(p), max_new) == ep.generate(list(p),
                                                             max_new)
                for p in probes))
            parity["seeded"] = bool(all(
                es.generate(list(p), max_new, temperature=0.8, seed=99)
                == ep.generate(list(p), max_new, temperature=0.8,
                               seed=99)
                for p in probes))
            # forced full eviction, then the SAME prompt: the recompute-
            # from-prefix path must reproduce the never-evicted stream
            want = es.generate(list(probes[0]), max_new)
            evicted = ep.kvcache.evict(10**9)
            ep._flush_derefs()
            parity["evict_recompute"] = \
                ep.generate(list(probes[0]), max_new) == want
            out["evicted_blocks"] = evicted
            # oversubscribed burst: every stream needs blocks the pool
            # cannot fund all at once — admission must hold + retry
            # through eviction and still deliver every token exactly
            # once (the zero-lost/zero-duplicate contract)
            burst = [[(j * 11 + i) % (cfg.vocab_size - 1) + 1
                      for i in range(2 * bt + 2)]
                     for j in range(2 * paged_slots)]
            want_burst = [es.generate(list(p), max_new) for p in burst]
            fail0 = ep.metrics()["kv_pool"]["alloc_failures"]
            rids = [ep.submit(list(p), max_new) for p in burst]
            for _ in range(10_000):
                if all(ep.is_done(r) for r in rids):
                    break
                ep.step()
            got_burst = [ep.result(r) for r in rids]
            parity["oversubscribed"] = got_burst == want_burst
            out["oversubscribed"] = {
                "streams": len(burst),
                "exact": parity["oversubscribed"],
                "alloc_failures": (ep.metrics()["kv_pool"]
                                   ["alloc_failures"] - fail0),
                "held_at_end": ep.metrics()["held_prefills"],
            }
            ep._pool.check_invariants()
        if parity:
            out["parity"] = parity
            out["paged_greedy_parity"] = (
                1.0 if all(parity.values()) else 0.0)
    finally:
        for eng in engines.values():
            eng.close()
    return out


def serving_observability_bench(on_tpu: bool,
                                budget: Budget | None = None) -> dict:
    """Tracing-on vs tracing-off A/B on the byte-pinned
    shared_prefix_chat trace (ISSUE 17, schema>=10): the observability
    layer's two committed contracts.

    - `obs_greedy_parity` (floor exactly 1.0): greedy tokens with every
      request carrying a SAMPLED trace id must be byte-identical to the
      untraced engine's — telemetry reads timestamps, it must never
      touch the dataplane;
    - `obs_tpot_overhead_ratio` (floor 0.95): tpot_p50(off)/tpot_p50(on)
      on the identical replay — the retrospective-span design (one
      blake2b + a handful of dict writes per request, aggregate counters
      only in the decode loop) keeps the hot path within noise.

    The record also carries the span-export proof (per-kind counts, one
    trace id's full span-name chain, JSONL line count) and the live SLO
    burn summary computed from the tracing-on replay through
    obs.slo.SloBurnTracker — the section `--check` prints."""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.loadgen.runner import run_trace
    from kubeflow_tpu.obs.slo import SloBurnTracker
    from kubeflow_tpu.obs.trace import TRACER, new_trace_id
    from kubeflow_tpu.serving.llm import LLMEngine

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 256),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128, kv_quantize="int8",
                      quantize="int8")
        mini = None
        max_new = 32
    else:
        # f32 on CPU, same rationale as the kernel A/B: the parity claim
        # is the MACHINERY's exactness; the overhead ratio is a smoke on
        # toy dims (the on-TPU record re-measures it at serving dims)
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256, dtype=jnp.float32)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 32),
                      decode_chunk=4, prefix_cache=True,
                      prefix_cache_blocks=96, kv_quantize="int8")
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=3.0, rate_rps=5.0)
        max_new = 12
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("shared_prefix_chat")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": f"d{cfg.d_model}xL{cfg.n_layers}",
                   "dtype": str(getattr(cfg.dtype, "__name__", cfg.dtype)),
                   **{k: v for k, v in eng_kw.items()
                      if k != "prefix_cache"}},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
    }

    def expired() -> bool:
        return budget is not None and budget.expired()

    def replay(engine) -> dict:
        wall = scenario.trace.duration_s * 4.0 + 60.0
        if budget is not None:
            wall = max(5.0, min(wall, budget.remaining()))
        res = run_trace(engine, trace, max_wall_s=wall)
        tpots = [r.tpot_ms() for r in res["records"]]
        ttfts = [r.ttft_ms() for r in res["records"]]

        def pct(vals, q):
            vals = [v for v in vals if v is not None]
            return (round(float(np.percentile(vals, q)), 3)
                    if vals else None)

        agg = res["summary"]["aggregate"]
        return res["records"], {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "throughput_tok_per_s": agg["throughput_tok_per_s"],
            "completed": agg["completed"],
            "timed_out": res["timed_out"],
        }

    prev_rate = TRACER.sample_rate
    engines: dict = {}
    try:
        for label, rate in (("tracing_off", 0.0), ("tracing_on", 1.0)):
            if expired():
                out.setdefault("skipped_for_budget", []).append(label)
                continue
            TRACER.set_sample_rate(rate)
            t0 = time.perf_counter()
            eng = LLMEngine(params, cfg, **eng_kw)
            engines[label] = eng
            if rate > 0.0:
                # every replayed request carries a (sampled) trace id —
                # run_trace doesn't know about tracing, so the shim is
                # the router/runtime minting step's stand-in
                real_submit = eng.submit
                eng.submit = (lambda *a, **kw: real_submit(
                    *a, trace=new_trace_id(), **kw))
            eng.warmup()
            if rate > 0.0:
                TRACER.sink.clear()   # count replay spans only
            records, rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            out[label] = rec
            if rate > 0.0:
                spans = TRACER.sink.spans()
                kinds: dict[str, int] = {}
                for s in spans:
                    kinds[s.kind] = kinds.get(s.kind, 0) + 1
                chain = sorted({s.name for s in spans
                                if s.trace_id == spans[0].trace_id}) \
                    if spans else []
                out["spans"] = {
                    "total": len(spans),
                    "dropped": TRACER.sink.dropped,
                    "by_kind": dict(sorted(kinds.items())),
                    "one_trace_chain": chain,
                    "jsonl_lines": len(
                        TRACER.sink.export_jsonl().splitlines()),
                }
                slo = SloBurnTracker(
                    ttft_slo_ms=scenario.trace.ttft_slo_ms,
                    tpot_slo_ms=scenario.trace.tpot_slo_ms)
                for r in records:
                    slo.record(r.tenant, r.ttft_ms(), r.tpot_ms(),
                               completed=r.completed)
                out["slo_burn"] = slo.summary()
        if "tracing_on" in out and "tracing_off" in out \
                and out["tracing_on"]["tpot_p50_ms"] \
                and out["tracing_off"]["tpot_p50_ms"]:
            out["obs_tpot_overhead_ratio"] = round(
                out["tracing_off"]["tpot_p50_ms"]
                / out["tracing_on"]["tpot_p50_ms"], 4)
        if "tracing_on" in engines and "tracing_off" in engines \
                and not expired():
            # byte parity: traced (sampled) vs untraced generation —
            # probes cover a radix HIT and a chunked (> largest bucket)
            # prompt, the paths where telemetry reads the most state
            TRACER.set_sample_rate(1.0)
            eoff, eon = engines["tracing_off"], engines["tracing_on"]
            bt = eoff.prefix_block_tokens
            shared = [(i * 7) % (cfg.vocab_size - 1) + 1
                      for i in range(2 * bt + bt // 2)]
            probes = [shared + [17, 23, 5],
                      shared + [101, 9],
                      [7, 9, 11],
                      list(range(3, eng_kw["buckets"][-1] + 10))]
            out["obs_greedy_parity"] = 1.0 if all(
                eoff.generate(list(p), max_new)
                == eon.generate(list(p), max_new)
                for p in probes) else 0.0
    finally:
        TRACER.set_sample_rate(prev_rate)
        for eng in engines.values():
            eng.close()
    return out


def _runtime_stamp() -> dict:
    """The live runtime a (section of a) record was measured under:
    platform/device kind/device count/jax versions — so CPU-smoke
    numbers can never masquerade as hardware claims (ISSUE 14
    satellite). Delegates to obs.build.runtime_stamp (ISSUE 17: the
    same helper stamps /healthz `build`, so a committed record and a
    live endpoint can never disagree on what 'the runtime' means)."""
    from kubeflow_tpu.obs.build import runtime_stamp

    return runtime_stamp()


def _geometry_31b() -> dict:
    """The 31B-class int8 serving geometry (PAPERS.md 'Fine-Tuning and
    Serving Gemma 4 31B on Google Cloud TPU'): analytic sizing proving
    it CANNOT fit one v5e chip and how the tp×pp layout carries it —
    committed alongside the smoke so the record names the target the
    machinery exists for. The measured true-dims run rides the first
    on-TPU record (ROADMAP open item #1)."""
    cfg = llama.LlamaConfig(
        vocab_size=128256, d_model=6144, n_layers=64, n_heads=48,
        n_kv_heads=8, d_ff=20480, max_seq_len=2048, remat=False)
    abstract = jax.eval_shape(lambda: llama.init(jax.random.key(0), cfg))
    n_params = int(sum(math.prod(l.shape)
                       for l in jax.tree.leaves(abstract)))
    # weight-only int8 (embed stays bf16: it is a gather) ≈ 1 B/param
    embed_params = cfg.vocab_size * cfg.d_model
    int8_bytes = (n_params - embed_params) + 2 * embed_params
    from kubeflow_tpu.parallel.pipeline import stage_bounds

    pp = 4
    bounds = stage_bounds(cfg.n_layers, pp)
    per_layer = (n_params - 2 * embed_params) // cfg.n_layers
    # boundary stages carry the entry/exit tensors on top of their layer
    # slabs: stage 0 the bf16 embed (2 B/param — a gather, never int8),
    # the last stage the int8 lm_head (~1 B/param) — omitting them would
    # overstate the fit margin on exactly the two stages most likely to
    # OOM
    per_stage_bytes = [(hi - lo) * per_layer for lo, hi in bounds]
    per_stage_bytes[0] += 2 * embed_params
    per_stage_bytes[-1] += embed_params   # lm_head: vocab x d, int8
    return {
        "model": (f"llama-31b-class(d{cfg.d_model}xL{cfg.n_layers}"
                  f"/ff{cfg.d_ff}/gqa{cfg.n_heads}:{cfg.n_kv_heads}"
                  f"/v{cfg.vocab_size})"),
        "n_params": n_params,
        "int8_weight_gib": round(int8_bytes / 2**30, 2),
        "hbm_per_chip_gib": 16.0,
        "fits_one_chip": bool(int8_bytes < 16 * 2**30),
        "layout": f"tp4xpp{pp} over v5e-16",
        "per_stage_weight_gib": [round(b / 2**30, 2)
                                 for b in per_stage_bytes],
    }


#: the serving_multichip child's -c program (the serving_8b child's
#: watchdog pattern): stages an 8-device CPU backend BEFORE any device
#: query — the 8-device simulated mesh is the whole point of the smoke.
_MULTICHIP_CHILD_SRC = """\
import json, os, sys, threading, time
deadline = time.monotonic() + float(sys.argv[1])
ppid0 = os.getppid()
def _watchdog():
    while True:
        if time.monotonic() > deadline or os.getppid() != ppid0:
            os._exit(3)
        time.sleep(2.0)
threading.Thread(target=_watchdog, daemon=True).start()
import jax
jax.config.update('jax_platforms', 'cpu')
import bench
out = bench.serving_multichip_smoke(
    budget_s=max(30.0, deadline - time.monotonic() - 15.0))
print('RESULT ' + json.dumps(out))
"""


def serving_multichip_bench(on_tpu: bool,
                            budget: Budget | None = None) -> dict:
    """tp×pp stage-sharded serving record (ISSUE 14, ROADMAP #2).

    On a multi-device box the smoke runs in-process; otherwise it runs
    in a FRESH subprocess whose XLA backend is forced to 8 virtual CPU
    devices (the simulated v5e-16's test stand-in, the dryrun's
    pattern) — the parent's single-device backend cannot place a
    ("stage", "tensor") mesh. Committed per layout: TTFT/TPOT
    percentiles, decode throughput, and `pipeline_bubble_frac` from the
    stage-sharded engine's per-stage timestamps; plus `greedy_parity` —
    byte-exactness vs the single-program engine on the IDENTICAL pinned
    trace (int8 KV + chunked prefill + prefix-cache on), the schema>=8
    floor."""
    if jax.local_device_count() >= 8:
        return serving_multichip_smoke(
            on_tpu=on_tpu,
            budget_s=budget.remaining() if budget else None)
    import re
    import subprocess
    import sys

    remaining = budget.remaining() if budget is not None else 1200.0
    timeout_s = max(60.0, min(1200.0, remaining - 30.0))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.Popen(
        [sys.executable, "-c", _MULTICHIP_CHILD_SRC, str(timeout_s)],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        start_new_session=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=timeout_s + 30.0)
    except subprocess.TimeoutExpired:
        _kill_process_group(proc)
        raise RuntimeError(
            f"multichip child exceeded its {timeout_s:.0f}s budget "
            "(process group killed)")
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"multichip subprocess rc={proc.returncode}: "
                       f"{err[-500:]}")


def serving_multichip_smoke(on_tpu: bool = False,
                            budget_s: float | None = None) -> dict:
    """The measured half of serving_multichip_bench, running wherever a
    >=8-device backend exists (the CPU child, or a real slice).

    One byte-pinned shared-prefix trace (chunked long prompts + radix
    reuse + int8 KV — every correctness-critical serving path at once)
    replayed greedy through (a) the single-program engine and (b) each
    tp×pp stage-sharded layout; outputs compared token-for-token. The
    TPU true-dims 31B run is NOT this smoke — `geometry_31b` records the
    target analytically until open item #1 lands a hardware record."""
    import numpy as np

    from kubeflow_tpu.loadgen import (generate_trace, load_scenario,
                                      miniature, trace_sha256)
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.multichip import StageShardedEngine

    deadline = (time.monotonic() + budget_s) if budget_s else None

    def left() -> float:
        return (deadline - time.monotonic()) if deadline else 1e9

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=3584, max_seq_len=1024, remat=False)
        eng_kw = dict(n_slots=8, max_len=512, buckets=(64, 256),
                      decode_chunk=8, prefix_cache=True,
                      prefix_cache_blocks=128, kv_quantize="int8")
        mini = None
        max_new = 32
    else:
        # f32 on CPU: cross-layout bf16 accumulation-order drift would
        # make byte parity a coin flip at toy dims; the committed claim
        # is the MACHINERY's exactness, measured in a dtype where the
        # comparison is meaningful (the dryrun serving parity's choice)
        import jax.numpy as jnp

        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=64, n_layers=4, n_heads=8,
            n_kv_heads=4, d_ff=128, max_seq_len=256,
            attention_impl="xla", remat=False, dtype=jnp.float32)
        eng_kw = dict(n_slots=4, max_len=160, buckets=(8, 32),
                      decode_chunk=4, prefix_cache=True,
                      prefix_cache_blocks=96, kv_quantize="int8")
        mini = dict(vocab=cfg.vocab_size, max_prompt_len=60,
                    duration_s=3.0, rate_rps=5.0)
        max_new = 12
    params = llama.init(jax.random.key(0), cfg)
    scenario = load_scenario("shared_prefix_chat")
    if mini is not None:
        scenario = miniature(scenario, **mini)
    trace = generate_trace(scenario.trace)
    out: dict = {
        "engine": {"model": f"d{cfg.d_model}xL{cfg.n_layers}",
                   "dtype": str(cfg.dtype.__name__ if hasattr(
                       cfg.dtype, "__name__") else cfg.dtype),
                   **{k: v for k, v in eng_kw.items()
                      if k != "prefix_cache"}},
        "scenario": scenario.name,
        "trace_sha256": trace_sha256(trace),
        "n_requests": len(trace.requests),
        "geometry_31b": _geometry_31b(),
        "runtime": _runtime_stamp(),
    }
    if not on_tpu:
        out["note"] = ("8-device CPU smoke: parity + bubble accounting "
                       "are the committed claims; TTFT/TPOT gains await "
                       "the on-TPU record (stages time-share the host)")

    def pct(vals, q):
        vals = [v for v in vals if v is not None]
        return round(float(np.percentile(vals, q)), 3) if vals else None

    def replay(engine) -> tuple[dict, dict]:
        """Greedy replay of the pinned trace (arrival order, burst
        submit — greedy outputs are arrival-timing-independent, which
        is what makes the parity comparison well-defined). Returns
        (outputs by request index, latency record)."""
        order = sorted(trace.requests, key=lambda r: (r.arrival_s,
                                                      r.index))
        t0 = time.monotonic()
        rids = [(req.index, engine.submit(
            list(req.prompt), min(req.max_new_tokens, max_new), 0.0,
            tenant=req.tenant)) for req in order]
        engine.run_until_idle()
        wall = time.monotonic() - t0
        outs: dict[int, list[int]] = {}
        ttfts, tpots = [], []
        for idx, rid in rids:
            tm = engine.request_timing(rid)
            outs[idx] = engine.result(rid)
            if tm["queue_wait_ms"] is not None \
                    and tm["prefill_ms"] is not None:
                ttfts.append(tm["queue_wait_ms"] + tm["prefill_ms"])
            if tm["decode_ms"] is not None and tm["n_tokens"] > 1:
                tpots.append(tm["decode_ms"] / (tm["n_tokens"] - 1))
            engine.release(rid)
        toks = sum(len(v) for v in outs.values())
        return outs, {
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
            "tpot_p50_ms": pct(tpots, 50), "tpot_p99_ms": pct(tpots, 99),
            "decode_tok_per_s": round(toks / max(wall, 1e-9), 1),
            "wall_s": round(wall, 2),
            "completed": len(outs),
        }

    # single-program reference (bare engine: the raw-engine perf point
    # the dataplane lint sanctions for bench.py)
    ref = LLMEngine(params, cfg, **eng_kw)
    t0 = time.perf_counter()
    ref.warmup()
    ref_outs, rec = replay(ref)
    rec["warmup_s"] = round(time.perf_counter() - t0, 1)
    out["single"] = rec
    # seeded reference for the overlap parity probe (ISSUE 20):
    # captured before the ref closes so the overlapped layouts compare
    # the SAMPLED path too, not just greedy
    seed_probe = [(i * 7) % (cfg.vocab_size - 1) + 1 for i in range(9)]
    ref_seeded = ref.generate(list(seed_probe), max_new,
                              temperature=0.8, seed=99)
    ref.close()
    del ref

    layouts = [("tp2xpp2", dict(stage=2, tensor=2)),
               ("tp1xpp4", dict(stage=4, tensor=1))]
    out["layouts"] = {}
    parities = []
    for name, geo in layouts:
        if left() < 60.0 and out["layouts"]:
            out.setdefault("skipped_for_budget", []).append(name)
            continue
        eng = StageShardedEngine(params, cfg, stage_timing=True,
                                 **geo, **eng_kw)
        try:
            t0 = time.perf_counter()
            eng.warmup()
            outs, rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            parity = (outs == ref_outs)
            parities.append(parity)
            pipe = eng.pipeline_perf()
            rec.update({
                "greedy_parity": bool(parity),
                "mesh": eng.mesh_info(),
                "pipeline_bubble_frac": pipe["bubble_frac"],
                "schedule_bubble_frac": pipe["schedule_bubble_frac"],
                "pipeline": pipe,
                "prefix_cache_hits": eng.metrics().get("prefix_hits"),
            })
            out["layouts"][name] = rec
        finally:
            eng.close()
            del eng
    # the committed contract fields (floor multichip_greedy_parity 1.0):
    # parity over EVERY layout that ran, bubble from the first layout
    out["greedy_parity"] = bool(parities and all(parities))
    first = next(iter(out["layouts"].values()), None)
    if first is not None:
        out["pipeline_bubble_frac"] = first["pipeline_bubble_frac"]
        if out["single"]["decode_tok_per_s"]:
            out["multichip_decode_ratio"] = round(
                first["decode_tok_per_s"]
                / out["single"]["decode_tok_per_s"], 4)
    # -- overlapped-wavefront re-measure (ISSUE 20, schema>=12): the
    # SAME layouts under stage_schedule="overlapped" — stages drain
    # their step queues without the per-program global barrier, and the
    # perf accounting switches to dispatch→drain occupancy windows. The
    # committed contract: byte parity preserved (greedy AND seeded —
    # the schedule moves WHEN stages block, never what they compute)
    # and the measured bubble no worse than this run's sync accounting
    # (the r13 record committed 0.72 sync).
    ov: dict = {"layouts": {}}
    out["overlap"] = ov
    ov_parities: list[bool] = []
    ov_seeded: list[bool] = []
    for name, geo in layouts:
        if left() < 60.0 and ov["layouts"]:
            ov.setdefault("skipped_for_budget", []).append(name)
            continue
        eng = StageShardedEngine(params, cfg, stage_timing=True,
                                 stage_schedule="overlapped",
                                 **geo, **eng_kw)
        try:
            t0 = time.perf_counter()
            eng.warmup()
            outs, rec = replay(eng)
            rec["warmup_s"] = round(time.perf_counter() - t0, 1)
            parity = (outs == ref_outs)
            ov_parities.append(parity)
            ov_seeded.append(
                eng.generate(list(seed_probe), max_new, temperature=0.8,
                             seed=99) == ref_seeded)
            pipe = eng.pipeline_perf()
            rec.update({
                "greedy_parity": bool(parity),
                "schedule": pipe["schedule"],
                "pipeline_bubble_frac": pipe["bubble_frac"],
                "pipeline": pipe,
            })
            ov["layouts"][name] = rec
        finally:
            eng.close()
            del eng
    ov["greedy_parity"] = bool(ov_parities and all(ov_parities))
    ov["seeded_parity"] = bool(ov_seeded and all(ov_seeded))
    first_ov = next(iter(ov["layouts"].values()), None)
    if first_ov is not None and first is not None:
        ov["pipeline_bubble_frac"] = first_ov["pipeline_bubble_frac"]
        ov["sync_bubble_frac"] = first["pipeline_bubble_frac"]
        ov["r13_sync_baseline"] = 0.72
        ov["bubble_not_worse"] = bool(
            ov["pipeline_bubble_frac"] is not None
            and ov["sync_bubble_frac"] is not None
            and ov["pipeline_bubble_frac"] <= ov["sync_bubble_frac"])
    return out


def rl_anakin_bench(on_tpu: bool) -> dict:
    """Podracer/Anakin RL point (ROADMAP #5, the r8 rl/ subsystem):

    - sustained env-steps/s of the fused rollout+PPO step (the whole
      acting+learning loop is ONE compiled program — this number is the
      on-device RL throughput the Podracer paper optimizes for);
    - a seeded CartPole reward curve with a committed threshold (the
      same seed is pinned bitwise by tests/test_rl_anakin.py, so the
      recorded curve is reproducible by construction);
    - a solo-vs-co-located interference record: the learner and a live
      serving engine share the chip, each measured alone and packed
      (PAPERS.md "Exploring the limits of Concurrency in ML Training on
      Google TPUs"), plus the gang scheduler PackingPolicy's decision on
      that record — the committed input that teaches the scheduler
      whether rl-learner/llm-serving may share a chip.
    """
    from kubeflow_tpu.rl.anakin import AnakinLearner
    from kubeflow_tpu.rl.config import REWARD_METRIC, AnakinConfig
    from kubeflow_tpu.serving.llm import LLMEngine

    cfg = AnakinConfig(
        env="cartpole",
        n_envs=2048 if on_tpu else 64,
        rollout_len=64 if on_tpu else 32,
        hidden=(64, 64), learning_rate=3e-3, seed=0)
    learner = AnakinLearner(cfg)
    state = learner.init(0)
    state, steps_per_s = learner.measure_steps_per_s(
        state, iters=20 if on_tpu else 10)

    # committed seeded reward curve (fresh state so the curve is the
    # canonical from-init trajectory, not continuation of the perf run)
    curve_state = learner.init(0)
    _, hist = learner.train(curve_state, 150, log_every=25)
    threshold = 100.0   # mean balanced steps; random policy sits at ~20
    curve = [{"update": h["update"],
              REWARD_METRIC: round(h[REWARD_METRIC], 2)} for h in hist]
    out = {
        "env": cfg.env, "n_envs": cfg.n_envs,
        "rollout_len": cfg.rollout_len,
        "env_steps_per_update": learner.env_steps_per_update(),
        "env_steps_per_s": round(steps_per_s, 1),
        "updates_per_s": round(
            steps_per_s / learner.env_steps_per_update(), 2),
        "seed": cfg.seed,
        "reward_curve": curve,
        "reward_threshold": threshold,
        "reward_reached": bool(hist[-1][REWARD_METRIC] >= threshold),
    }
    try:
        out["interference"] = _rl_interference_point(learner, state, on_tpu,
                                                     LLMEngine)
    except Exception as e:   # best-effort, like the other extras
        out["interference_error"] = f"{type(e).__name__}: {e}"
    return out


def _rl_interference_point(learner, state, on_tpu: bool, engine_cls) -> dict:
    """Solo/solo/packed rates for (Anakin learner, serving engine) on one
    chip, and the PackingPolicy verdict the gang scheduler would apply."""
    from kubeflow_tpu.control.scheduler import PackingPolicy
    from kubeflow_tpu.rl.packing import measure_interference

    cfg = llama.LlamaConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=3584, max_seq_len=1024, remat=False,
    ) if on_tpu else llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    n_slots = 8 if on_tpu else 2
    new_tokens = 32 if on_tpu else 8
    prompt = list(range(1, 100)) if on_tpu else [3, 7, 11]
    engine = engine_cls(params, cfg, n_slots=n_slots,
                        max_len=256 if on_tpu else 64,
                        buckets=(128,) if on_tpu else (16,))

    cur = {"state": state}

    def learner_chunk() -> float:
        cur["state"], metrics = learner.step(cur["state"])
        float(metrics["loss"])   # force completion (fetch = sync)
        return float(learner.env_steps_per_update())

    def serve_chunk() -> float:
        rids = [engine.submit(prompt, new_tokens) for _ in range(n_slots)]
        engine.run_until_idle()
        for r in rids:
            engine.release(r)
        return float(n_slots * new_tokens)

    # warmup INSIDE the try: an OOM mid-warmup (shared chip) must still
    # close() the engine — it is cyclic, so gc alone does not drop its
    # KV cache/params HBM promptly, and the rest of the bench would run
    # against a needlessly pinned chip
    try:
        engine.warmup()
        record = measure_interference(
            "rl-learner", learner_chunk, "llm-serving", serve_chunk,
            seconds=4.0 if on_tpu else 1.5,
            unit_a="env_steps/s", unit_b="tok/s")
    finally:
        engine.close()
        del engine, params
    policy = PackingPolicy()
    decision = policy.learn("rl-learner", "llm-serving", record.to_json())
    return {**record.to_json(), "decision": decision.to_json(),
            "policy": {"min_combined_retention":
                       policy.min_combined_retention,
                       "min_each_retention": policy.min_each_retention,
                       "max_per_chip": policy.max_per_chip}}


if __name__ == "__main__":
    import sys

    if "--check" in sys.argv:
        _record = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_EXTRAS.json")
        fails = check_floors(_record)
        for f_ in fails:
            print(f"FLOOR FAIL: {f_}", file=sys.stderr)
        gated = gated_out_floors(_record)
        if gated:
            # an old record passing --check is NOT attesting these
            # contracts — say so explicitly instead of silently passing
            print(json.dumps({"schema_gated_out": gated}))
        burn = slo_burn_summary(_record)
        if burn is not None:
            # the validated record's SLO-burn picture rides --check so
            # the gate's output says not just "floors hold" but how far
            # the recorded serving run sat from its error budget
            print(json.dumps({"slo_burn": burn}))
        print(json.dumps({"floors": "fail" if fails else "pass",
                          "n_failures": len(fails),
                          "n_schema_gated_out": len(gated)}))
        sys.exit(1 if fails else 0)
    if "serving_multichip" in sys.argv:
        # section-only entry (the ISSUE 14 smoke): run the multichip
        # record standalone and print it — operators and the child
        # subprocess share this path
        out = serving_multichip_bench(
            "tpu" in str(jax.devices()[0].device_kind).lower(), Budget())
        print(json.dumps({"serving_multichip": out}, indent=1))
        sys.exit(0)
    if "serving_kernels" in sys.argv:
        # section-only entry (the ISSUE 15 A/B): run the xla-vs-flash
        # kernel record standalone and print it
        out = serving_kernels_bench(
            "tpu" in str(jax.devices()[0].device_kind).lower(), Budget())
        print(json.dumps({"serving_kernels": out}, indent=1))
        sys.exit(0)
    if "serving_prefill_kernels" in sys.argv:
        # section-only entry (the ISSUE 20 A/B): run the xla-vs-flash
        # chunked-prefill record standalone and print it
        out = serving_prefill_kernels_bench(
            "tpu" in str(jax.devices()[0].device_kind).lower(), Budget())
        print(json.dumps({"serving_prefill_kernels": out}, indent=1))
        sys.exit(0)
    if "serving_observability" in sys.argv:
        # section-only entry (the ISSUE 17 A/B): tracing-on vs
        # tracing-off parity/overhead record standalone
        out = serving_observability_bench(
            "tpu" in str(jax.devices()[0].device_kind).lower(), Budget())
        print(json.dumps({"serving_observability": out}, indent=1))
        sys.exit(0)
    if "serving_paged_kv" in sys.argv:
        # section-only entry (the ISSUE 19 A/B): slab-vs-paged
        # equal-KV-bytes record standalone
        out = serving_paged_kv_bench(
            "tpu" in str(jax.devices()[0].device_kind).lower(), Budget())
        print(json.dumps({"serving_paged_kv": out}, indent=1))
        sys.exit(0)
    main()
