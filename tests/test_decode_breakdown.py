"""Decode-step attribution (r6 tentpole part a): serving_decode_breakdown
splits one batched decode step into the five buckets a serving step is
made of — weight read / attention+KV update / sampling+penalties /
dispatch RTT / host fetch+replay — by timing the engine's own compiled
program against single-stage-stripped variants. The numbers here are CPU
toy numbers; what the fast lane pins is the CONTRACT: the buckets exist,
are non-negative, sum to the measured device step, and profiling leaves
the engine serviceable."""

import os

import jax
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine
from kubeflow_tpu.training.profiling import serving_decode_breakdown

BUCKETS = ("weight_read", "attention_kv_update", "sampling_penalties",
           "dispatch_rtt_per_step", "host_fetch_replay_per_step")


@pytest.fixture(scope="module")
def engine():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    eng = LLMEngine(params, cfg, n_slots=2, max_len=64, buckets=(16,),
                    decode_chunk=4)
    eng.warmup()
    return eng


def _assert_device_partition(bd):
    """The STRUCTURE of the device buckets, not a ratio of CPU timings:
    attention and sampling are differentials of separately timed runs
    (full - nosample, nosample - weight read), each clamped at 0. Where
    neither clamp fired the three sum to the device step to the
    rounding; a clamp (timing noise on a loaded CPU: a stripped variant
    that ran slower than the full program) can only push the sum above
    it, never under."""
    b = bd["buckets_ms"]
    device_sum = (b["weight_read"] + b["attention_kv_update"]
                  + b["sampling_penalties"])
    slack = 1e-3    # four buckets rounded to 1e-4 ms each
    assert device_sum >= bd["device_step_ms"] - slack
    if b["attention_kv_update"] > 0 and b["sampling_penalties"] > 0:
        assert device_sum == pytest.approx(bd["device_step_ms"], abs=slack)


def test_breakdown_buckets_account_for_the_device_step(engine):
    engine.perf_counters(reset=True)
    baseline = engine.generate([1, 2, 3], 8)   # populate host counters
    bd = serving_decode_breakdown(engine, steps=2, iters=3)
    b = bd["buckets_ms"]
    assert set(BUCKETS) <= set(b)
    for name in BUCKETS:
        assert b[name] is None or b[name] >= 0, (name, b)
    # the three device buckets are a PARTITION of the measured device
    # step (sampling and attention are differentials against it)
    _assert_device_partition(bd)
    # host buckets came from the live counters populated above
    assert b["host_fetch_replay_per_step"] is not None
    assert bd["perf_counters"]["decode_steps"] > 0
    assert bd["weight_read_bytes"] > 0
    # profiling resets slot state like warmup: the engine still serves,
    # and deterministically so
    assert engine.generate([1, 2, 3], 8) == baseline


def test_breakdown_attn_subattribution_unquantized(engine):
    """attn_kernel/attn_dequant (ISSUE 15 satellite) sub-attribute the
    attention+KV bucket: the attention probe runs the selected impl
    over the live span, and an UNQUANTIZED cache's dequant cost is 0.0
    by definition (None is reserved for engines whose cache isn't a
    probe-able single-program slab)."""
    bd = serving_decode_breakdown(engine, steps=1, iters=2)
    b = bd["buckets_ms"]
    assert "attn_kernel" in b and "attn_dequant" in b
    assert b["attn_kernel"] is not None and b["attn_kernel"] >= 0
    assert b["attn_dequant"] == 0.0
    # prefill_attn (ISSUE 20 satellite) prices one prefill-attention
    # chunk through the selected prefill impl on the same live cache
    assert "prefill_attn" in b
    assert b["prefill_attn"] is not None and b["prefill_attn"] >= 0
    # sub-attribution never perturbs the bucket PARTITION contract
    _assert_device_partition(bd)


def test_breakdown_attn_dequant_measured_on_int8_cache():
    """An int8 KV engine gets a real (>= 0, not-None) dequant
    sub-bucket — the read+convert tax the fused kernel folds into its
    block loads."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    eng = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                    decode_chunk=2, kv_quantize="int8")
    bd = serving_decode_breakdown(eng, steps=1, iters=2)
    b = bd["buckets_ms"]
    assert b["attn_dequant"] is not None and b["attn_dequant"] >= 0
    assert b["attn_kernel"] is not None and b["attn_kernel"] >= 0


def test_breakdown_kv_gather_none_on_slab(engine):
    """kv_gather (ISSUE 19 satellite) prices the block-table
    indirection on the decode-span KV read — slab engines read
    contiguously by construction, so the bucket is None there."""
    bd = serving_decode_breakdown(engine, steps=1, iters=2)
    assert "kv_gather" in bd["buckets_ms"]
    assert bd["buckets_ms"]["kv_gather"] is None


def test_breakdown_kv_gather_measured_on_paged_engine():
    """A paged engine gets a real kv_gather number (gather-through-
    tables minus contiguous read of the same volume), the attention
    probes read through the live block tables, and the kv_handoff
    probe — which times the slab slice-out program — stays None:
    paged banking is refcount bookkeeping, not a copy."""
    from kubeflow_tpu.serving.paged import PagedLLMEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    eng = PagedLLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                         decode_chunk=2, kv_quantize="int8",
                         prefix_cache=True)
    try:
        bd = serving_decode_breakdown(eng, steps=1, iters=2)
        b = bd["buckets_ms"]
        assert isinstance(b["kv_gather"], float) and b["kv_gather"] >= 0
        assert b["attn_kernel"] is not None and b["attn_kernel"] >= 0
        assert b["attn_dequant"] is not None and b["attn_dequant"] >= 0
        # the prefill probe reads through the same live block tables
        assert b["prefill_attn"] is not None and b["prefill_attn"] >= 0
        assert b["kv_handoff"] is None
        # profiling leaves the paged engine serviceable
        assert len(eng.generate([1, 2, 3], 6)) == 6
    finally:
        eng.close()


def test_breakdown_records_analytic_floor_when_bandwidth_given(engine):
    bd = serving_decode_breakdown(engine, steps=1, iters=2, hbm_gbps=100.0)
    assert bd["weight_read_floor_ms"] > 0
    assert bd["weight_read_frac_of_peak"] > 0


def test_breakdown_clamps_steps_on_small_cache():
    """A cache too small for the default chunk x iters KV writes clamps
    steps (then iters) instead of silently profiling a degenerate
    everything-clamped-at-max_len program state."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    eng = LLMEngine(params, cfg, n_slots=2, max_len=32, buckets=(8,),
                    decode_chunk=16)
    bd = serving_decode_breakdown(eng, iters=2)
    assert bd["steps"] < 16                     # clamped to fit max_len
    assert (2 * bd["iters"] + 4) * bd["steps"] + 2 <= 32
    assert bd["buckets_ms"]["weight_read"] >= 0


def test_breakdown_captures_profiler_trace(engine, tmp_path):
    trace_dir = str(tmp_path / "decode_trace")
    bd = serving_decode_breakdown(engine, steps=1, iters=2,
                                  trace_dir=trace_dir)
    # jax.profiler capture is best-effort (some sandboxes refuse it) but
    # must be RECORDED either way: a dir marker or an explicit error
    assert ("trace_dir" in bd) != ("trace_error" in bd)
    if "trace_dir" in bd:
        assert os.path.exists(os.path.join(trace_dir, "PROFILE_DONE"))
        assert os.listdir(trace_dir)


def test_breakdown_pipeline_bubble_none_on_single_program(engine):
    """The pipeline_bubble bucket (ISSUE 14 satellite) exists on every
    breakdown but is None for single-program engines — the bucket only
    measures a stage pipeline's idle wall."""
    bd = serving_decode_breakdown(engine, steps=2, iters=2)
    assert "pipeline_bubble" in bd["buckets_ms"]
    assert bd["buckets_ms"]["pipeline_bubble"] is None
    assert "pipeline" not in bd


@pytest.mark.slow
def test_breakdown_pipeline_bubble_on_stage_sharded_engine():
    """On a stage-sharded engine with stage_timing armed, the bucket
    carries measured per-stage idle wall per decode step and the
    `pipeline` sub-record rides the breakdown."""
    from kubeflow_tpu.serving.multichip import StageShardedEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    eng = StageShardedEngine(params, cfg, stage=2, stage_timing=True,
                             n_slots=2, max_len=64, buckets=(16,),
                             decode_chunk=4)
    try:
        bd = serving_decode_breakdown(eng, steps=2, iters=2)
        assert bd["buckets_ms"]["pipeline_bubble"] is not None
        assert bd["buckets_ms"]["pipeline_bubble"] >= 0
        assert bd["pipeline"]["stages"] == 2
        assert bd["pipeline"]["steps"] > 0
        # the pipeline record names its schedule kind (sync is default)
        assert bd["pipeline"]["schedule"] == "sync"
        # kernel probes are gated to single-program slab/pool engines
        assert bd["buckets_ms"]["prefill_attn"] is None
        # profiling leaves the engine serviceable (warmup-style reset)
        assert len(eng.generate([1, 2, 3], 6)) == 6
    finally:
        eng.close()
