"""Loadgen scenario runner + SLO accounting.

The SLO math is pinned against a HAND-COMPUTED miniature record set (the
ISSUE's verification bar: every number below is derivable with a pencil).
Engine-backed replays run MINIATURE traces in the fast lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.loadgen.control import (MEASURED_CHUNK_TTFT_MS,
                                          SLOController, pick_decode_chunk)
from kubeflow_tpu.loadgen.runner import run_scenario, run_trace
from kubeflow_tpu.loadgen.scenarios import load_scenario, miniature
from kubeflow_tpu.loadgen.slo import RequestRecord, jain_index, summarize
from kubeflow_tpu.loadgen.trace import TraceConfig, generate_trace
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine


# -- pure SLO math (hand-computed) ------------------------------------------

def _hand_records():
    """Four requests, 10s window, SLO = 100ms TTFT / 50ms TPOT:
    - A/r0: ttft 50ms, tpot (0.5-0.05)/9 = 50ms -> MEETS (boundary).
    - A/r1: ttft 200ms -> misses TTFT.
    - B/r2: rejected at admission.
    - B/r3: client cancelled after 4 tokens."""
    return [
        RequestRecord(0, "A", 0.0, 10, submit_s=0.0, first_token_s=0.05,
                      finish_s=0.5, n_tokens=10, finish_reason="stop"),
        RequestRecord(1, "A", 1.0, 10, submit_s=1.0, first_token_s=1.2,
                      finish_s=1.4, n_tokens=10, finish_reason="length"),
        RequestRecord(2, "B", 2.0, 20),
        RequestRecord(3, "B", 3.0, 10, submit_s=3.0, first_token_s=3.05,
                      finish_s=3.3, n_tokens=4, finish_reason="cancelled",
                      client_cancelled=True),
    ]


def test_slo_summary_matches_hand_computation():
    s = summarize(_hand_records(), ttft_slo_ms=100.0, tpot_slo_ms=50.0,
                  duration_s=10.0)
    agg = s["aggregate"]
    assert agg["n_requests"] == 4
    assert agg["completed"] == 2
    assert agg["rejected"] == 1
    assert agg["client_cancelled"] == 1
    # met=1 (r0 only) over denom = 4 offered - 1 client-cancelled = 3
    assert agg["slo_attainment"] == round(1 / 3, 4)
    # delivered 10+10+0+4 = 24 tokens over 10s; goodput counts r0 only
    assert agg["throughput_tok_per_s"] == 2.4
    assert agg["goodput_tok_per_s"] == 1.0
    # offered 10+10+20+10 = 50 tokens -> saturation 24/50
    assert agg["saturation"] == 0.48
    ta, tb = s["per_tenant"]["A"], s["per_tenant"]["B"]
    assert ta["slo_attainment"] == 0.5          # 1 met of 2
    assert ta["service_ratio"] == 1.0           # 20/20
    assert tb["service_ratio"] == round(4 / 30, 4)
    assert tb["slo_attainment"] == 0.0          # met 0 of denom 1
    assert ta["ttft_p50_ms"] == 125.0           # median of 50 and 200
    assert ta["tpot_p50_ms"] == round(
        (50.0 + (0.2 / 9) * 1e3) / 2, 3)        # r0 50ms, r1 22.22ms
    assert agg["fairness_jain"] == jain_index([1.0, round(4 / 30, 4)])
    assert agg["fairness_min_over_max"] == round(round(4 / 30, 4) / 1.0, 4)


def test_jain_index_extremes():
    assert jain_index([1.0, 1.0, 1.0]) == 1.0
    assert jain_index([1.0, 0.0, 0.0]) == round(1 / 3, 4)
    assert jain_index([]) is None
    assert jain_index([0.0, 0.0]) == 1.0


def test_ttft_tpot_boundary_semantics():
    r = RequestRecord(0, "A", 0.0, 4, submit_s=0.0, first_token_s=0.1,
                      finish_s=0.1, n_tokens=1, finish_reason="stop")
    assert r.tpot_ms() is None        # single token: no inter-token gap
    assert r.meets_slo(100.0, 1.0)    # ttft exactly at the SLO passes
    assert not r.meets_slo(99.9, 1.0)


# -- control hook ------------------------------------------------------------

def test_pick_decode_chunk_from_measured_table():
    assert pick_decode_chunk(500.0) == 8      # both fit -> largest
    assert pick_decode_chunk(250.0) == 4      # only chunk 4 meets 250ms
    assert pick_decode_chunk(100.0) == 4      # none fit -> smallest tabled
    assert pick_decode_chunk(500.0, max_chunk=4) == 4
    assert MEASURED_CHUNK_TTFT_MS[4] < MEASURED_CHUNK_TTFT_MS[8]


class _FakeEngine:
    def __init__(self, chunk=8):
        self.decode_chunk = chunk
        self.decode_chunk_max = chunk

    def set_decode_chunk(self, c):
        self.decode_chunk = max(1, min(int(c), self.decode_chunk_max))
        return self.decode_chunk


def test_slo_controller_halves_on_miss_and_recovers():
    eng = _FakeEngine(8)
    c = SLOController(100.0, interval_s=1.0)
    c.maybe_adjust(eng, 0.0)          # arms the interval clock
    c.observe(400.0)
    assert c.maybe_adjust(eng, 1.5) == 4
    c.observe(400.0)                  # EMA still far over target
    assert c.maybe_adjust(eng, 3.0) == 2
    for _ in range(30):
        c.observe(10.0)               # now comfortably under target
    assert c.maybe_adjust(eng, 4.5) == 4
    assert eng.decode_chunk == 4
    assert [p["chunk"] for p in c.trajectory] == [4, 2, 4]


def test_slo_controller_respects_interval_and_warm_clamp():
    eng = _FakeEngine(8)
    c = SLOController(100.0, interval_s=5.0)
    c.maybe_adjust(eng, 0.0)
    c.observe(400.0)
    assert c.maybe_adjust(eng, 1.0) is None   # inside the interval
    for _ in range(50):
        c.observe(1.0)
    assert c.maybe_adjust(eng, 6.0) is None   # already at the warmed max
    assert eng.decode_chunk == 8


# -- engine-backed miniature replays (fast lane) -----------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = llama.LlamaConfig(vocab_size=128, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=160, attention_impl="xla",
                            dtype=jnp.float32, remat=False)
    params = llama.init(jax.random.key(0), cfg)
    eng = LLMEngine(params, cfg, n_slots=2, max_len=128, buckets=(8, 16),
                    decode_chunk=8)
    eng.warmup()
    return eng


def test_steady_miniature_end_to_end(engine):
    s = miniature(load_scenario("steady"), vocab=128, max_prompt_len=14,
                  duration_s=2.0, rate_rps=5.0)
    out = run_scenario(engine, s)
    agg = out["aggregate"]
    assert not out["timed_out"]
    assert agg["completed"] == agg["n_requests"] > 0
    assert agg["rejected"] == 0
    # no EOS on random weights: every budget is delivered in full
    assert agg["saturation"] == 1.0
    assert 0.0 <= agg["slo_attainment"] <= 1.0
    assert "t0" in out["per_tenant"]
    assert out["trace_sha256"] == run_scenario(engine, s)["trace_sha256"]
    # the engine is fully drained and released
    m = engine.metrics()
    assert m["active"] == 0 and m["queued"] == 0


def test_cancellation_storm_frees_capacity(engine):
    """Every client disconnects shortly after arrival while the 2-slot
    engine is saturated: queued and mid-decode requests both get cut,
    goodput < throughput, and the engine drains clean."""
    cancelled_before = engine.metrics()["cancelled"]
    # a near-instant burst (400 rps x 0.1 s) against 2 slots builds a
    # backlog the ~10-50 ms disconnects reliably cut into — the tiny CPU
    # engine decodes a 50-token budget in ~7 ms, so per-request delays
    # sized for the full-scale scenario would never fire here
    cfg = TraceConfig(seed=9, duration_s=0.1, base_rate_rps=400.0,
                      n_tenants=2, prompt_len_mix=((2, 10, 1.0),),
                      output_len=(40, 60), vocab=128, cancel_frac=1.0,
                      cancel_after_s=(0.01, 0.05), ttft_slo_ms=2000.0,
                      tpot_slo_ms=500.0)
    trace = generate_trace(cfg)
    assert len(trace.requests) >= 20
    res = run_trace(engine, trace)
    agg = res["summary"]["aggregate"]
    assert agg["client_cancelled"] > 0
    assert engine.metrics()["cancelled"] > cancelled_before
    # cancelled requests deliver partial (or zero) tokens: demand is NOT
    # fully served, and none of it counts as goodput
    assert agg["saturation"] < 1.0
    assert agg["goodput_tok_per_s"] <= agg["throughput_tok_per_s"]
    m = engine.metrics()
    assert m["active"] == 0 and m["queued"] == 0


def test_multi_tenant_fairness_accounting(engine):
    """Three skewed tenants through share caps: per-tenant tables exist
    for every tenant that offered work and the fairness metrics are
    populated."""
    s = load_scenario("multi_tenant_lora")
    mini = miniature(s, vocab=128, max_prompt_len=14, duration_s=2.0,
                     rate_rps=8.0)
    # the shared tiny engine has no adapters loaded: strip the adapter
    # fleet (tenancy, caps, and skew are what this test exercises)
    mini = mini.replace(trace=mini.trace.replace(adapters=(),
                                                n_tenants=3))
    out = run_scenario(engine, mini)
    agg = out["aggregate"]
    assert agg["completed"] + agg["rejected"] + agg["client_cancelled"] \
        <= agg["n_requests"]
    assert len(out["per_tenant"]) >= 2
    assert agg["fairness_jain"] is not None
    assert agg["fairness_min_over_max"] is not None
    m = engine.metrics()
    assert m["active"] == 0 and m["queued"] == 0


def test_runner_rejects_missing_adapters(engine):
    s = miniature(load_scenario("multi_tenant_lora"), vocab=128,
                  max_prompt_len=14, duration_s=2.0)
    with pytest.raises(ValueError, match="adapters"):
        run_trace(engine, generate_trace(s.trace))


def test_tenant_ids_unique_and_bounded(engine):
    """Distinct tenant names mint distinct scheduler ids (the id
    assignment is atomic under _submit_lock), and past MAX_TENANTS new
    names degrade to the shared anonymous id instead of growing the map
    without bound."""
    with engine._submit_lock:
        ids = [engine._tenant_id(f"u{i}") for i in range(5)]
    assert len(set(ids)) == 5
    engine.MAX_TENANTS = len(engine._tenant_idx)   # instance shadow
    try:
        with engine._submit_lock:
            assert engine._tenant_id("overflow-tenant") == 0
            assert engine._tenant_id("u0") == ids[0]   # existing: stable
        assert "overflow-tenant" not in engine._tenant_idx
    finally:
        del engine.MAX_TENANTS


def test_set_decode_chunk_applies_and_clamps(engine):
    assert engine.set_decode_chunk(4) == 4
    assert engine.metrics()["decode_chunk"] == 4
    # a request still decodes correctly at the re-picked chunk
    rid = engine.submit([3, 5, 7], 6)
    engine.run_until_idle()
    assert len(engine.result(rid)) == 6
    engine.release(rid)
    assert engine.set_decode_chunk(64) == 8   # clamped to the warmed menu
    assert engine.set_decode_chunk(8) == 8

