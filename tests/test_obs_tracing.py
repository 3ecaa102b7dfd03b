"""Request-scoped tracing + unified /metrics + SLO burn (ISSUE 17
tentpole). Unit half: the obs primitives (bounded span ring,
deterministic sampling, JSONL export, SLO burn math, weakref scrape
hooks). E2E half, over real sockets: ONE trace id minted at the router
rides `X-Trace-Id` through router relay → server handler → supervisor
journal → engine phases, and a supervisor crash-replay keeps the
original attempt, the restart, and the resumed generation under the
SAME trace id. Plus the /metrics Prometheus-text and /healthz payload
shapes on both frontends, and the heartbeat / circuit-breaker series
under injected chaos."""

from __future__ import annotations

import gc
import json
import logging
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.chaos import (FaultInjector, FaultScriptConfig,
                                FaultSpec, generate_fault_script)
from kubeflow_tpu.models import llama
from kubeflow_tpu.obs import metrics as obs_metrics
from kubeflow_tpu.obs.metrics import render_metrics
from kubeflow_tpu.obs.slo import SloBurnTracker
from kubeflow_tpu.obs.trace import (PHASES, STALL_NS, TRACE_HEADER, TRACER,
                                    NOOP_SPAN, PhaseClock, SpanSink,
                                    StepAggregator, Tracer, new_trace_id)
from kubeflow_tpu.serving.agent import EngineSupervisor
from kubeflow_tpu.serving.llm import LLMEngine
from kubeflow_tpu.serving.llm_runtime import LLMModel
from kubeflow_tpu.serving.model import ModelRepository, load_model
from kubeflow_tpu.serving.router import OPEN, Router
from kubeflow_tpu.serving.server import ModelServer

# -- unit: span ring + sampling ----------------------------------------------


def test_span_ring_is_bounded_and_counts_drops():
    sink = SpanSink(capacity=4)
    tr = Tracer(sink=sink, sample_rate=1.0)
    for i in range(6):
        tr.record_span(f"s{i}", "queue", "t" * 8, 0.0, 1.0)
    assert len(sink) == 4
    assert sink.dropped == 2
    assert [s.name for s in sink.spans()] == ["s2", "s3", "s4", "s5"]
    sink.clear()
    assert len(sink) == 0 and sink.dropped == 0


def test_sampling_is_deterministic_per_trace_id():
    """The keep/drop verdict is a pure function of the trace id: two
    independent tracers at the same rate agree on every id — how the
    router, supervisor, and engine reach one decision with no shared
    state."""
    a = Tracer(sample_rate=0.5)
    b = Tracer(sample_rate=0.5)
    ids = [new_trace_id() for _ in range(400)]
    verdicts = [a.sampled(t) for t in ids]
    assert verdicts == [b.sampled(t) for t in ids]
    kept = sum(verdicts)
    assert 100 < kept < 300          # ~0.5, loose bound
    assert all(Tracer(sample_rate=1.0).sampled(t) for t in ids)
    assert not any(Tracer(sample_rate=0.0).sampled(t) for t in ids)
    assert not a.sampled(None) and not a.sampled("")


def test_sampled_out_spans_cost_nothing_and_guards_hold():
    sink = SpanSink()
    tr = Tracer(sink=sink, sample_rate=0.0)
    assert tr.span("x", "queue", new_trace_id()) is NOOP_SPAN
    NOOP_SPAN.set(a=1).end()          # absorbs silently
    tr.record_span("x", "queue", new_trace_id(), 0.0, 1.0)
    tr.set_sample_rate(1.0)
    tr.record_span("x", "queue", "tid", None, 1.0)   # half-open: dropped
    tr.record_span("x", "queue", "tid", 0.0, None)
    assert len(sink) == 0
    sp = tr.span("y", "decode", "tid", start_s=1.0)
    sp.end(end_s=3.0)
    sp.end(end_s=9.0)                 # idempotent: exports once
    assert len(sink) == 1
    assert sink.spans()[0].duration_ms() == 2000.0
    assert tr.set_sample_rate(7.0) == 1.0    # clamped
    assert tr.set_sample_rate(-1.0) == 0.0


def test_jsonl_export_filters_and_roundtrips(tmp_path):
    sink = SpanSink()
    tr = Tracer(sink=sink, sample_rate=1.0)
    t1, t2 = new_trace_id(), new_trace_id()
    tr.record_span("a", "queue", t1, 0.0, 1.0, backend="x")
    tr.record_span("b", "decode", t2, 1.0, 2.0)
    tr.record_span("c", "http", t1, 2.0, 3.0)
    text = sink.export_jsonl()
    lines = [json.loads(ln) for ln in text.splitlines()]
    assert [ln["name"] for ln in lines] == ["a", "b", "c"]
    assert lines[0]["attrs"] == {"backend": "x"}
    only_t1 = sink.export_jsonl(trace_id=t1)
    assert [json.loads(ln)["name"]
            for ln in only_t1.splitlines()] == ["a", "c"]
    p = tmp_path / "trace.jsonl"
    sink.export_jsonl(path=str(p), trace_id=t2)
    assert json.loads(p.read_text())["name"] == "b"


def test_step_aggregator_window():
    agg = StepAggregator()
    before = agg.snapshot()
    agg.note_step(8, steps=2)
    agg.note_step(3)
    w = StepAggregator.window(before, agg.snapshot())
    assert w == {"decode_steps": 3, "decode_tokens": 11}


def test_span_json_carries_unix_start():
    """`start_unix_ns` lays a JSONL export over a profiler trace: the
    span's monotonic start moved by the one anchor pair read at import."""
    sink = SpanSink()
    now_s, unix_ns = time.monotonic(), time.time_ns()
    Tracer(sink=sink).record_span("a", "queue", "tid", now_s, now_s + 1.0)
    rec = json.loads(sink.export_jsonl())
    assert rec["start_s"] == now_s
    assert abs(rec["start_unix_ns"] - unix_ns) < 50_000_000   # 50 ms


# -- unit: the engine thread's phase clock ------------------------------------


def test_phase_clock_partitions_the_timeline():
    """Entering a phase ends the one before, so between the first enter
    and any mark the phases' nanoseconds sum to the wall between them —
    exactly, whatever the driver did meanwhile. Re-entering the open
    phase is not a new occurrence."""
    c = PhaseClock("unit")
    c.hold_open = True
    c.enter("sched")
    t0 = c._t0
    c.enter("sched")                  # same phase: no new occurrence
    c.enter("decode_plan")
    c.enter("decode_dispatch")
    c.note_step(8, steps=4)           # the StepAggregator counts ride along
    c.enter("decode_fetch")
    time.sleep(0.01)
    c.enter("replay")
    c.leave()                         # held open: the clock keeps running
    m = c.mark()
    assert sum(m.ns) == m.at_ns - t0
    by = dict(zip(PHASES, m.counts))
    assert by["sched"] == by["decode_dispatch"] == by["replay"] == 1
    assert dict(zip(PHASES, m.ns))["decode_fetch"] >= 10_000_000
    assert (m.steps, m.tokens) == (4, 8)
    # a driver that does not hold the thread: leave() stops the clock
    c.hold_open = False
    c.leave()
    a = c.mark()
    time.sleep(0.005)
    assert c.mark().ns == a.ns


def test_phase_clock_device_empty_overlay():
    """`device_empty_ns` runs from a fetch that left nothing dispatched
    and unfetched to the next program call; a fetch with work still in
    flight starts nothing; a mark counts the open stretch."""
    c = PhaseClock("unit")
    c.enter("decode_fetch")
    c.fetched(outstanding=True)
    c.enter("replay")
    time.sleep(0.005)
    c.enter("decode_dispatch")
    assert c.device_empty_ns == 0
    c.enter("decode_fetch")
    c.fetched(outstanding=False)
    c.enter("replay")
    time.sleep(0.01)
    # closed so far: the sliver of `decode_fetch` after the fetch returned
    assert c.device_empty_ns < 1_000_000
    assert c.mark().device_empty_ns >= 10_000_000
    c.enter("sched")                  # not a program call: still empty
    c.enter("prefill_dispatch")
    closed = c.device_empty_ns
    assert closed >= 10_000_000
    time.sleep(0.002)
    assert c.mark().device_empty_ns == closed


def _script_three_phases(c):
    """One empty interval over `replay`, `sched` and `prefill_pack`."""
    c.enter("decode_fetch")
    c.fetched(outstanding=False)
    for phase in ("replay", "sched", "prefill_pack"):
        c.enter(phase)
        time.sleep(0.003)
    c.enter("prefill_dispatch")
    return {"replay", "sched", "prefill_pack", "decode_fetch"}


def _script_opens_mid_phase(c):
    """The fetch returns 5 ms into `decode_fetch`: only the rest of that
    occurrence is empty, and a second fetch while empty restarts nothing."""
    c.enter("decode_fetch")
    time.sleep(0.005)
    c.fetched(outstanding=False)
    time.sleep(0.003)
    c.fetched(outstanding=False)
    c.enter("decode_dispatch")
    assert 3_000_000 <= c.device_empty_by_phase["decode_fetch"] \
        < c.ns["decode_fetch"] - 4_000_000
    return {"decode_fetch"}


def _script_in_flight(c):
    """A fetch that leaves a program in flight empties nothing; the
    dispatch phases themselves never hold empty time."""
    c.enter("decode_fetch")
    c.fetched(outstanding=True)
    c.enter("replay")
    time.sleep(0.002)
    c.enter("decode_dispatch")
    c.enter("decode_fetch")
    c.fetched(outstanding=False)
    c.enter("decode_plan")
    time.sleep(0.002)
    c.enter("decode_dispatch")
    time.sleep(0.002)
    c.enter("decode_fetch")
    return {"decode_fetch", "decode_plan"}


def _script_idle_and_a_stopped_clock(c):
    """`idle` takes its share like any phase; a driver that does not
    hold the thread stops the overlay with the clock."""
    c.hold_open = True
    c.enter("prefill_fetch")
    c.fetched(outstanding=False)
    c.enter("idle")
    time.sleep(0.004)
    c.hold_open = False
    c.leave()
    time.sleep(0.06)                  # nobody's phase, nobody's empty time
    c.enter("sched")
    time.sleep(0.002)
    c.enter("prefill_dispatch")
    assert c.device_empty_ns < 50_000_000
    assert c.device_empty_by_phase["idle"] >= 4_000_000
    return {"prefill_fetch", "idle", "sched"}


@pytest.mark.parametrize("script", [
    _script_three_phases, _script_opens_mid_phase, _script_in_flight,
    _script_idle_and_a_stopped_clock], ids=lambda f: f.__name__[8:])
def test_device_empty_is_split_by_phase_exactly(script):
    """Every device-empty interval goes to the phases it fell in: the
    split sums to `device_empty_ns` to the nanosecond at every mark, is
    never more than the phase's own wall, and only the scripted phases
    hold any."""
    c = PhaseClock("unit")
    first = c.mark()
    holders = script(c)
    time.sleep(0.001)
    m = c.mark()                      # with a phase open
    assert sum(m.device_empty_by_phase) == m.device_empty_ns
    assert sum(c.device_empty_by_phase.values()) == c.device_empty_ns
    for p, empty, ns in zip(PHASES, m.device_empty_by_phase, m.ns):
        assert 0 <= empty <= ns
        assert (empty > 0) <= (p in holders), p
    assert {p for p, e in zip(PHASES, m.device_empty_by_phase) if e} \
        >= holders - {"decode_fetch", "prefill_fetch"}
    u = c.usage(first, None)
    assert sum(u["device_empty_by_phase_ms"].values()) == pytest.approx(
        u["device_empty_ms"], abs=0.001 * len(PHASES))
    assert set(u["device_empty_by_phase_ms"]) <= holders


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_phase_cpu_tells_running_from_waiting(how):
    """Beside each phase's wall stands the thread's own CPU time in it:
    never more than the wall, far less in a phase that sleeps, within
    20 % in one that spins (the best of a few tries: the machine is
    shared)."""
    ratios = []
    for _ in range(5):
        c = PhaseClock("unit")
        c.enter("sched")
        first = c.mark()
        c.enter("replay")
        if how == "sleeps":
            time.sleep(0.05)
        else:
            end = time.monotonic() + 0.05
            while time.monotonic() < end:
                pass
        c.enter("sched")
        # (two clocks, read back to back: microseconds of slack an
        # occurrence)
        for p in PHASES:
            assert 0 <= c.cpu_ns[p] <= c.ns[p] + 20_000 * c.counts[p]
        m = c.mark()
        assert all(a <= b + 20_000 * n
                   for a, b, n in zip(m.cpu_ns, m.ns, m.counts))
        u = c.usage(first, None)
        assert set(u["cpu_ms"]) == set(u["phases"])
        assert all(u["cpu_ms"][p] <= u["phases"][p][0] for p in u["cpu_ms"])
        assert c.ns["replay"] >= 50_000_000
        ratios.append(c.cpu_ns["replay"] / c.ns["replay"])
        if how == "sleeps" or ratios[-1] >= 0.8:
            break
    if how == "sleeps":
        assert ratios[-1] < 0.1
    else:
        assert max(ratios) >= 0.8, ratios


def test_a_coarse_cpu_clock_is_summed_as_read_and_cut_per_window(
        monkeypatch):
    """Where the thread CPU clock ticks in 10 ms steps (a sandboxed
    host), a tick lands whole in whichever phase is open: the clock
    keeps what it read (fair over many occurrences: every tick is in
    SOME phase's sum), and a request's usage cuts each phase's CPU to
    its wall over the window."""
    real = time.thread_time_ns
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: real() // 10_000_000 * 10_000_000)
    c = PhaseClock("unit")
    c.enter("sched")
    first = c.mark()
    def spin(seconds):
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            pass

    start = time.thread_time_ns()
    for _ in range(150):              # ~9 ticks over 300 phases of 0.3 ms
        c.enter("replay")
        spin(0.0003)
        c.enter("decode_plan")
        spin(0.0003)
    c.enter("sched")
    ticks = time.thread_time_ns() - start
    assert ticks >= 50_000_000
    # every tick is whole in some phase's sum, none cut to a 0.3 ms wall
    assert all(v % 10_000_000 == 0 for v in c.cpu_ns.values())
    assert sum(c.cpu_ns.values()) == ticks
    assert max(c.cpu_ns["replay"], c.cpu_ns["decode_plan"]) >= 20_000_000
    u = c.usage(first, None)
    assert all(u["cpu_ms"][p] <= u["phases"][p][0] for p in u["cpu_ms"])


def test_gc_pause_lands_in_its_phase_and_in_metrics():
    """One `gc.callbacks` hook for the process: a collection forced
    inside a phase is that phase's `gc_ns` (of every clock with a phase
    open: a collection stops all threads), the usage's `gc_ms`, and
    `process_gc_pause_seconds_total{generation}` with the longest pause
    beside it."""
    from kubeflow_tpu.obs.trace import GC

    assert gc.callbacks.count(GC) == 1
    c, other = PhaseClock("unit"), PhaseClock("other")
    c.enter("sched")
    other.enter("idle")
    first = c.mark()
    c.enter("replay")
    junk = [[i] for i in range(50_000)]
    before = GC.ns[2]
    gc.collect()
    paused = GC.ns[2] - before        # the full collection alone
    assert paused > 0 and GC.longest_ns > 0
    assert c.mark().gc_ns - first.gc_ns >= paused    # the open phase
    c.enter("sched")
    del junk
    assert c.gc_ns["replay"] >= paused and c.gc_ns["idle"] == 0
    assert c.gc_ns["replay"] <= c.ns["replay"]
    other.enter("sched")
    assert other.gc_ns["idle"] >= paused
    assert c.usage(first, None)["gc_ms"] >= round(paused / 1e6, 3) > 0
    text = render_metrics()
    assert "# TYPE process_gc_pause_seconds_total counter" in text
    assert _metric_value(
        text, 'process_gc_pause_seconds_total{generation="2"}') \
        >= paused / 1e9 * 0.99
    assert _metric_value(text, "process_gc_pause_max_seconds") > 0
    # two scrapes add no pause twice
    total = sum(_metric_value(
        render_metrics(),
        f'process_gc_pause_seconds_total{{generation="{g}"}}')
        for g in range(3))
    assert total <= GC.total_ns / 1e9 * 1.001 + 1e-9


@pytest.mark.parametrize("work", ["queued", "active", "none"])
def test_idle_is_a_stall_only_when_work_was_waiting(work, monkeypatch,
                                                    caplog):
    """`idle` is where the engine thread is meant to wait, so an idle
    occurrence of `STALL_NS` is nothing, UNLESS it began with
    `context()` reporting queued or active requests: then it is a stall
    like any other (one line, one count, the longest of its window)."""
    monkeypatch.setattr("kubeflow_tpu.obs.trace.STALL_NS", 20_000_000)
    ctx = {"in_flight": "nothing", "queued": 0, "active": 0}
    if work != "none":
        ctx[work] = 2
    c = PhaseClock("unit-idle", lambda: ctx)
    n_before = obs_metrics.ENGINE_STALLS.value(engine="unit-idle",
                                               phase="idle")
    c.enter("sched")
    start_s = time.monotonic()
    with caplog.at_level(logging.WARNING, logger="kubeflow_tpu.obs.trace"):
        c.enter("idle")
        ctx.update(queued=0, active=0)    # what counts is how it BEGAN
        time.sleep(0.03)
        assert (c.longest_since(start_s, time.monotonic_ns())[1]
                == "idle") is (work != "none")
        c.enter("sched")
    lines = [r.getMessage() for r in caplog.records
             if "unit-idle stall: phase idle" in r.getMessage()]
    assert len(lines) == (0 if work == "none" else 1)
    assert obs_metrics.ENGINE_STALLS.value(
        engine="unit-idle", phase="idle") == n_before + len(lines)
    if lines:
        assert "cpu_ms=" in lines[0] and "gc_ms=" in lines[0]
        span = [s for s in TRACER.sink.spans() if s.kind == "stall"][-1]
        assert span.attrs["phase"] == "idle"
        assert span.attrs["cpu_ms"] < span.attrs["duration_ms"]
        assert span.attrs["gc_ms"] >= 0


def test_a_clock_takes_its_own_phase_set():
    """One class for every loop: the phase tuple, the dispatch phases
    and the phases that may wait are the constructor's, the annotation's
    prefix the clock's name; an unknown phase is an error, not a new
    one."""
    c = PhaseClock("loop", phases=("wait", "call", "read"),
                   dispatch=frozenset(("call",)),
                   waits=frozenset(("read",)))
    assert c._annotation == {"wait": "loop.wait", "call": "loop.call",
                             "read": "loop.read"}
    c.enter("read")
    c.fetched(outstanding=False)
    c.enter("wait")
    time.sleep(0.002)
    c.enter("call")
    c.enter("read")
    m = c.mark()
    assert len(m.ns) == len(m.cpu_ns) == len(m.device_empty_by_phase) == 3
    assert c.device_empty_by_phase["wait"] >= 2_000_000
    assert c.device_empty_by_phase["call"] == 0
    # `read` may wait: never the longest occurrence
    assert c.longest_since(0.0, time.monotonic_ns())[1] in ("wait", "call")
    with pytest.raises(KeyError):
        c.enter("sched")
    assert PhaseClock("engine")._annotation["sched"] == "engine.sched"


def test_phase_clock_longest_occurrence_of_a_window():
    """`phase_max` is the longest single non-idle occurrence that
    overlapped submit -> finish: an older, longer one that ended before
    the submit does not count, `idle` never does, the open phase does."""
    c = PhaseClock("unit")
    c.enter("prefill_fetch")
    time.sleep(0.03)
    c.enter("idle")
    time.sleep(0.04)                  # longest of all, but idle
    submit_s = time.monotonic()
    c.enter("decode_fetch")
    time.sleep(0.01)
    c.enter("replay")
    ns, phase = c.longest_since(submit_s, time.monotonic_ns())
    assert phase == "decode_fetch" and 10_000_000 <= ns < 30_000_000
    # from before the prefill fetch the older, longer occurrence wins
    ns, phase = c.longest_since(submit_s - 1.0, time.monotonic_ns())
    assert phase == "prefill_fetch" and ns >= 30_000_000
    time.sleep(0.02)                  # the open `replay` outgrows both
    assert c.longest_since(submit_s, time.monotonic_ns())[1] == "replay"


# -- the clock inside a toy engine --------------------------------------------


@pytest.fixture(scope="module")
def toy_engine():
    cfg = llama.LlamaConfig.tiny()
    eng = LLMEngine(llama.init(jax.random.key(0), cfg), cfg, n_slots=2,
                    max_len=64, buckets=(16,), decode_chunk=2)
    eng.warmup()
    eng.phase_clock.hold_open = True   # this test module drives the thread
    yield eng
    eng.close()


def _run(eng, *rids):
    while not all(eng.is_done(r) for r in rids):
        assert eng.step()


def test_engine_usage_phases_sum_to_decode_ms(toy_engine):
    """Sink B: over a finished request the phases partition the window
    `decode_ms` covers (first token -> finish), prefill waves of OTHER
    requests included, and every name is one of PHASES."""
    eng = toy_engine
    a = eng.submit([1, 2, 3, 4, 5], 24)
    for _ in range(3):
        eng.step()
    b = eng.submit([9, 8, 7], 6)       # its prefill lands inside a's decode
    _run(eng, a, b)
    tm = eng.request_timing(a)
    engine = tm["engine"]
    # PR 25's keys, PR 28's `kv_blocks`, and exactly three more (PR 36)
    assert set(engine) == {"phases", "device_empty_ms", "phase_max_ms",
                           "phase_max", "kv_blocks"} | {
        "cpu_ms", "device_empty_by_phase_ms", "gc_ms"}
    assert all(isinstance(v, list) and len(v) == 2 and isinstance(v[1], int)
               for v in engine["phases"].values())
    assert set(engine["cpu_ms"]) == set(engine["phases"])
    for p, ms in engine["cpu_ms"].items():
        assert 0 <= ms <= engine["phases"][p][0] + 0.5
    assert sum(engine["device_empty_by_phase_ms"].values()) == \
        pytest.approx(engine["device_empty_ms"], abs=0.01)
    assert set(engine["device_empty_by_phase_ms"]) <= set(PHASES) - {
        "prefill_dispatch", "decode_dispatch"}
    assert engine["gc_ms"] >= 0
    assert set(engine["phases"]) <= set(PHASES)
    assert "idle" not in engine["phases"]
    total = sum(ms for ms, _ in engine["phases"].values())
    assert total == pytest.approx(tm["decode_ms"], rel=0.01)
    # b's prefill wave ran inside a's window and is named as such
    assert engine["phases"]["prefill_dispatch"][1] >= 1
    assert engine["phases"]["prefill_fetch"][0] > 0
    assert engine["phases"]["decode_dispatch"][1] >= 24 // 2 - 1
    assert engine["phase_max"] in PHASES and engine["phase_max_ms"] > 0
    assert 0 <= engine["device_empty_ms"] <= tm["decode_ms"]
    # a request that never got a token has no window to report
    c = eng.submit([1, 2, 3], 4)
    eng.cancel(c)
    eng.step()                         # applied at the chunk boundary
    assert eng.is_done(c)
    assert eng.request_timing(c)["engine"] is None
    for r in (a, b, c):
        eng.release(r)
    assert not eng._phase_mark and not eng._phase_fin


def test_device_empty_only_when_nothing_is_in_flight(toy_engine, monkeypatch):
    """The overlay stands still while a decode chunk is dispatched and
    unfetched (pipelined decode keeps one in flight), and grows across a
    sleep planted between a fetch and the next program call."""
    eng = toy_engine
    clock = eng.phase_clock
    a = eng.submit([1, 2, 3, 4], 40)
    while eng._pending is None:
        eng.step()
    before = clock.mark().device_empty_ns
    for _ in range(5):
        eng.step()
        assert eng._pending is not None
    assert clock.mark().device_empty_ns == before
    # the next prefill burst drains the chunk (a fetch that leaves nothing
    # in flight), then admits (the sleep), then dispatches
    admit = eng._admit_prefills
    monkeypatch.setattr(eng, "_admit_prefills",
                        lambda acts: (time.sleep(0.05), admit(acts))[1])
    b = eng.submit([5, 6, 7], 2)
    eng.step()
    assert clock.mark().device_empty_ns - before >= 50_000_000
    monkeypatch.undo()
    _run(eng, a, b)
    engine = eng.request_timing(a)["engine"]
    assert engine["device_empty_ms"] >= 50
    # the planted sleep lay in the admission: `sched` holds it
    assert engine["device_empty_by_phase_ms"]["sched"] >= 50
    eng.release(a)
    eng.release(b)


def test_stall_is_logged_counted_spanned_and_named_in_usage(
        toy_engine, monkeypatch, caplog):
    """Sink C's stall rule: one non-idle phase occurrence of 500 ms or
    more gives one WARNING, one `serving_engine_stalls_total` increment
    and a `stall` span (no trace id needed), and the usage of a request
    alive across it names the phase."""
    eng = toy_engine
    a = eng.submit([1, 2, 3, 4], 12)
    while eng._pending is None:
        eng.step()
    n_before = obs_metrics.ENGINE_STALLS.value(engine="engine",
                                               phase="sched")
    spans_before = len([s for s in TRACER.sink.spans()
                        if s.kind == "stall"])
    admit = eng._admit_prefills
    monkeypatch.setattr(eng, "_admit_prefills",
                        lambda acts: (time.sleep(0.6), admit(acts))[1])
    b = eng.submit([5, 6, 7], 2)
    with caplog.at_level(logging.WARNING, logger="kubeflow_tpu.obs.trace"):
        eng.step()
        monkeypatch.undo()
        _run(eng, a, b)
    warned = [r for r in caplog.records
              if "engine stall" in r.getMessage()
              and "phase sched" in r.getMessage()]
    assert len(warned) == 1
    assert "queued=" in warned[0].getMessage()
    assert "active=" in warned[0].getMessage()
    # a sleep: the line says the thread was off the CPU, and not for GC
    said = dict(kv.split("=") for kv in warned[0].getMessage()
                .split("(")[1].rstrip(")").split(", "))
    assert float(said["cpu_ms"]) < 300 and float(said["gc_ms"]) < 300
    assert obs_metrics.ENGINE_STALLS.value(
        engine="engine", phase="sched") == n_before + 1
    stalls = [s for s in TRACER.sink.spans() if s.kind == "stall"]
    assert len(stalls) == spans_before + 1
    assert stalls[-1].attrs["phase"] == "sched"
    assert stalls[-1].attrs["cpu_ms"] < 300
    assert stalls[-1].duration_ms() >= STALL_NS / 1e6
    engine = eng.request_timing(a)["engine"]
    assert engine["phase_max"] == "sched" and engine["phase_max_ms"] >= 600
    # the stall lay inside a's decode window, so its phase carries it
    assert engine["phases"]["sched"][0] >= 600
    eng.release(a)
    eng.release(b)


def test_kv_block_counts_reach_usage_and_metrics(toy_engine):
    """Each decode dispatch counts the KV blocks its attention grid
    spans (slots x span / block) and those the slots' lengths let the
    kernel copy (none for a dead slot): `usage.engine.kv_blocks` carries
    the request's window of both, /metrics the two cumulative series."""
    eng = toy_engine
    clock = eng.phase_clock
    assert eng._kv_block_tokens() == 64     # a cache shorter than a block
    before = clock.mark().kv_blocks
    chunks = clock.counts["decode_dispatch"]
    a = eng.submit([1, 2, 3, 4, 5], 12)     # the other slot stays dead
    _run(eng, a)
    chunks = clock.counts["decode_dispatch"] - chunks
    fetched, spanned = (e - s for s, e in zip(before,
                                              clock.mark().kv_blocks))
    # one block to a span here: 2 slots spanned, the live one fetched
    assert (fetched, spanned) == (chunks, 2 * chunks) and chunks >= 5
    # the request's window opens at its first token: every decode
    # dispatch but those before it
    got = eng.request_timing(a)["engine"]["kv_blocks"]
    assert got[1] == 2 * got[0] and 0 < got[0] <= fetched
    eng.release(a)
    text = render_metrics()
    for name, at_least in (("fetched", fetched), ("spanned", spanned)):
        series = f"serving_engine_kv_blocks_{name}_total"
        assert f"# TYPE {series} counter" in text
        assert _metric_value(text, series + '{engine="engine"}') >= at_least
    # a clock that saw no decode dispatch reports no such key
    c = PhaseClock("unit")
    c.enter("sched")
    first = c.mark()
    c.enter("replay")
    assert "kv_blocks" not in c.usage(first, None)
    c.note_kv_blocks(3, 8)
    assert c.usage(first, None)["kv_blocks"] == [3, 8]


def test_decode_host_counters_are_read_from_the_phase_clock(toy_engine):
    """`self._perf` and the engine's second view of it are gone: the
    decode host counters are the phase clock's own, read as differences."""
    eng = toy_engine
    assert not hasattr(eng, "_perf")
    c = eng.phase_clock

    def read():
        return {"dispatch_s": c.ns["decode_dispatch"] / 1e9,
                "fetch_replay_s": (c.ns["decode_fetch"]
                                   + c.ns["replay"]) / 1e9,
                "decode_chunks": c.counts["decode_dispatch"],
                "decode_steps": c.steps}

    base = read()
    zero = {k: v - base[k] for k, v in read().items()}
    assert zero["decode_chunks"] == zero["decode_steps"] == 0
    assert zero["dispatch_s"] == 0
    eng.generate([1, 2, 3], 8)
    pc = {k: v - base[k] for k, v in read().items()}
    assert pc["decode_chunks"] >= 3 and pc["decode_steps"] >= 7
    assert pc["dispatch_s"] > 0 and pc["fetch_replay_s"] > 0
    assert (pc["decode_chunks"] <= pc["decode_steps"]
            <= pc["decode_chunks"] * eng.decode_chunk)


def test_metrics_render_the_phase_series(toy_engine):
    """Sink C, pull model: a scrape adds what closed since the last one
    to the two cumulative series; scraping twice adds nothing twice."""
    eng = toy_engine
    eng.generate([4, 5, 6], 6)
    text = render_metrics()
    assert "# TYPE serving_engine_phase_seconds_total counter" in text
    assert "# TYPE serving_engine_device_empty_seconds_total counter" \
        in text
    for phase in PHASES:
        assert f'serving_engine_phase_seconds_total{{engine="engine",' \
            f'phase="{phase}"}}' in text
    series = 'serving_engine_phase_seconds_total{engine="engine",' \
        'phase="decode_dispatch"}'
    first = _metric_value(text, series)
    assert first > 0
    assert "# TYPE serving_engine_phase_cpu_seconds_total counter" in text
    # the device-empty series carries the phase; its sum over phase is
    # the series PR 25 had
    empty = [_metric_value(
        text, 'serving_engine_device_empty_seconds_total'
        f'{{engine="engine",phase="{phase}"}}') for phase in PHASES]
    assert sum(empty) > 0
    assert sum(empty) <= eng.phase_clock.mark().device_empty_ns / 1e9 + 1e-6
    for phase in PHASES:
        cpu = _metric_value(
            text, 'serving_engine_phase_cpu_seconds_total'
            f'{{engine="engine",phase="{phase}"}}')
        wall = _metric_value(
            text, 'serving_engine_phase_seconds_total'
            f'{{engine="engine",phase="{phase}"}}')
        # as the clocks read: microseconds of slack an occurrence
        assert 0 <= cpu <= wall * 1.05 + 1e-3
    assert _metric_value(render_metrics(), series) == first
    assert "serving_phase_seconds" not in text    # the dead histogram


def test_engine_programs_have_one_name_per_kind(toy_engine):
    """A device trace names a module `jit_<function name>`: one name per
    KIND of program, no chunk, span or bucket in it (a bare partial
    compiled as `jit__unknown`)."""
    eng = toy_engine
    assert eng._decode_fn(2).__name__ == "decode"
    assert eng._decode_fn(1, 32).__name__ == "decode"
    assert eng._prefill_fn(16, 1).__name__ == "prefill"
    assert eng._cont_fn(16, 16, 1).__name__ == "prefill_cont"
    assert eng._extract_fn(16).__name__ == "extract_prefix"
    assert eng._extract_raw_fn(16).__name__ == "extract_prefix_raw"
    assert eng._spec_fn(1, 32, 2).__name__ == "decode_spec"


def test_supervisor_journal_carries_engine_phases():
    """The engine's rid is released at completion; `engine` survives in
    the journal's phases like the three durations beside it."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(0), cfg)
    sup = EngineSupervisor(
        lambda: LLMEngine(params, cfg, n_slots=2, max_len=32,
                          buckets=(8,), decode_chunk=2))
    try:
        rid = sup.submit([1, 2, 3], 6)
        sup.run_until_idle()
        tm = sup.request_timing(rid)
        assert tm["decode_ms"] is not None
        assert set(tm["engine"]["phases"]) <= set(PHASES)
        assert tm["engine"]["phases"]["decode_dispatch"][1] >= 2
        # the supervisor's own journal poll is the engine thread's time too
        assert sup.phase_clock is sup.engine.phase_clock
        assert sup.phase_clock.counts["replay"] > 0
    finally:
        sup.close()
    assert sup.phase_clock is None      # no live engine, no clock


# -- unit: SLO burn -----------------------------------------------------------


def test_slo_burn_tracker_math():
    """Hand-computable: 4 requests, 1 TTFT miss → attainment 0.75,
    burn = (1 - 0.75) / 0.01 budget = 25x."""
    slo = SloBurnTracker(ttft_slo_ms=100.0, tpot_slo_ms=10.0,
                         window_s=300.0, budget=0.01)
    for ttft in (50.0, 80.0, 90.0):
        slo.record("t0", ttft, 5.0)
    slo.record("t0", 500.0, 5.0)              # TTFT miss
    s = slo.summary()
    assert s["slo"] == {"ttft_ms": 100.0, "tpot_ms": 10.0,
                        "error_budget": 0.01}
    t0 = s["tenants"]["t0"]
    assert t0["n"] == 4 and t0["met"] == 3
    assert t0["attainment"] == pytest.approx(0.75)
    assert t0["burn_rate"] == pytest.approx(25.0)
    assert s["aggregate"]["n"] == 4
    # a not-completed request is a miss even with perfect latencies
    slo.record("t1", 10.0, 1.0, completed=False)
    assert slo.summary()["tenants"]["t1"]["met"] == 0
    # window: samples age out
    old = SloBurnTracker(ttft_slo_ms=100.0, tpot_slo_ms=10.0,
                         window_s=1.0)
    old.record("t", 500.0, 5.0, now=time.monotonic() - 10.0)
    assert "t" not in old.summary()["tenants"]


def test_slo_burn_publishes_gauges_through_scrape_hook():
    slo = SloBurnTracker(ttft_slo_ms=100.0, tpot_slo_ms=10.0)
    slo.record("tenantA", 50.0, 5.0)
    obs_metrics.add_scrape_hook(slo, type(slo).publish)
    try:
        text = render_metrics()
        assert 'slo_attainment{tenant="tenantA"} 1' in text
        assert 'slo_burn_rate{tenant="tenantA"} 0' in text
        assert 'slo_attainment{tenant="_aggregate"}' in text
    finally:
        obs_metrics.remove_scrape_hooks(slo)


# -- unit: scrape hooks + render shape ---------------------------------------


def test_scrape_hooks_are_weakref_and_crash_isolated():
    class Owner:
        def publish(self):
            obs_metrics.INFLIGHT.set(7, component="hooktest")

    calls = []
    owner = Owner()
    obs_metrics.add_scrape_hook(owner, Owner.publish)

    class Bomb:
        def boom(self):
            calls.append(1)
            raise RuntimeError("dying component")

    bomb = Bomb()
    obs_metrics.add_scrape_hook(bomb, Bomb.boom)
    try:
        text = render_metrics()     # bomb raises; render survives
        assert calls == [1]
        assert 'serving_inflight{component="hooktest"} 7' in text
        del owner
        gc.collect()
        obs_metrics.INFLIGHT.set(0, component="hooktest")
        text = render_metrics()
        # the collected owner's hook is gone: nothing re-set the gauge
        assert 'serving_inflight{component="hooktest"} 0' in text
    finally:
        obs_metrics.remove_scrape_hooks(bomb)


def test_render_metrics_is_prometheus_text():
    obs_metrics.REQUESTS.inc(component="unittest", event="completed")
    text = render_metrics()
    assert "# HELP serving_requests_total" in text
    assert "# TYPE serving_requests_total counter" in text
    assert re.search(r'serving_requests_total\{component="unittest",'
                     r'event="completed"\} \d+', text)
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert "trace_buffer_spans" in text
    assert text.endswith("\n")


# -- e2e: one trace id across router → server → supervisor → engine -----------

PROMPT = [72, 105, 33]
MAX_TOKENS = 12


def _crash_now(seed: int = 1):
    return generate_fault_script(FaultScriptConfig(
        seed=seed, duration_s=1.0,
        faults=(FaultSpec("backend_crash", 1, (0.0, 0.0)),)), name="now")


def _llm_server(**model_kw):
    cfg = llama.LlamaConfig(vocab_size=128, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=64, attention_impl="xla",
                            dtype=jnp.float32, remat=False)
    m = LLMModel("llm", model={k: getattr(cfg, k) for k in
                               ("vocab_size", "d_model", "n_layers",
                                "n_heads", "n_kv_heads", "d_ff",
                                "max_seq_len", "attention_impl",
                                "remat")},
                 n_slots=2, max_len=64, buckets=(8, 16), seed=0,
                 decode_chunk=2,
                 supervisor={"stall_timeout_s": 30.0,
                             "backoff_base_s": 0.3,
                             "backoff_cap_s": 0.6,
                             "rewarm": False},
                 sse_keepalive_s=0.05, **model_kw)
    repo = ModelRepository()
    repo.register(m)
    server = ModelServer(repo).start()
    yield m, server
    server.stop()
    m.unload()


@pytest.fixture(scope="module")
def llm_server():
    yield from _llm_server()


@pytest.fixture(scope="module")
def timed_server():
    """The same server with `usage_timing` on: Sink B's channel."""
    yield from _llm_server(usage_timing=True)


def _completion_usage(port: int, stream: bool) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/openai/v1/completions",
        data=json.dumps({"model": "llm", "prompt": PROMPT,
                         "max_tokens": MAX_TOKENS, "temperature": 0.0,
                         "stream": stream}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        raw = r.read().decode()
    if not stream:
        return json.loads(raw)["usage"]
    chunks = [ln[6:] for ln in raw.splitlines() if ln.startswith("data: {")]
    return json.loads(chunks[-1])["usage"]


def _post_completion(port: int, trace_id: str, timeout=120.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/openai/v1/completions",
        data=json.dumps({"model": "llm", "prompt": PROMPT,
                         "max_tokens": MAX_TOKENS,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json",
                 TRACE_HEADER: trace_id}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200
        return json.loads(r.read())


def test_one_trace_id_spans_router_to_engine(llm_server):
    """THE tentpole acceptance path: a trace id presented to the ROUTER
    is honored (not re-minted) and every layer's span lands under it —
    router relay, server handler, supervisor journal lifetime, engine
    queue/prefill/decode — exportable as one JSONL chain."""
    m, server = llm_server
    r = Router("t/obs")
    trace_id = "ab" * 16
    try:
        r.set_backends(server.port)
        body = _post_completion(r.port, trace_id)
        assert body["choices"][0]["text"]
    finally:
        r.stop()
    spans = TRACER.sink.spans(trace_id)
    names = {s.name for s in spans}
    assert {"router.relay", "server.http", "supervisor.supervise",
            "engine.queue", "engine.prefill",
            "engine.decode"} <= names, names
    by_name = {s.name: s for s in spans}
    assert by_name["router.relay"].kind == "http"
    assert by_name["router.relay"].attrs["backend"] == server.port
    assert by_name["engine.decode"].kind == "decode"
    # the decode span carries the aggregate step counters, never
    # per-token children
    # the first token comes from prefill, the window covers the rest
    assert by_name["engine.decode"].attrs["decode_tokens"] >= MAX_TOKENS - 1
    assert by_name["engine.decode"].attrs["decode_steps"] >= 1
    kinds = {s.kind for s in spans}
    assert "decode" in kinds and "http" in kinds and "supervise" in kinds
    # exported JSONL carries the whole chain under the one id
    lines = [json.loads(ln) for ln in
             TRACER.sink.export_jsonl(trace_id=trace_id).splitlines()]
    assert {ln["trace_id"] for ln in lines} == {trace_id}
    assert {ln["name"] for ln in lines} >= names


@pytest.mark.slow
def test_crash_replay_stays_under_one_trace_id(llm_server):
    """A request that survives a mid-generation engine crash (journal
    replay) keeps its ORIGINAL trace id: the exported chain shows the
    killed first attempt, the restart window, and the resumed
    generation as one story — even though the crashed engine never got
    to emit its own spans (the journal is the only witness)."""
    import http.client
    import threading

    m, server = llm_server
    trace_id = "cd" * 16
    sup = m.supervisor
    replayed0 = sup.accounting()["replayed"]
    out_box: list[list[int]] = []

    def client():
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=120)
        conn.request(
            "POST", "/openai/v1/completions",
            body=json.dumps({"model": "llm", "prompt": PROMPT,
                             "max_tokens": MAX_TOKENS,
                             "temperature": 0.0,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: trace_id})
        resp = conn.getresponse()
        toks: list[int] = []
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):].strip()
            if data == b"[DONE]":
                break
            for c in json.loads(data).get("choices", ()):
                if c.get("token_id") is not None:
                    toks.append(int(c["token_id"]))
        out_box.append(toks)
        conn.close()

    t = threading.Thread(target=client, daemon=True)
    t.start()
    # arm on server-side truth: >=2 tokens journaled and in flight, so
    # the kill provably lands mid-generation (the chaos-test idiom)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with sup._lock:
            n = max((len(e.base_tokens) + len(e.tokens)
                     for e in sup._journal.values() if not e.terminal),
                    default=None)
        if n is not None and n >= 2:
            break
        time.sleep(0.001)
    else:
        pytest.fail("stream never reached 2 in-flight tokens")
    sup.arm_faults(_crash_now(seed=31))
    t.join(timeout=120)
    assert not t.is_alive(), "stream hung through the crash"
    assert len(out_box[0]) == MAX_TOKENS
    assert sup.accounting()["replayed"] >= replayed0 + 1
    spans = TRACER.sink.spans(trace_id)
    names = {s.name: s for s in spans}
    assert "supervisor.attempt" in names      # the killed first attempt
    att = names["supervisor.attempt"]
    assert att.attrs["outcome"] == "killed"
    assert att.attrs["tokens_delivered"] >= 2
    assert "supervisor.restart" in names      # the restart window
    assert names["supervisor.resume"].attrs["mode"] == "replayed"
    assert "engine.decode" in names           # the resumed generation
    assert {s.trace_id for s in spans} == {trace_id}
    assert "replayed" in names["supervisor.supervise"].attrs["chain"]


def test_server_metrics_and_healthz_payloads(llm_server):
    m, server = llm_server
    _post_completion(server.port, new_trace_id())   # alone, this is the
    # first request the server sees
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10) as r:
        assert r.status == 200
        assert "text/plain" in r.headers.get("Content-Type", "")
        text = r.read().decode()
    assert "# TYPE serving_requests_total counter" in text
    assert 'serving_http_requests_total{model="llm",verb="completions"}' \
        in text
    # declared whether or not this worker ever counted a restart (the
    # series itself exists only after one)
    assert "# TYPE supervisor_restarts_total counter" in text
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=10) as r:
        health = json.loads(r.read())
    assert health["alive"] is True
    assert health["uptime_s"] >= 0
    assert health["build"]["kubeflow_tpu"]
    assert "platform" in health["build"]
    assert "slo" in health
    # the pre-obs JSON metrics view survives unchanged for callers
    mm = server._metrics()
    assert "request_count" in mm and "latency_sum_s" in mm


@pytest.mark.parametrize("stream", [False, True])
def test_usage_is_unchanged_with_usage_timing_off(llm_server, stream):
    """Golden keys: without `usage_timing` nothing of the phase clock,
    nor the server thread's two spans, reaches the usage object."""
    _, server = llm_server
    usage = _completion_usage(server.port, stream)
    assert usage == {"prompt_tokens": len(PROMPT),
                     "completion_tokens": MAX_TOKENS,
                     "total_tokens": len(PROMPT) + MAX_TOKENS}


@pytest.mark.parametrize("stream", [False, True])
def test_usage_timing_carries_engine_and_server_spans(timed_server, stream):
    """Sink B end to end, through the supervisor's journal: `engine`
    (phases that sum to decode_ms), `pre_submit_ms` on both paths,
    `first_write_lag_ms` on the streaming one only."""
    _, server = timed_server
    usage = _completion_usage(server.port, stream)
    assert {"queue_wait_ms", "prefill_ms", "decode_ms", "engine",
            "pre_submit_ms"} <= set(usage)
    assert ("first_write_lag_ms" in usage) is stream
    assert ("stream_write_lag_max_ms" in usage) is stream
    assert "submit_s" not in usage      # an instant, not for the client
    engine = usage["engine"]
    assert set(engine["phases"]) <= set(PHASES)
    total = sum(ms for ms, _ in engine["phases"].values())
    assert total == pytest.approx(usage["decode_ms"], rel=0.01)
    assert engine["phases"]["decode_dispatch"][1] >= 1
    assert 0 <= usage["pre_submit_ms"] < 5_000
    if stream:
        # the first chunk cannot be written before the token exists
        assert 0 <= usage["first_write_lag_ms"] < 5_000
        # picked up within polls of the journal's copy, not chunks later
        assert 0 <= usage["stream_write_lag_max_ms"] < 5_000
    assert {"cpu_ms", "device_empty_by_phase_ms", "gc_ms"} <= set(engine)


class _ScriptedEngine:
    """What `_stream_from` asks of an engine, fed by a thread that
    appends a chunk of two tokens every 20 ms and stamps each append."""

    def __init__(self, chunks: int):
        self.tokens: list[int] = []
        self.stamp = None
        self.done = False
        self.released = False
        self._thread = threading.Thread(target=self._run, args=(chunks,),
                                        daemon=True)

    def _run(self, chunks: int) -> None:
        for _ in range(chunks):
            time.sleep(0.02)
            self.stamp = (len(self.tokens), time.monotonic())
            self.tokens += [7, 7]
        self.done = True

    def is_done(self, rid): return self.done
    def partial_result(self, rid): return list(self.tokens)
    def partial_logprobs(self, rid): return [0.0] * len(self.tokens)
    def last_append(self, rid): return self.stamp
    def finish_reason(self, rid): return "length"
    def cancel(self, rid): return True
    def release(self, rid): self.released = True


@pytest.mark.parametrize("stream_thread", ["keeps_up", "sleeps_through"])
def test_stream_write_lag_is_the_longest_wait_of_a_token(stream_thread):
    """The stream thread keeps the longest a token waited from its
    append to being picked up: against the append's stamp while it keeps
    up, and against its own previous look once several appends went by
    unseen, where the newest stamp would read one chunk's age at most."""
    eng = _ScriptedEngine(chunks=10)
    m = LLMModel("scripted", usage_timing=True)
    m._engine = eng
    m._check_alive = lambda deadline: None
    info: dict = {}
    gen = m._stream_from(0, info=info)
    eng._thread.start()
    got = []
    for tok, _ in gen:
        got.append(tok)
        if stream_thread == "sleeps_through" and len(got) == 2:
            time.sleep(0.15)          # a blocked write, a starved thread
    eng._thread.join(timeout=10)
    assert len(got) == 20 and eng.released
    lag = info["write_lag_max_ms"]
    if stream_thread == "keeps_up":
        assert 0 <= lag < 15          # under one chunk period
    else:
        assert 120 <= lag < 400       # the sleep, not the newest chunk's age
    # without usage_timing nothing is asked of the engine or reported
    quiet = LLMModel("scripted-off")
    eng2 = _ScriptedEngine(chunks=2)
    quiet._engine, quiet._check_alive = eng2, lambda deadline: None
    eng2.last_append = None           # would raise if it were called
    info2: dict = {}
    eng2._thread.start()
    assert len(list(quiet._stream_from(0, info=info2))) == 4
    assert "write_lag_max_ms" not in info2


def test_loop_driven_clock_names_every_instant(timed_server):
    """`LLMModel._loop` holds the thread: with no work its time is
    `idle` (plus the wake-ups' `sched`), and between two marks the
    phases sum to the wall exactly."""
    m, _ = timed_server
    clock = m._engine.phase_clock
    assert clock is not None and clock.hold_open
    a = clock.mark()
    time.sleep(0.2)
    b = clock.mark()
    # exact on the engine thread; a mark read from THIS thread may catch
    # one transition halfway
    assert sum(b.ns) - sum(a.ns) == pytest.approx(b.at_ns - a.at_ns,
                                                  abs=5_000_000)
    idle = PHASES.index("idle")
    assert b.ns[idle] - a.ns[idle] >= 0.9 * (b.at_ns - a.at_ns)


def test_router_metrics_and_healthz_payloads():
    repo = ModelRepository()
    repo.register(load_model("mean", "m"))
    a = ModelServer(repo).start()
    r = Router("t/obs-metrics")
    try:
        r.set_backends(a.port)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{r.port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers.get("Content-Type", "")
            text = resp.read().decode()
        assert f'router_circuit_state{{backend="{a.port}"}} 0' in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{r.port}/healthz", timeout=10) as resp:
            health = json.loads(resp.read())
        assert health["alive"] is True and health["router"] == "t/obs-metrics"
        assert health["uptime_s"] >= 0
        assert health["build"]["kubeflow_tpu"]
        assert health["backends"] == {str(a.port): "closed"}
    finally:
        r.stop()
        a.stop()


# -- chaos-driven metric series ----------------------------------------------


def _metric_value(text: str, series: str) -> float | None:
    m = re.search(rf"^{re.escape(series)} ([0-9.e+-]+)$", text,
                  flags=re.M)
    return float(m.group(1)) if m else None


def test_circuit_breaker_transitions_visible_in_metrics():
    """An injected router↔backend partition trips the breaker: the
    per-backend state gauge walks closed→open→half_open→closed and the
    transitions counter records each entry — all readable from
    /metrics while it happens."""
    repo = ModelRepository()
    repo.register(load_model("mean", "m"))
    a = ModelServer(repo).start()
    script = generate_fault_script(FaultScriptConfig(
        seed=7, duration_s=10.0,
        faults=(FaultSpec("partition", 1, (0.0, 0.0), (0.6, 0.6)),)),
        name="part")
    inj = FaultInjector(script)
    r = Router("t/obs-cb", failure_threshold=1, circuit_open_s=0.2)
    series = f'router_circuit_transitions_total{{backend="{a.port}"'
    try:
        r.set_backends(a.port)
        r.set_fault_injector(inj)
        inj.start()
        req = urllib.request.Request(
            r.url + "/v1/models/m:predict",
            data=json.dumps({"instances": [[1.0, 3.0]]}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            urllib.request.urlopen(req, timeout=10)
        except urllib.error.HTTPError:
            pass                      # 502: partitioned single backend
        text = render_metrics()
        assert _metric_value(
            text, f'router_circuit_state{{backend="{a.port}"}}') == 2
        opens = _metric_value(text, series + ',to="open"}')
        assert opens and opens >= 1
        time.sleep(0.75)              # partition over, hold-off expired
        assert r.circuit_states()[a.port] != OPEN
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200     # the half-open probe closes it
        text = render_metrics()
        assert _metric_value(
            text, f'router_circuit_state{{backend="{a.port}"}}') == 0
        assert _metric_value(text, series + ',to="half_open"}') >= 1
        assert _metric_value(text, series + ',to="closed"}') >= 1
    finally:
        r.stop()
        a.stop()


def test_heartbeat_metrics_under_drop_chaos():
    """heartbeat_drop chaos suppresses sends: the dropped counter grows
    while consecutive_failures stays 0 (drops are not failures); a
    genuinely failing reporter walks the failure gauge up and latches
    reporter_dead — each step visible in /metrics."""
    from kubeflow_tpu.runtime.heartbeat import HeartbeatReporter
    from kubeflow_tpu.runtime.rendezvous import PyCoordinatorServer

    srv = PyCoordinatorServer(hb_ttl_s=5.0)
    script = generate_fault_script(FaultScriptConfig(
        seed=11, duration_s=10.0,
        faults=(FaultSpec("heartbeat_drop", 1, (0.0, 0.0),
                          (0.6, 0.6)),)), name="drop")
    inj = FaultInjector(script)
    inj.start()
    text0 = render_metrics()
    dropped0 = _metric_value(
        text0, 'heartbeat_events_total{event="dropped"}') or 0
    hb = HeartbeatReporter(srv.address, "hb-obs", 1, 0, "10.0.0.1:5000",
                           0.15, max_consecutive_failures=2,
                           injector=inj)
    try:
        deadline = time.monotonic() + 10
        while hb.dropped < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert hb.dropped >= 2, "no beats dropped"
        text = render_metrics()
        assert _metric_value(
            text, 'heartbeat_events_total{event="dropped"}') \
            >= dropped0 + 2
        assert _metric_value(text, "heartbeat_consecutive_failures") == 0
        assert _metric_value(text, "heartbeat_reporter_dead") == 0

        def always_fail(gang, rank):
            raise ConnectionResetError("injected: coordinator gone")

        hb._client.heartbeat = always_fail
        deadline = time.monotonic() + 10
        while not hb.reporter_dead and time.monotonic() < deadline:
            time.sleep(0.02)
        assert hb.reporter_dead
        text = render_metrics()
        assert _metric_value(text, "heartbeat_reporter_dead") == 1
        assert _metric_value(text, "heartbeat_consecutive_failures") >= 2
        failed = _metric_value(
            text, 'heartbeat_events_total{event="failed"}')
        assert failed and failed >= 2
    finally:
        hb.stop(mark_done=False)
        srv.stop()
