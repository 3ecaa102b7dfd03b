"""THE serving-plane instrument set (ISSUE 17 tentpole, piece 2).

One process-wide registry — ``utils.metrics.REGISTRY``, the same object
the control plane's reconciler instruments live in — and every
serving-layer metric NAME declared in this module, nowhere else
(scripts/check_observability.py enforces it: ad-hoc
``registry.counter("...")`` calls outside the central modules are lint
findings). Engine, supervisor, router, radix cache, heartbeat and
scheduler import instruments from here; ``render_metrics()`` is the one
scrape path ``GET /metrics`` serves on ModelServer AND the router.

Naming convention (docs/ARCHITECTURE.md "Observability"):
``<plane>_<noun>_<unit|total>`` with the component/event split carried
by labels, not name proliferation — e.g. every lifecycle event of every
layer is ``serving_requests_total{component=,event=}``.

Pull-model gauges (queue depth, circuit state, SLO burn) come from
SCRAPE HOOKS: live objects register a callback that refreshes their
gauges just before each render. Hooks hold a weakref to their owner so
a closed-but-not-deregistered engine can never keep itself alive or
poison later scrapes.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any, Callable

from kubeflow_tpu.utils.metrics import REGISTRY, Registry  # noqa: F401

# -- explicit latency buckets (seconds) ---------------------------------------
# TTFT spans queue+prefill: sub-10ms cache hits through multi-second
# cold chunked prefills. TPOT is per-token: sub-ms kernel steps through
# ~1s interpret-mode smoke steps. Queue-wait shares TTFT's shape but
# needs the sub-ms floor for idle-engine admissions.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                5.0, 10.0, 30.0)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5)
QUEUE_WAIT_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                      5.0, 30.0)

# -- request lifecycle (every layer, one name) --------------------------------
REQUESTS = REGISTRY.counter(
    "serving_requests_total",
    "Request lifecycle events across serving layers",
    ["component", "event"])
TTFT_SECONDS = REGISTRY.histogram(
    "serving_ttft_seconds", "Submit to first token", ["component"],
    buckets=TTFT_BUCKETS)
TPOT_SECONDS = REGISTRY.histogram(
    "serving_tpot_seconds", "Per-token decode latency (per request)",
    ["component"], buckets=TPOT_BUCKETS)
QUEUE_WAIT_SECONDS = REGISTRY.histogram(
    "serving_queue_wait_seconds", "Submit to prefill dispatch",
    ["component"], buckets=QUEUE_WAIT_BUCKETS)
INFLIGHT = REGISTRY.gauge(
    "serving_inflight", "Live requests per component", ["component"])

# -- HTTP frontends -----------------------------------------------------------
HTTP_REQUESTS = REGISTRY.counter(
    "serving_http_requests_total", "Frontend requests by model and verb",
    ["model", "verb"])
HTTP_LATENCY = REGISTRY.histogram(
    "serving_http_request_seconds", "Frontend request wall",
    ["model", "verb"])
MODEL_READY = REGISTRY.gauge(
    "serving_model_ready", "1 = model loaded and ready", ["model"])
MODEL_LOAD_SECONDS = REGISTRY.histogram(
    "serving_model_load_seconds", "Model load() wall", ["model"])

# -- router -------------------------------------------------------------------
#: closed=0, half_open=1, open=2 (ordered by escalation)
CIRCUIT_STATE_CODES = {"closed": 0, "half_open": 1, "open": 2}
CIRCUIT_STATE = REGISTRY.gauge(
    "router_circuit_state",
    "Per-backend breaker state (0=closed 1=half_open 2=open)",
    ["backend"])
CIRCUIT_TRANSITIONS = REGISTRY.counter(
    "router_circuit_transitions_total",
    "Breaker state entries by target state", ["backend", "to"])

# -- supervisor ---------------------------------------------------------------
SUPERVISOR_RESTARTS = REGISTRY.counter(
    "supervisor_restarts_total", "Engine restarts by detected cause",
    ["cause"])

# -- kv/prefix cache ----------------------------------------------------------
PREFIX_EVENTS = REGISTRY.counter(
    "kvcache_prefix_events_total",
    "Radix prefix-cache events (hit/miss/insert/evict)", ["event"])
KV_FREE_BLOCKS = REGISTRY.gauge(
    "kvcache_free_blocks",
    "Allocatable KV blocks currently free (paged: pool free list; "
    "slab: radix store headroom)", ["engine"])
KV_WATERMARK_FRAC = REGISTRY.gauge(
    "kvcache_watermark_frac",
    "Free fraction of allocatable KV capacity (the paged admission "
    "signal: 1.0 = empty, 0.0 = fully committed)", ["engine"])

# -- heartbeat ----------------------------------------------------------------
HEARTBEAT_EVENTS = REGISTRY.counter(
    "heartbeat_events_total", "Reporter sends by outcome "
    "(sent/failed/dropped)", ["event"])
HEARTBEAT_CONSECUTIVE_FAILURES = REGISTRY.gauge(
    "heartbeat_consecutive_failures",
    "Consecutive failed sends of the live reporter", [])
HEARTBEAT_REPORTER_DEAD = REGISTRY.gauge(
    "heartbeat_reporter_dead", "1 = reporter exhausted its retry budget",
    [])

# -- attention impls (scrape-hook fed) ----------------------------------------
ATTENTION_IMPL = REGISTRY.gauge(
    "serving_attention_impl_info",
    "Resolved attention impl per engine phase (info-style: one series "
    "per (engine, phase=prefill|decode, impl=xla|flash), value 1)",
    ["engine", "phase", "impl"])

# -- a model family's decode-step counts (scrape-hook fed) --------------------
ENGINE_STEP_COUNT = REGISTRY.gauge(
    "engine_step_counter",
    "What the served family's decode steps count (its STEP_COUNTERS: the "
    "routed experts' assignments, expert visits, dropped rows, load, the "
    "latent context rows read, the recurrent states updated), folded over "
    "the chunks replayed so far, its prompt_counters (the prompt tokens "
    "through the state-space scans) and its cache_stats (the bytes of "
    "each kind of cache)",
    ["engine", "name"])

# -- scheduler (scrape-hook fed) ----------------------------------------------
SCHED_QUEUED = REGISTRY.gauge(
    "scheduler_queued", "Requests waiting for admission", ["engine"])
SCHED_ACTIVE = REGISTRY.gauge(
    "scheduler_active", "Requests holding decode slots", ["engine"])
SCHED_SHED = REGISTRY.counter(
    "scheduler_shed_total", "Requests shed by degraded-mode policy",
    ["engine"])

# -- engine thread phases (scrape-hook fed from the engine's PhaseClock) ------
ENGINE_PHASE_SECONDS = REGISTRY.counter(
    "serving_engine_phase_seconds_total",
    "Engine-thread wall per phase (obs.trace.PHASES partitions the "
    "thread's timeline)", ["engine", "phase"])
ENGINE_PHASE_CPU_SECONDS = REGISTRY.counter(
    "serving_engine_phase_cpu_seconds_total",
    "The engine thread's own CPU time per phase: the phase's wall less "
    "this is what the thread waited (device, runtime queue, interpreter "
    "or OS, by phase)", ["engine", "phase"])
ENGINE_DEVICE_EMPTY_SECONDS = REGISTRY.counter(
    "serving_engine_device_empty_seconds_total",
    "Wall with nothing dispatched and unfetched, by the phase the engine "
    "thread was in: summed over phase, a floor under the device's idle "
    "time", ["engine", "phase"])
ENGINE_KV_BLOCKS_FETCHED = REGISTRY.counter(
    "serving_engine_kv_blocks_fetched_total",
    "KV blocks the decode dispatches' slot lengths let the attention "
    "kernel copy", ["engine"])
ENGINE_KV_BLOCKS_SPANNED = REGISTRY.counter(
    "serving_engine_kv_blocks_spanned_total",
    "KV blocks the decode dispatches' attention grids covered (slots x "
    "span / block)", ["engine"])
ENGINE_STALLS = REGISTRY.counter(
    "serving_engine_stalls_total",
    "Single phase occurrences of 500 ms or more (idle only when it "
    "began with work queued or active)",
    ["engine", "phase"])

# -- what stops every thread (obs.trace.GC, one gc.callbacks hook) ------------
GC_PAUSE_SECONDS = REGISTRY.counter(
    "process_gc_pause_seconds_total",
    "Wall the collector held the interpreter, by generation",
    ["generation"])
GC_PAUSE_MAX_SECONDS = REGISTRY.gauge(
    "process_gc_pause_max_seconds",
    "The longest single collection since the process started", [])

# -- SLO burn (scrape-hook fed from SloBurnTracker) ---------------------------
SLO_ATTAINMENT = REGISTRY.gauge(
    "slo_attainment", "Windowed SLO attainment per tenant", ["tenant"])
SLO_BURN_RATE = REGISTRY.gauge(
    "slo_burn_rate",
    "Windowed error-budget burn multiplier per tenant (1.0 = burning "
    "exactly the budget)", ["tenant"])

# -- tracing self-observation -------------------------------------------------
TRACE_BUFFER_SPANS = REGISTRY.gauge(
    "trace_buffer_spans", "Spans currently held in the ring buffer", [])
TRACE_SPANS_DROPPED = REGISTRY.gauge(
    "trace_spans_dropped_total", "Spans evicted from the full ring "
    "buffer since last clear", [])

# -- scrape hooks -------------------------------------------------------------

_hooks_lock = threading.Lock()
_hooks: list[tuple[weakref.ref, Callable[[Any], None]]] = []


def add_scrape_hook(owner: Any, fn: Callable[[Any], None]) -> None:
    """Refresh-before-render callback: ``fn(owner)`` runs on every
    ``render_metrics()``. Held via weakref to ``owner`` — when the owner
    is collected the hook silently unregisters, so short-lived engines
    in tests cannot accumulate."""
    with _hooks_lock:
        _hooks.append((weakref.ref(owner), fn))


def remove_scrape_hooks(owner: Any) -> None:
    with _hooks_lock:
        _hooks[:] = [(r, f) for r, f in _hooks if r() is not owner]


def run_scrape_hooks() -> None:
    with _hooks_lock:
        live = [(r, f) for r, f in _hooks if r() is not None]
        _hooks[:] = live
        snapshot = list(live)
    for ref, fn in snapshot:
        owner = ref()
        if owner is None:
            continue
        try:
            fn(owner)
        except Exception:
            # a dying component must not take the scrape down with it
            pass


def render_metrics() -> str:
    """THE scrape path: refresh pull-model gauges, then render the one
    process registry as Prometheus text."""
    from kubeflow_tpu.obs.trace import GC, TRACER

    run_scrape_hooks()
    GC.publish()
    TRACE_BUFFER_SPANS.set(len(TRACER.sink))
    TRACE_SPANS_DROPPED.set(TRACER.sink.dropped)
    return REGISTRY.render()
